"""The two-layer configuration: its reference, its tiny cell on the CPU and
the faults its checks must catch."""

import contextlib
import functools
import importlib
import io
import json
import os

import numpy as np
import pytest

from bench import control
from bench import run as brun
from bench.harness import correct, reference, reference_2l
from bench.tests import tiny

N_ANT = 2


def _load(*parts):
    with open(os.path.join(tiny.REPO, *parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """The tiny cell at 2 RX and two layers: the x5g_106prb_2l
    configuration's radio and reference, its tree pinned anew."""
    root = tiny.make_root(str(tmp_path_factory.mktemp("bench_2l")),
                          n_ant=N_ANT)
    two = _load("bench", "configs", "x5g_106prb_2l.json")
    path = os.path.join(root, "bench", "configs", "tiny.json")
    cfg = json.load(open(path))
    cfg.update({k: two[k] for k in ("n_layers", "dmrs_ports", "tpmi",
                                    "dmrs_cdm_groups_without_data",
                                    "reference")})
    json.dump(cfg, open(path, "w"))
    lim_path = os.path.join(root, "bench", "limits", f"{tiny.CELL}.json")
    lim = json.load(open(lim_path))
    lim["tree"] = tiny.pin_tree(root)
    json.dump(lim, open(lim_path, "w"))
    return root


@pytest.fixture(scope="module")
def recorded(root):
    """One run of the tiny two-layer cell, and its context."""
    args = brun.parse(["--workload", tiny.CELL, "--seed", str(2**31 + 41),
                       "--seconds", "2"])
    ctx = brun.make_context(args, root, tiny.cpu_device)
    driver = importlib.import_module(
        f"bench.harness.{ctx.traffic['driver']}")
    return ctx, driver.run(ctx)


def test_two_layer_cell_is_correct_under_every_limit(root):
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = brun.main(["--workload", tiny.CELL, "--seed", str(2**31 + 42),
                        "--seconds", "2", "--trace", "0"],
                       root=root, device_fn=tiny.cpu_device)
    assert rc == 0
    line = json.loads(buf.getvalue().strip().splitlines()[-1])
    assert line["correct"], line["checks"]
    limits = json.load(open(os.path.join(
        root, "bench", "limits", f"{tiny.CELL}.json")))["limits"]
    assert set(line["checks"]) == set(limits)


def test_both_experts_serve_in_the_checked_sample(recorded):
    ctx, run = recorded
    g = correct.gaps(ctx, run)
    assert "snr_gap_db.ai" in g and "snr_gap_db.mmse" in g, g


@pytest.mark.parametrize("fault", ["mrc_layer0", "swap_layers", "no_occ"])
def test_planted_receiver_fault_fails_the_checks(recorded, monkeypatch,
                                                 fault):
    """The reference with a receiver fault in the program's place, on the
    program's recorded history: at least one number exceeds its limit."""
    ctx, run = recorded
    monkeypatch.setattr(reference, "reference_for", lambda config: (
        functools.partial(reference_2l.SlotReference, fault=fault)))
    g = control.control_gaps(ctx, run, "f64")
    lim = ctx.limits["limits"]
    assert any(g.get(k, np.inf) > v for k, v in lim.items()), g


def test_reference_2l_refuses_other_layer_counts():
    cfg = _load("bench", "configs", "x5g_106prb_2l.json")
    traffic = _load("bench", "traffic", "slot_gpg.json")
    assert reference.reference_for(cfg) is reference_2l.SlotReference
    reference_2l.SlotReference(cfg, traffic, 0, {})
    for n in (1, 3):
        with pytest.raises(ValueError, match=f"n_layers={n}"):
            reference_2l.SlotReference(dict(cfg, n_layers=n), traffic, 0, {})
    with pytest.raises(ValueError, match="n_layers=2"):
        reference.SlotReference(cfg, traffic, 0, {})
    with pytest.raises(ValueError, match="fault"):
        reference_2l.SlotReference(cfg, traffic, 0, {}, fault="other")
