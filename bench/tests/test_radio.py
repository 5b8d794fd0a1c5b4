"""A configuration's receive antennas, layers and reference reach the run:
the spec's keywords, the reference the configuration names, and the tiny
cell at two receive antennas."""

import contextlib
import dataclasses
import importlib
import io
import json
import os

import pytest

from bench import run as brun
from bench.harness import correct, program, reference
from bench.tests import tiny

N_ANT = 2


@contextlib.contextmanager
def program_at(n_ant: int):
    """The program's slot at ``n_ant`` receive antennas, as a spec that
    carries the configuration's ``n_ant`` gives it."""
    from repro.core.session import ArchesSession

    real = ArchesSession.__init__

    def init(self, spec, *args, **kwargs):
        real(self, spec, *args, **kwargs)
        self.cfg = dataclasses.replace(self.cfg, n_ant=n_ant)

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(ArchesSession, "__init__", init)
        yield


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    with program_at(N_ANT):
        return tiny.make_root(str(tmp_path_factory.mktemp("bench_2rx")),
                              n_ant=N_ANT)


def test_two_antenna_cell_is_correct_under_every_limit(root):
    buf = io.StringIO()
    with program_at(N_ANT), contextlib.redirect_stdout(buf):
        rc = brun.main(["--workload", tiny.CELL, "--seed", str(2**31 + 21),
                        "--seconds", "2", "--trace", "0"],
                       root=root, device_fn=tiny.cpu_device)
    assert rc == 0
    line = json.loads(buf.getvalue().strip().splitlines()[-1])
    assert line["correct"], line["checks"]
    limits = json.load(open(os.path.join(
        root, "bench", "limits", f"{tiny.CELL}.json")))["limits"]
    assert set(line["checks"]) == set(limits)
    assert all(c["value"] <= c["limit"] for c in line["checks"].values())


def test_named_default_reference_gives_the_same_checks(root):
    args = brun.parse(["--workload", tiny.CELL, "--seed", str(2**31 + 22),
                       "--seconds", "2"])
    ctx = brun.make_context(args, root, tiny.cpu_device)
    assert "reference" not in ctx.config
    driver = importlib.import_module(
        f"bench.harness.{ctx.traffic['driver']}")
    with program_at(N_ANT):
        run = driver.run(ctx)
    named = dataclasses.replace(ctx, config=dict(ctx.config,
                                                 reference="reference"))
    assert correct.gaps(named, run) == correct.gaps(ctx, run)


def test_reference_for_defaults_to_the_base_reference():
    base = reference.SlotReference
    assert reference.reference_for({}) is base
    assert reference.reference_for({"reference": "reference"}) is base


@pytest.mark.parametrize("name", ["no_such_reference", "tree", "../x", 3])
def test_unknown_reference_fails_in_make_context(tmp_path, name):
    """No module, a module without ``SlotReference``, no module name: each
    is refused, naming the key, before the chip is looked for."""
    bench = json.load(open(os.path.join(tiny.REPO, "BENCHMARK.json")))
    cfg = json.load(open(os.path.join(tiny.REPO, "bench", "configs",
                                      "x5g_106prb.json")))
    os.makedirs(tmp_path / "bench" / "configs")
    with open(tmp_path / "bench" / "configs" / "x5g_106prb.json", "w") as f:
        json.dump(dict(cfg, reference=name), f)
    with open(tmp_path / "BENCHMARK.json", "w") as f:
        json.dump(bench, f)

    def no_chip(n):
        raise AssertionError("the chip was looked for")

    args = brun.parse(["--workload", "x5g.slot.gpg", "--seed", "1",
                       "--seconds", "1"])
    with pytest.raises(ValueError, match='"reference"'):
        brun.make_context(args, str(tmp_path), no_chip)


def test_base_reference_refuses_more_than_one_layer():
    cfg = json.load(open(os.path.join(tiny.REPO, "bench", "configs",
                                      "x5g_106prb.json")))
    traffic = json.load(open(os.path.join(tiny.REPO, "bench", "traffic",
                                          "slot_gpg.json")))
    reference.SlotReference(cfg, traffic, 0, {})
    with pytest.raises(ValueError, match="n_layers=2"):
        reference.SlotReference(dict(cfg, n_layers=2), traffic, 0, {})


@dataclasses.dataclass(frozen=True)
class _RadioSpec:
    n_prb: int = 24
    n_ant: int = 4
    n_layers: int = 1


@dataclasses.dataclass(frozen=True)
class _NoRadioSpec:
    n_prb: int = 24


def test_build_session_passes_the_radio_a_spec_declares():
    cfg = {"n_prb": 106, "n_ant": 8, "n_layers": 2}
    assert program.radio_kwargs(cfg, _RadioSpec) == {"n_ant": 8,
                                                     "n_layers": 2}
    assert program.radio_kwargs(cfg, _NoRadioSpec) == {}
    from repro.core.session import CampaignSpec

    passed = program.radio_kwargs(cfg, CampaignSpec)
    assert all(getattr(CampaignSpec(**passed), k) == v
               for k, v in passed.items())
