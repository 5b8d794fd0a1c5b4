"""A whole run of the harness on the CPU at a tiny size, with the chip
check skipped: correct as it stands, not correct with the timed path
broken underneath, and not correct with the control in the program's
place."""

import contextlib
import importlib
import io
import json

import numpy as np
import pytest

from bench import control
from bench import run as brun
from bench.harness import correct, program
from bench.tests import tiny

SECONDS = "2"


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(str(tmp_path_factory.mktemp("bench")))


@pytest.fixture(scope="module")
def x5g_expert_root(tmp_path_factory):
    """The tiny cell with the x5g configuration's AI expert (32 channels,
    4 residual blocks), whose correction the AI limits are set for."""
    return tiny.make_root(str(tmp_path_factory.mktemp("bench_ai")),
                          expert=(32, 4))


def run_line(root, seed, monkeypatch=None, fault=None, session_fault=None):
    if session_fault is not None:
        real_build = program.build_session

        def build(config, traffic, weights, host_policies=None):
            return session_fault(real_build, config, traffic, weights,
                                 host_policies)

        monkeypatch.setattr(program, "build_session", build)
    if fault is not None:
        real = program.slot_program

        def broken(session, seed):
            prog = real(session, seed)
            step = prog.step

            def bad(link, sw, slot_idx, p):
                return fault(step, link, sw, slot_idx, p)

            prog.step = bad
            return prog

        monkeypatch.setattr(program, "slot_program", broken)
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = brun.main(["--workload", tiny.CELL, "--seed", str(seed),
                        "--seconds", SECONDS, "--trace", "0"],
                       root=root, device_fn=tiny.cpu_device)
    assert rc == 0
    return json.loads(buf.getvalue().strip().splitlines()[-1])


def fails(line, name):
    c = line["checks"][name]
    return c["value"] is None or c["value"] > c["limit"]


def test_run_is_correct_and_reports_its_metrics(root):
    line = run_line(root, 2**31 + 11)
    assert line["correct"], line["checks"]
    assert set(line) >= {"correct", "attempted", "failed", "metrics",
                         "device", "checks"}
    assert list(line)[-1] == "checks"
    assert set(line["metrics"]) == {"slot_us", "slot_ues_per_s", "setup_s"}
    assert line["failed"] == 0 and line["attempted"] > 0


def _state_unchanged(step, link, sw, slot_idx, p):
    _, _, out = step(link, sw, slot_idx, p)
    return link, sw, out


def _half_batch(step, link, sw, slot_idx, p):
    import jax
    import jax.numpy as jnp

    link, sw, out = step(link, sw, slot_idx, p)
    half = out["tb_ok"].shape[0] // 2

    def fold(x):
        return jnp.concatenate([x[:half], x[:half]])[: x.shape[0]]

    return link, sw, jax.tree.map(fold, out)


def _answer_altered(step, link, sw, slot_idx, p):
    link, sw, out = step(link, sw, slot_idx, p)
    out["kpms"]["oai"]["snr"] = out["kpms"]["oai"]["snr"] + 0.1
    return link, sw, out


def _decision_altered(step, link, sw, slot_idx, p):
    link, sw, out = step(link, sw, slot_idx, p)
    out["raw_decision"] = out["raw_decision"].at[0].set(
        1 - out["raw_decision"][0])
    return link, sw, out


@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch,
                                   _answer_altered, _decision_altered])
def test_broken_timed_path_is_not_correct(root, monkeypatch, fault):
    line = run_line(root, 2**31 + 12, monkeypatch, fault)
    assert not line["correct"], line["checks"]


def _mmse_for_ai(step, link, sw, slot_idx, p):
    """UEs the register gives the AI expert are served by MMSE; the modes
    and the overflow are reported as the register has them."""
    import jax.numpy as jnp

    _, _, real = step(link, sw, slot_idx, p)
    link, sw_m, out = step(link, sw._replace(
        active_mode=jnp.ones_like(sw.active_mode)), slot_idx, p)
    out = dict(out, active_mode=real["active_mode"],
               gated_overflow=real["gated_overflow"])
    return link, sw_m, out


def _skip_cnn(build, config, traffic, weights, host_policies):
    """The program's AI expert without its CNN (a zero head: the comb-2
    baseline alone); the reference keeps the run's weights."""
    import jax

    no_head = dict(jax.tree.map(lambda x: x, weights),
                   head_w=weights["head_w"] * 0, head_b=weights["head_b"] * 0)
    return build(config, traffic, no_head, host_policies)


def test_wrong_ai_answer_is_not_correct(x5g_expert_root, monkeypatch):
    """Both wrong experts for the AI-served slot-UEs fail the AI RSRP
    check: MMSE in the AI expert's place, and the CNN skipped."""
    line = run_line(x5g_expert_root, 2**31 + 14, monkeypatch,
                    fault=_mmse_for_ai)
    assert fails(line, "rsrp_rel_gap.ai"), line["checks"]
    monkeypatch.undo()
    line = run_line(x5g_expert_root, 2**31 + 14, monkeypatch,
                    session_fault=_skip_cnn)
    assert fails(line, "rsrp_rel_gap.ai"), line["checks"]
    assert not line["correct"]


def _tree_moved(scale: float, leaves: bool):
    """A session whose fitted tree has every split's threshold times
    ``scale``, or its leaves' modes swapped."""
    def fault(build, config, traffic, weights, host_policies):
        import dataclasses

        from repro.core.policy import DecisionTreePolicy

        session = build(config, traffic, weights, host_policies)
        fitted = session.host_policies[0]
        t = fitted.tree
        t = dataclasses.replace(
            t, threshold=t.threshold * scale,
            leaf_values=(1 - t.leaf_values) if leaves else t.leaf_values)
        session._host_policies = (DecisionTreePolicy(
            t, fitted.feature_names),)
        return session
    return fault


@pytest.mark.parametrize("fault", [_tree_moved(1.01, False),
                                   _tree_moved(1.0, True)],
                         ids=["threshold", "leaves"])
def test_other_policy_than_pinned_is_not_correct(root, monkeypatch, fault):
    line = run_line(root, 2**31 + 15, monkeypatch, session_fault=fault)
    assert not line["correct"], line["checks"]


def test_control_is_not_correct(tmp_path):
    """The control (the reference with int8 estimator matmuls in the
    program's place) fails one of the cell's numbers under the x5g
    limits, at the x5g width (106 PRB) with two UEs: the Wiener matrix of
    a narrow carrier has too few small weights for int8 to lose."""
    wide = tiny.make_root(str(tmp_path), n_prb=106, n_ues=2)
    args = brun.parse(["--workload", tiny.CELL, "--seed", str(2**31 + 13),
                       "--seconds", SECONDS])
    ctx = brun.make_context(args, wide, tiny.cpu_device)
    driver = importlib.import_module(
        f"bench.harness.{ctx.traffic['driver']}")
    run = driver.run(ctx)
    sound = correct.gaps(ctx, run)
    int8 = control.control_gaps(ctx, run, "int8")
    lim = ctx.limits["limits"]
    assert all(sound[k] <= v for k, v in lim.items()), sound
    assert any(int8.get(k, np.inf) > v for k, v in lim.items()), int8
