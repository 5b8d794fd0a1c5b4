"""Fused gated hot path: one kernel == the unfused triple.

Three layers of the tentpole contract:

* **kernel** — ``gated_expert_apply`` matches the unfused gather ->
  folded-GEMM -> scatter composition across the gating edge cases:
  all-AI, all-MMSE, U == 1, odd U, capacity 1, exact-capacity boundary,
  padding rows.  The jnp reference backend is bitwise; the Pallas kernel
  (interpret mode) is bitwise equal to its own forward in plain jnp
  (``forward_2d_ref`` over the same views), batch-composition-free on
  served UEs, and agrees with the XLA forward to f32 rounding (its 2-D
  per-antenna GEMMs are what Mosaic compiles).
* **bank** — the ``gated_fused_apply`` hook slots into ``ExpertBank`` (3+
  expert banks included) without changing any output or accounting leaf;
  the in-scan NMSE audit trips on divergent outputs (adversarial inputs,
  NaN/inf) and reverts tripped UEs to the fail-safe baseline while still
  charging the executed FLOPs.
* **engine** — ``BatchedPuschPipeline(fused_gated=True)`` campaigns are
  bitwise-equal to unfused gated campaigns on *every* trajectory leaf
  (cost accounting included), open- and closed-loop, and on a forced
  8-shard mesh (subprocess) with the no-collective HLO audit.  The bf16
  expert variant (``expert_dtype="bfloat16"``) is NOT bitwise — its
  audit + fail-safe behaviour is asserted instead.

Exact-capacity boundary coverage (the overflow-audit satellite): when the
number of selected UEs equals the capacity, no UE may be flagged as
overflow and the K'th selected UE must be served by the AI expert — at the
bank, the executed-cost accounting, and the ``BatchedRunHistory`` layers.
"""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.expert_bank import ExecutionMode, Expert, ExpertBank
from repro.core.runtime import BatchedRunHistory
from repro.core.telemetry import physical_trajectory
from repro.kernels.gated_expert import gated_expert_apply, gated_expert_apply_ref
from repro.kernels.gated_expert import ops as gated_ops
from repro.kernels.gated_expert.gated_expert import forward_2d_ref, kernel_params
from repro.kernels.switch_select.ref import switch_gather_batched_tree_ref
from repro.phy.ai_estimator import (
    AiEstimatorConfig,
    ai_estimate_folded,
    fold_ai_params,
    init_params,
)
from repro.phy.estimators import estimator_flops
from repro.phy.nr import SlotConfig
from repro.phy.pipeline import BatchedPuschPipeline
from repro.phy.scenario import GOOD, constant_schedule, good_poor_good_schedule

CFG = SlotConfig(n_prb=24)
NET = AiEstimatorConfig(channels=8, n_res_blocks=1)


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.PRNGKey(0), CFG, NET)


@pytest.fixture(scope="module")
def folded(params):
    return fold_ai_params(params, CFG.n_dmrs_sym)


def _assert_tree_equal(a, b):
    jax.tree.map(
        lambda x, y: np.testing.assert_array_equal(np.asarray(x), np.asarray(y)),
        a,
        b,
    )


def _mk_inputs(seed: int, n_ues: int):
    """Random LS input + baseline in the engine's layout contract."""
    k1, k2, k3, k4 = jax.random.split(jax.random.PRNGKey(seed), 4)
    ls_shape = (n_ues, CFG.n_ant, CFG.n_dmrs_sym, CFG.n_pilot_sc)
    des_shape = (n_ues, CFG.n_ant, 1, CFG.n_sc, CFG.n_dmrs_sym)
    h_ls = (jax.random.normal(k1, ls_shape)
            + 1j * jax.random.normal(k2, ls_shape)).astype(jnp.complex64)
    des = (jax.random.normal(k3, des_shape)
           + 1j * jax.random.normal(k4, des_shape)).astype(jnp.complex64)
    return h_ls, des


def _gating(mode: np.ndarray, capacity: int):
    """Replicate ``ExpertBank._run_gated``'s stable compaction plan."""
    is_gated = np.asarray(mode) == 0
    pos = np.cumsum(is_gated.astype(np.int32)) - 1
    within = is_gated & (pos < capacity)
    src = np.where(within, pos, -1).astype(np.int32)
    order = np.argsort(np.logical_not(is_gated).astype(np.int32),
                       kind="stable")
    idx = order[:capacity].astype(np.int32)
    return jnp.asarray(idx), jnp.asarray(src)


# -- kernel: fused == unfused composition -------------------------------------


EDGE_CASES = [
    # (n_ues, capacity, mode vector): the gating edge-case grid
    (6, 3, [0, 1, 0, 0, 1, 1]),   # exact boundary: selected == capacity
    (6, 6, [0] * 6),              # all-AI, full capacity
    (6, 2, [1] * 6),              # all-MMSE: only padding rows
    (1, 1, [0]),                  # single UE, served
    (1, 1, [1]),                  # single UE, kept
    (5, 1, [1, 0, 1, 0, 1]),      # odd U, capacity 1, one overflow
    (3, 3, [1, 0, 1]),            # padding rows past the one selected UE
]


#: f32 agreement of the kernel's per-antenna forward with the XLA path's
#: batched GEMMs, relative to the largest output magnitude: the two sum the
#: same products in different blockings (~1e-7 apart at this width)
F32_REL_TOL = 1e-5


def _forward_2d_twin(src, h_ls, des, folded):
    """The fused kernel's result built in plain jnp: ``forward_2d_ref`` on
    every served (UE, antenna) tile of the kernel's own views, the
    baseline bytes everywhere else.  Run op by op, as interpret mode runs
    the kernel body: under ``jit`` XLA's fusions round some sums one ulp
    differently."""
    n_sym, n_p = h_ls.shape[2:]
    kp = kernel_params(folded)
    x = gated_ops._ls_view(h_ls)
    out = gated_ops._estimate_view(des, n_p)
    for u in np.flatnonzero(np.asarray(src) >= 0):
        for a in range(h_ls.shape[1]):
            out = out.at[u, a].set(jnp.stack(forward_2d_ref(kp, x[u, a], n_p)))
    return gated_ops._from_estimate_view(out, n_p, n_sym)


@pytest.mark.parametrize("n_ues,capacity,mode", EDGE_CASES)
def test_fused_kernel_matches_unfused_bitwise(folded, n_ues, capacity, mode):
    """The ``ref`` backend is the unfused triple bitwise, and the Pallas
    kernel (interpret mode) is its plain-jnp twin bitwise: kept UEs keep
    their baseline bytes, served UEs get ``forward_2d_ref``'s.  Besides,
    every served UE gets bitwise the bytes a capacity-1 call on that UE
    alone gives (no batch dependence), and the kernel matches the unfused
    XLA forward to f32 rounding."""
    h_ls, des = _mk_inputs(n_ues * 10 + capacity, n_ues)
    idx, src = _gating(np.asarray(mode, np.int32), capacity)

    # the unfused triple, composed by hand
    compact_out = ai_estimate_folded(folded, jnp.take(h_ls, idx, axis=0))
    want = switch_gather_batched_tree_ref(src, compact_out, des)

    ref = gated_expert_apply(idx, src, h_ls, des, folded, backend="ref")
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(want))

    fused = np.asarray(gated_expert_apply(
        idx, src, h_ls, des, folded, backend="pallas", interpret=True
    ))
    np.testing.assert_array_equal(
        fused, np.asarray(_forward_2d_twin(src, h_ls, des, folded))
    )
    want = np.asarray(want)
    served = np.flatnonzero(np.asarray(src) >= 0)
    kept = np.flatnonzero(np.asarray(src) < 0)
    # kept UEs round-trip the baseline bytes untouched
    np.testing.assert_array_equal(fused[kept], np.asarray(des)[kept])
    if served.size:
        scale = np.abs(want[served]).max()
        np.testing.assert_allclose(
            fused[served], want[served], rtol=0, atol=F32_REL_TOL * scale
        )
    for u in served:
        # non-vacuous: served UEs actually received the expert's output
        assert not np.array_equal(fused[u], np.asarray(des)[u])
        alone = gated_expert_apply(
            jnp.asarray([u], jnp.int32),
            jnp.where(jnp.arange(n_ues) == u, 0, -1).astype(jnp.int32),
            h_ls, des, folded, backend="pallas", interpret=True,
        )
        np.testing.assert_array_equal(fused[u], np.asarray(alone)[u])


@pytest.mark.parametrize("n_ues,capacity,mode", EDGE_CASES[:3])
def test_fused_kernel_bf16_backends_agree(folded, n_ues, capacity, mode):
    """bf16 is not bitwise vs f32, but ref and Pallas backends must agree
    with each other, and kept UEs stay bitwise-untouched."""
    h_ls, des = _mk_inputs(7, n_ues)
    idx, src = _gating(np.asarray(mode, np.int32), capacity)
    kw = dict(compute_dtype=jnp.bfloat16)
    ref = gated_expert_apply(idx, src, h_ls, des, folded, backend="ref", **kw)
    fused = gated_expert_apply(
        idx, src, h_ls, des, folded, backend="pallas", interpret=True, **kw
    )
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(fused))
    kept = np.flatnonzero(np.asarray(src) < 0)
    for u in kept:
        np.testing.assert_array_equal(np.asarray(fused)[u], np.asarray(des)[u])
    served = np.flatnonzero(np.asarray(src) >= 0)
    if served.size:
        f32 = gated_expert_apply(idx, src, h_ls, des, folded, backend="ref")
        # reduced precision genuinely reduced: some served value moved
        assert not np.array_equal(
            np.asarray(fused)[served], np.asarray(f32)[served]
        )
        # ... but not far (sanity bound, not the audit's job)
        np.testing.assert_allclose(
            np.asarray(fused)[served], np.asarray(f32)[served],
            rtol=0.05, atol=0.05,
        )


def test_fused_apply_validates(folded):
    h_ls, des = _mk_inputs(0, 4)
    idx, src = _gating(np.asarray([0, 1, 1, 1], np.int32), 1)
    with pytest.raises(ValueError, match="backend"):
        gated_expert_apply(idx, src, h_ls, des, folded, backend="nope")


# -- bank: fused hook wiring + exact-capacity boundary + audit ----------------


def _toy_bank(**kw):
    experts = [
        Expert(name="ai", fn=lambda p, x: 2.0 * x + 1.0, flops=100.0),
        Expert(name="mmse", fn=lambda p, x: -x, flops=7.0),
    ]
    return ExpertBank(experts, default_mode=1, **kw)


def _toy_fused_hook(fn):
    """A fused hook implemented as the reference composition over ``fn``."""

    def hook(idx, src, base, x):
        compact = fn(None, jnp.take(x, idx, axis=0))
        return switch_gather_batched_tree_ref(src, compact, base)

    return hook


def test_bank_fused_hook_matches_unfused():
    x = jax.random.normal(jax.random.PRNGKey(1), (6, 4, 5))
    hook = _toy_fused_hook(lambda p, x: 2.0 * x + 1.0)
    plain = _toy_bank(execution_mode=ExecutionMode.GATED, gated_capacity=2)
    fused = _toy_bank(
        execution_mode=ExecutionMode.GATED, gated_capacity=2,
        gated_fused_apply=hook,
    )
    for seed in range(4):
        mode = jax.random.randint(jax.random.PRNGKey(seed), (6,), 0, 2)
        op, of = plain(mode, x), fused(mode, x)
        _assert_tree_equal(op.selected, of.selected)
        _assert_tree_equal(op.served_by, of.served_by)
        _assert_tree_equal(op.overflow, of.overflow)
        _assert_tree_equal(op.executed_ue, of.executed_ue)


def test_bank_fused_hook_three_experts():
    """The hook composes with >2 experts: cheap ones stay dense."""
    experts = [
        Expert(name="ai", fn=lambda p, x: 2.0 * x, flops=100.0),
        Expert(name="mmse", fn=lambda p, x: -x, flops=7.0),
        Expert(name="ls", fn=lambda p, x: x + 3.0, flops=1.0),
    ]
    hook = _toy_fused_hook(lambda p, x: 2.0 * x)
    plain = ExpertBank(
        experts, default_mode=1, execution_mode=ExecutionMode.GATED,
        gated_capacity=1,
    )
    fused = ExpertBank(
        experts, default_mode=1, execution_mode=ExecutionMode.GATED,
        gated_capacity=1, gated_fused_apply=hook,
    )
    x = jax.random.normal(jax.random.PRNGKey(2), (6, 9))
    mode = jnp.asarray([0, 2, 1, 0, 2, 1], jnp.int32)
    op, of = plain(mode, x), fused(mode, x)
    _assert_tree_equal(op.selected, of.selected)
    np.testing.assert_array_equal(np.asarray(of.served_by), [0, 2, 1, 1, 2, 1])
    np.testing.assert_array_equal(np.asarray(of.executed_ue), [1, 6, 6])


def test_bank_fused_hook_requires_gated():
    with pytest.raises(ValueError, match="GATED"):
        _toy_bank(gated_fused_apply=lambda *a: None)
    with pytest.raises(ValueError, match="GATED"):
        _toy_bank(audit_threshold=0.5)
    with pytest.raises(ValueError, match="> 0"):
        _toy_bank(execution_mode=ExecutionMode.GATED, audit_threshold=0.0)


@pytest.mark.parametrize("boundary_mode", [
    [0, 0, 0, 1, 1, 1],  # the K selected UEs lead
    [1, 0, 1, 0, 1, 0],  # the K'th selected UE is the *last* UE
    [0, 1, 1, 0, 0, 1],  # mixed
])
def test_bank_exact_capacity_boundary_no_spurious_overflow(boundary_mode):
    """selected == capacity: zero overflow, the K'th UE is served by AI,
    and the executed accounting counts exactly K expert runs."""
    capacity = 3
    mode = jnp.asarray(boundary_mode, jnp.int32)
    assert int((mode == 0).sum()) == capacity  # the boundary premise
    x = jax.random.normal(jax.random.PRNGKey(5), (6, 8))
    bank = _toy_bank(
        execution_mode=ExecutionMode.GATED, gated_capacity=capacity
    )
    out = bank(mode, x)
    np.testing.assert_array_equal(
        np.asarray(out.overflow), np.zeros(6, bool)
    )
    # every selected UE — the K'th included — served by the AI expert
    sel = np.flatnonzero(np.asarray(mode) == 0)
    np.testing.assert_array_equal(np.asarray(out.served_by)[sel], 0)
    np.testing.assert_array_equal(
        np.asarray(out.selected)[sel], np.asarray(2.0 * x + 1.0)[sel]
    )
    np.testing.assert_array_equal(np.asarray(out.executed_ue), [3, 6])
    assert float(bank.executed_flops(out)) == 3 * 100.0 + 6 * 7.0
    per_ue = np.asarray(bank.executed_flops_per_ue(out))
    np.testing.assert_array_equal(per_ue[sel], 107.0)
    # one more selection must overflow exactly one UE (the boundary is tight)
    over = bank(mode.at[int(np.flatnonzero(mode)[0])].set(0), x)
    assert int(np.asarray(over.overflow).sum()) == 1


def test_bank_audit_trips_on_divergent_expert():
    """Adversarial expert output: the audit reverts to the baseline, flags
    the UE, flips served_by to the fail-safe — but still charges the run."""
    experts = [
        Expert(name="ai", fn=lambda p, x: 1e6 * x, flops=100.0),
        Expert(name="mmse", fn=lambda p, x: -x, flops=7.0),
    ]
    bank = ExpertBank(
        experts, default_mode=1, execution_mode=ExecutionMode.GATED,
        gated_capacity=2, audit_threshold=1.0,
    )
    x = jax.random.normal(jax.random.PRNGKey(3), (4, 8))
    mode = jnp.asarray([0, 1, 0, 1], jnp.int32)
    out = bank(mode, x)
    np.testing.assert_array_equal(
        np.asarray(out.audit_tripped), [True, False, True, False]
    )
    # tripped UEs serve the fail-safe baseline, bitwise
    np.testing.assert_array_equal(np.asarray(out.selected), np.asarray(-x))
    np.testing.assert_array_equal(np.asarray(out.served_by), [1, 1, 1, 1])
    np.testing.assert_array_equal(np.asarray(out.overflow), [False] * 4)
    # the expert executed for both tripped UEs: the cost is real
    assert float(bank.executed_flops(out)) == 2 * 100.0 + 4 * 7.0
    per_ue = np.asarray(bank.executed_flops_per_ue(out))
    np.testing.assert_allclose(per_ue, [107.0, 7.0, 107.0, 7.0])


def test_bank_audit_trips_on_nan_output():
    """A diverged (NaN/inf) forward must trip — NMSE comparisons are
    NaN-unsafe unless written trip-by-default."""
    experts = [
        Expert(name="ai", fn=lambda p, x: x * jnp.float32("nan"), flops=1.0),
        Expert(name="mmse", fn=lambda p, x: -x, flops=1.0),
    ]
    bank = ExpertBank(
        experts, default_mode=1, execution_mode=ExecutionMode.GATED,
        audit_threshold=1e6,  # generous — only the NaN can trip it
    )
    x = jnp.ones((3, 4))
    out = bank(jnp.zeros((3,), jnp.int32), x)
    np.testing.assert_array_equal(np.asarray(out.audit_tripped), [True] * 3)
    np.testing.assert_array_equal(np.asarray(out.selected), np.asarray(-x))
    assert np.isfinite(np.asarray(out.selected)).all()


def test_bank_audit_quiet_on_faithful_expert():
    bank = _toy_bank(
        execution_mode=ExecutionMode.GATED, audit_threshold=1e9
    )
    x = jax.random.normal(jax.random.PRNGKey(4), (5, 6))
    mode = jnp.asarray([0, 1, 0, 1, 0], jnp.int32)
    out = bank(mode, x)
    assert not np.asarray(out.audit_tripped).any()
    plain = _toy_bank(execution_mode=ExecutionMode.GATED)
    _assert_tree_equal(out.selected, plain(mode, x).selected)


# -- engine: fused campaigns == unfused, every leaf ---------------------------


def _run_pair(params, modes, *, n_slots, n_ues, **engine_kw):
    sched = good_poor_good_schedule(poor_start=n_slots // 3,
                                    poor_end=2 * n_slots // 3)
    key = jax.random.PRNGKey(9)
    base = dict(net=NET, execution_mode=ExecutionMode.GATED, **engine_kw)
    unfused = BatchedPuschPipeline(CFG, params, **base)
    fused = BatchedPuschPipeline(CFG, params, fused_gated=True, **base)
    _, tu = unfused.run(sched, modes, n_slots=n_slots, n_ues=n_ues, key=key)
    _, tf = fused.run(sched, modes, n_slots=n_slots, n_ues=n_ues, key=key)
    return tu, tf


def test_engine_fused_traces_to_identical_program_off_tpu(params):
    """Off-TPU the fused engine dispatches to the jnp reference, which is
    the *same* composition (same jit'd scatter, same folded GEMMs) as the
    unfused bank path — the jaxprs are identical, which is why
    ``bench_gated`` reports one shared wall-time for both on CPU."""
    import re

    n_ues = 4
    base = dict(net=NET, execution_mode=ExecutionMode.GATED, gated_capacity=2)
    unfused = BatchedPuschPipeline(CFG, params, **base)
    fused = BatchedPuschPipeline(CFG, params, fused_gated=True, **base)
    mode = jnp.zeros((n_ues,), jnp.int32)
    h_ls = jnp.ones(
        (n_ues, CFG.n_ant, CFG.n_dmrs_sym, CFG.n_pilot_sc), jnp.complex64
    )
    texts = []
    for eng in (unfused, fused):
        j = str(jax.make_jaxpr(lambda m, h: eng.bank(m, h).selected)(mode, h_ls))
        texts.append(re.sub(r"0x[0-9a-f]+", "0xX", j))  # thunk identities
    assert texts[0] == texts[1]


@pytest.mark.parametrize("n_ues", [1, 3, 4])
def test_engine_fused_matches_unfused_open_loop(params, n_ues):
    """Every trajectory leaf — physical, KPM, and cost accounting —
    bitwise-equal, including odd batch sizes and U == 1."""
    n_slots = 6
    rng = np.random.default_rng(n_ues)
    modes = rng.integers(0, 2, size=(n_slots, n_ues)).astype(np.int32)
    tu, tf = _run_pair(params, modes, n_slots=n_slots, n_ues=n_ues)
    _assert_tree_equal(tu, tf)


@pytest.mark.parametrize("fill,capacity", [
    (0, None),  # all-AI at full capacity
    (1, None),  # all-MMSE: only padding rows through the kernel path
    (0, 1),     # all-AI at capacity 1: overflow + fused interact
    (0, 2),     # exact boundary when 2 of 4 UEs stay AI below
])
def test_engine_fused_edge_grids(params, fill, capacity):
    n_slots, n_ues = 4, 4
    modes = np.full((n_slots, n_ues), fill, np.int32)
    if capacity == 2:
        modes[:, 2:] = 1  # exactly `capacity` AI selections per slot
    tu, tf = _run_pair(
        params, modes, n_slots=n_slots, n_ues=n_ues, gated_capacity=capacity
    )
    _assert_tree_equal(tu, tf)
    if capacity == 2:
        # exact boundary at the engine layer: no spurious overflow
        assert int(np.asarray(tf["gated_overflow"]).sum()) == 0


def test_engine_fused_matches_unfused_closed_loop(params):
    from repro.core.closed_loop import SwitchConfig
    from repro.core.policy import ThresholdPolicy
    from repro.core.telemetry import SELECTED_KPMS

    n_slots, n_ues = 8, 4
    sched = good_poor_good_schedule(poor_start=2, poor_end=6)
    pol = ThresholdPolicy(
        feature_idx=SELECTED_KPMS.index("snr"), threshold=8.0, hysteresis=0.5
    ).to_device()
    sw_cfg = SwitchConfig(
        feature_names=SELECTED_KPMS, window_slots=2, backend="ref"
    )
    key = jax.random.PRNGKey(11)
    base = dict(net=NET, execution_mode=ExecutionMode.GATED)
    unfused = BatchedPuschPipeline(CFG, params, **base)
    fused = BatchedPuschPipeline(CFG, params, fused_gated=True, **base)
    _, swu, tu = unfused.run_closed_loop(
        sched, pol, sw_cfg, n_slots=n_slots, n_ues=n_ues, key=key
    )
    _, swf, tf = fused.run_closed_loop(
        sched, pol, sw_cfg, n_slots=n_slots, n_ues=n_ues, key=key
    )
    _assert_tree_equal(tu, tf)
    np.testing.assert_array_equal(
        np.asarray(swu.n_switches), np.asarray(swf.n_switches)
    )


def test_engine_exact_capacity_boundary_history(params):
    """BatchedRunHistory at the boundary: K'th UE counted as AI-served,
    zero overflow, executed FLOPs == the K-expert cost model."""
    n_slots, n_ues, capacity = 4, 4, 2
    modes = np.ones((n_slots, n_ues), np.int32)
    modes[:, [1, 3]] = 0  # exactly `capacity` selections, last UE included
    gated = BatchedPuschPipeline(
        CFG, params, net=NET,
        execution_mode=ExecutionMode.GATED, gated_capacity=capacity,
    )
    _, traj = gated.run(
        constant_schedule(GOOD), modes, n_slots=n_slots, n_ues=n_ues
    )
    hist = BatchedRunHistory.from_trajectory(modes, traj)
    assert hist.overflow_slot_ues == 0
    assert hist.ai_share == pytest.approx(capacity / n_ues)
    f_ai, f_mmse = NET.flops(CFG), estimator_flops(CFG)
    np.testing.assert_allclose(
        hist.executed_flops_per_slot(),
        capacity * f_ai + n_ues * f_mmse, rtol=1e-6,
    )
    # per-UE: the K'th (last) UE carries the AI cost, not a fallback cost
    per_ue = np.asarray(traj["executed_flops"])
    np.testing.assert_allclose(
        per_ue[:, 3], f_ai + f_mmse, rtol=1e-6
    )


def test_engine_bf16_audit_fail_safe(params):
    """A paranoid threshold trips the audit on every bf16-served UE: the
    physical trajectory collapses to the all-MMSE campaign, audit flags
    surface in telemetry, and the executed FLOPs still charge the AI runs."""
    n_slots, n_ues = 3, 4
    sched = constant_schedule(GOOD)
    modes = np.ones((n_slots, n_ues), np.int32)
    modes[:, :2] = 0
    bf16 = BatchedPuschPipeline(
        CFG, params, net=NET,
        execution_mode=ExecutionMode.GATED, fused_gated=True,
        expert_dtype="bfloat16", audit_nmse_threshold=1e-14,
    )
    conc = BatchedPuschPipeline(CFG, params, net=NET)
    key = jax.random.PRNGKey(6)
    _, tb = bf16.run(sched, modes, n_slots=n_slots, n_ues=n_ues, key=key)
    tripped = np.asarray(tb["audit_tripped"])
    np.testing.assert_array_equal(tripped, modes == 0)  # every AI UE trips
    # fail-safe: physically identical to committing MMSE everywhere
    _, tm = conc.run(sched, 1, n_slots=n_slots, n_ues=n_ues, key=key)
    _assert_tree_equal(physical_trajectory(tb), physical_trajectory(tm))
    # history: tripped UEs are not AI-served, but their compute was spent
    hist = BatchedRunHistory.from_trajectory(modes, tb)
    assert hist.ai_share == 0.0
    assert hist.audit_tripped_slot_ues == n_slots * 2
    f_ai, f_mmse = NET.flops(CFG), estimator_flops(CFG)
    np.testing.assert_allclose(
        hist.executed_flops_per_slot(), 2 * f_ai + n_ues * f_mmse, rtol=1e-6
    )


def test_engine_bf16_audit_quiet_at_sane_threshold(params):
    """At the benchmark's loose threshold benign campaigns never trip, and
    the bf16 ref/pallas parity carries through the engine (the f32 engine
    stays bitwise vs its own unfused twin by the tests above)."""
    n_slots, n_ues = 3, 4
    modes = np.ones((n_slots, n_ues), np.int32)
    modes[:, 0] = 0
    bf16 = BatchedPuschPipeline(
        CFG, params, net=NET,
        execution_mode=ExecutionMode.GATED, fused_gated=True,
        expert_dtype="bfloat16", audit_nmse_threshold=1.0,
    )
    _, tb = bf16.run(
        constant_schedule(GOOD), modes, n_slots=n_slots, n_ues=n_ues
    )
    assert int(np.asarray(tb["audit_tripped"]).sum()) == 0
    assert int(np.asarray(tb["gated_overflow"]).sum()) == 0


def test_engine_validates_fused_kwargs(params):
    with pytest.raises(ValueError, match="GATED"):
        BatchedPuschPipeline(CFG, params, net=NET, fused_gated=True)
    with pytest.raises(ValueError, match="expert_dtype"):
        BatchedPuschPipeline(CFG, params, net=NET, expert_dtype="fp8")


# -- engine: 8-shard mesh (subprocess: XLA_FLAGS precedes jax init) -----------


_FUSED_SHARDED_CHECK = r"""
import numpy as np, jax, jax.numpy as jnp

assert len(jax.devices()) == 8, jax.devices()

from repro.core.expert_bank import ExecutionMode
from repro.core.session import ArchesSession, CampaignSpec, ExpertBankSpec
from repro.core.topology import CellTopology, TopologySpec, open_loop_fn
from repro.phy.ai_estimator import AiEstimatorConfig, init_params
from repro.phy.channel import broadcast_params_to_ues
from repro.phy.nr import SlotConfig
from repro.phy.pipeline import (
    BatchedPuschPipeline, init_device_link, resolve_schedule,
)
from repro.phy.scenario import good_poor_good_schedule

S, U = 4, 8
CFG = SlotConfig(n_prb=24)
NET = AiEstimatorConfig(channels=8, n_res_blocks=1)
params = init_params(jax.random.PRNGKey(0), CFG, NET)
sched = good_poor_good_schedule(poor_start=1, poor_end=3)
topo = CellTopology.build(
    TopologySpec(n_cells=4, coupling=0.3, n_shards=8), U
)
assert topo.n_shards == 8, topo.n_shards

kw = dict(net=NET, execution_mode=ExecutionMode.GATED, gated_capacity=1)
unfused = BatchedPuschPipeline(CFG, params, **kw)
fused = BatchedPuschPipeline(CFG, params, fused_gated=True, **kw)

key = jax.random.PRNGKey(3)
profile, p = resolve_schedule(CFG, sched, S, U)
p = broadcast_params_to_ues(p, U)
ue_keys = jax.vmap(lambda u: jax.random.fold_in(key, u))(jnp.arange(U))
modes = jnp.ones((S, U), jnp.int32).at[:, ::2].set(0)  # 1 AI UE per shard
mk_args = lambda: (init_device_link(U), ue_keys, modes, p,
                   jnp.asarray(topo.cell_of_ue), topo.cell_params)

# 1) the fused gated scan stays shard-local: HLO collective audit
fn_f = open_loop_fn(fused, topo, profile)
hlo = jax.jit(fn_f).lower(*mk_args()).compile().as_text()
assert "all-reduce" in hlo, "expected the cell-mean psum to lower"
for bad in ("all-gather", "all-to-all", "collective-permute"):
    assert bad not in hlo, f"cross-device {bad} in the fused gated scan"

# 2) fused == unfused on 8 shards, bitwise, every trajectory leaf
fn_u = open_loop_fn(unfused, topo, profile)
_, tf = jax.jit(fn_f)(*mk_args())
_, tu = jax.jit(fn_u)(*mk_args())
jax.tree.map(
    lambda a, b: np.testing.assert_array_equal(np.asarray(a), np.asarray(b)),
    tu, tf,
)
assert int(np.asarray(tf["gated_overflow"]).sum()) == 0

# 3) sharded auto-capacity regression: a zero-AI-demand campaign on 8
#    shards must provision a buildable capacity (one slot per shard), not
#    the raw demand count 0 that per_shard_capacity rejects
spec = CampaignSpec(
    path="gated", scenario="good_poor_good",
    scenario_args=(("poor_start", 1), ("poor_end", 3)),
    n_ues=U, n_slots=S, modes=1,
    bank=ExpertBankSpec(execution_mode="gated", gated_capacity=8,
                        channels=8, n_res_blocks=1, fused=True),
    topology=TopologySpec(n_cells=4, coupling=0.3, n_shards=8),
)
hist = ArchesSession(spec, ai_params=params).run(auto_capacity=True)
assert hist.provisioned_capacity == 8, hist.provisioned_capacity
assert hist.overflow_slot_ues == 0

print("FUSED-SHARDED-8 OK")
"""


def test_fused_sharded_engine_on_forced_8_device_mesh():
    env = dict(os.environ)
    env["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count=8 "
        + env.get("XLA_FLAGS", "")
    ).strip()
    src = os.path.join(os.path.dirname(__file__), os.pardir, "src")
    env["PYTHONPATH"] = os.path.abspath(src) + os.pathsep + env.get(
        "PYTHONPATH", ""
    )
    proc = subprocess.run(
        [sys.executable, "-c", _FUSED_SHARDED_CHECK],
        env=env, capture_output=True, text=True, timeout=540,
    )
    assert proc.returncode == 0, (
        f"fused sharded check failed\n--- stdout ---\n{proc.stdout}\n"
        f"--- stderr ---\n{proc.stderr[-4000:]}"
    )
    assert "FUSED-SHARDED-8 OK" in proc.stdout
