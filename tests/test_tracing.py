"""The profiler's view of ARCHES (``repro.tracing``).

* Every op of the closed-loop slot step runs under exactly one of the six
  stage scopes, so a device trace splits the slot by stage.  Checked on the
  step's lowered HLO at a tiny CPU size: a nested jit's callee carries
  ``op_name`` paths relative to its call, so each op is followed down the
  call tree from the entry computation, as XLA's inliner does.
* ``_closed_slot_step``, now a Python method that opens the
  ``arches.slot.dispatch`` span around the compiled step, still compiles
  once and computes what the closed-loop scan computes, bit for bit (read
  through the paths of its ``SlotOutputs`` view), and shows one span per
  call while a profiler session is active.
* ``run_streaming``'s phase timers fill the same ``stats`` keys.

The step compiled for a TPU v5e (its Pallas kernels' names, its scopes) is
checked in ``test_tpu_compile.py``, where the described chip lives.
"""

import glob
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import tracing
from repro.core.closed_loop import (
    SwitchConfig,
    export_tree_tables,
    init_device_switch,
)
from repro.core.expert_bank import ExecutionMode
from repro.core.telemetry import SELECTED_KPMS
from repro.phy import pipeline
from repro.phy.ai_estimator import AiEstimatorConfig, init_params
from repro.phy.nr import SlotConfig
from repro.phy.scenario import good_poor_good_schedule

CFG = SlotConfig(n_prb=4)
NET = AiEstimatorConfig(channels=4, n_res_blocks=1)
N_UES, N_SLOTS = 4, 6
STAGE_NAMES = tuple(s.split(".", 1)[1] for s in tracing.STAGES)
STAGE_RE = re.compile(
    r"(?<![\w.])arches\.(" + "|".join(STAGE_NAMES) + r")(?![\w.])")
# containers (their callees are followed) and ops that compute nothing
TRIVIAL = {"parameter", "constant", "tuple", "get-tuple-element", "call",
           "while", "conditional", "splat"}


def step_inputs(cfg=CFG, n_ues=N_UES):
    """A gated engine, its tree policy and one closed-loop slot's inputs."""
    eng = pipeline.BatchedPuschPipeline(
        cfg, init_params(jax.random.PRNGKey(0), cfg, NET), net=NET,
        execution_mode=ExecutionMode.GATED, gated_capacity=n_ues // 2,
    )
    profile, params = pipeline.resolve_schedule(
        cfg, good_poor_good_schedule(poor_start=2, poor_end=4), N_SLOTS,
        n_ues,
    )
    sw_cfg = SwitchConfig(feature_names=SELECTED_KPMS)
    n_feat = len(SELECTED_KPMS)
    policy = export_tree_tables(
        np.array([0, 1, 2]), np.array([10.0, 5.0, 3.0], np.float32),
        np.array([1.0, 0.0, 1.0, 0.0], np.float32), n_feat, 2,
    )
    ue_keys = jax.vmap(lambda u: jax.random.fold_in(jax.random.PRNGKey(1), u))(
        jnp.arange(n_ues))
    return dict(
        eng=eng, profile=profile, params=params, sw_cfg=sw_cfg,
        policy=policy, ue_keys=ue_keys, link=pipeline.init_device_link(n_ues),
        sw=init_device_switch(n_ues, n_feat, sw_cfg),
    )


@pytest.fixture(scope="module")
def step():
    return step_inputs()


def _slot(x, params, s):
    p = jax.tree.map(lambda v: v[s], params)
    return (x["profile"], x["sw_cfg"], x["link"], x["sw"], jnp.int32(s),
            x["ue_keys"], p, x["policy"])


def _parse_hlo(text: str):
    """``{computation: [(opcode, op_name, callees)]}`` and the entry's name."""
    comps, entry, cur = {}, None, None
    for line in text.splitlines():
        if line and not line[0].isspace() and line.rstrip().endswith("{"):
            words = line.split()
            is_entry = words[0] == "ENTRY"
            cur = (words[1] if is_entry else words[0]).lstrip("%")
            entry = cur if is_entry else entry
            comps[cur] = []
            continue
        if cur is None or " = " not in line:
            continue
        rest = line.split(" = ", 1)[1]
        opcode = re.search(r"\s([a-z][a-z\-]*)\(", rest)
        if not opcode:
            continue
        opcode = opcode.group(1)
        if opcode == "broadcast" and "dimensions={}" in line:
            opcode = "splat"  # of a scalar: fused into its consumers
        op_name = re.search(r'op_name="((?:[^"\\]|\\.)*)"', line)
        callees = []
        if opcode in ("call", "while", "conditional"):
            callees = re.findall(
                r"(?:to_apply|condition|body|true_computation|"
                r"false_computation)=%?([\w.\-]+)", line)
            branches = re.search(r"branch_computations=\{([^}]*)\}", line)
            if branches:
                callees += [b.strip().lstrip("%")
                            for b in branches.group(1).split(",")]
        comps[cur].append(
            (opcode, op_name.group(1) if op_name else "", callees))
    return comps, entry


def _op_stages(text: str):
    """Every op instance of the inlined program with the stage names on
    its call path (outer to inner)."""
    comps, entry = _parse_hlo(text)
    out = []

    def walk(comp, path):
        for opcode, op_name, callees in comps[comp]:
            here = path + STAGE_RE.findall(op_name)
            out.append((opcode, op_name, here))
            for c in callees:
                walk(c, here)

    walk(entry, [])
    return out


def test_lowered_step_puts_every_op_under_one_stage(step):
    x = step
    lowered = pipeline._closed_slot_step.lower(
        x["eng"], *_slot(x, x["params"], 3))
    ops = _op_stages(lowered.as_text(dialect="hlo", debug_info=True))
    real = [(opcode, name, st) for opcode, name, st in ops
            if opcode not in TRIVIAL]
    assert len(real) > 500
    assert {st[-1] for _, _, st in real if st} == set(STAGE_NAMES)
    unscoped = [(opcode, name) for opcode, name, st in real if not st]
    assert not unscoped, unscoped[:10]
    nested = [(opcode, name, st) for opcode, name, st in real
              if len(set(st)) != 1]
    assert not nested, nested[:10]


def test_stage_scopes_add_no_device_op(step):
    """The same step traced with every scope turned into a no-op lowers to
    the same ops, in the same order: a scope is metadata only."""
    x = step
    args = _slot(x, x["params"], 3)

    def opcodes(text):
        comps, entry = _parse_hlo(text)
        return [opcode for c in comps.values() for opcode, _, _ in c]

    with_scopes = opcodes(pipeline._closed_slot_step.lower(
        x["eng"], *args).as_text(dialect="hlo"))
    eng = step_inputs()["eng"]  # a new engine: a new trace, not a cache hit
    real_stage = tracing.stage
    try:
        tracing.stage = lambda name: jax.named_scope("plain")
        without = opcodes(pipeline._closed_slot_step.lower(
            eng, *args).as_text(dialect="hlo"))
    finally:
        tracing.stage = real_stage
    assert with_scopes == without


def test_slot_step_wrapper_compiles_once_and_matches_the_scan(step):
    from jax import monitoring

    x = step
    compiles = [0]

    def on_event(event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles[0] += 1

    monitoring.register_event_duration_secs_listener(on_event)
    try:
        link, sw, outs = x["link"], x["sw"], []
        for s in range(3):
            args = list(_slot(x, x["params"], s))
            args[2], args[3] = link, sw
            link, sw, out = x["eng"]._closed_slot_step(*args)
            outs.append(out)
        jax.block_until_ready(outs)
    finally:
        monitoring.unregister_event_duration_listener(on_event)
    assert compiles[0] == 1

    first3 = jax.tree.map(lambda v: v[:3], x["params"])
    link_s, sw_s, traj = x["eng"]._run_closed_scan(
        x["profile"], x["sw_cfg"], x["link"], x["sw"], x["ue_keys"], first3,
        x["policy"],
    )
    for s, out in enumerate(outs):
        got = jax.tree_util.tree_leaves_with_path(out.to_dict())
        want = jax.tree.leaves(jax.tree.map(lambda v: v[s], traj))
        assert len(got) == len(want)
        for (path, a), b in zip(got, want):
            np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b),
                err_msg=f"slot {s} {jax.tree_util.keystr(path)}")
    for a, b in zip(jax.tree.leaves((link, sw)),
                    jax.tree.leaves((link_s, sw_s))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _trace_event_names(trace_dir):
    path, = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return [e.name
            for plane in jax.profiler.ProfileData.from_file(path).planes
            for line in plane.lines for e in line.events]


def test_slot_step_wrapper_shows_one_dispatch_span_per_call(step, tmp_path):
    x = step
    args = _slot(x, x["params"], 0)
    jax.block_until_ready(x["eng"]._closed_slot_step(*args))  # compile
    trace_dir = str(tmp_path / "trace")
    jax.profiler.start_trace(trace_dir)
    try:
        for _ in range(3):
            jax.block_until_ready(x["eng"]._closed_slot_step(*args))
    finally:
        jax.profiler.stop_trace()
    assert _trace_event_names(trace_dir).count(tracing.SLOT_DISPATCH) == 3


@pytest.fixture(scope="module")
def streaming_session():
    from repro.core.session import ArchesSession, CampaignSpec, ExpertBankSpec
    from repro.core.streaming import ChurnSchedule

    churn = ChurnSchedule(
        n_ue_ids=3, segment_slots=2, initial=(0, 1),
        events=((2, 2, "attach"), (2, 0, "detach")),
    )
    spec = CampaignSpec(
        path="batched", scenario="churn_cell", n_ues=2, n_slots=N_SLOTS,
        n_prb=4, churn=churn, bank=ExpertBankSpec(channels=4, n_res_blocks=1),
    )
    return ArchesSession(spec)


def test_run_streaming_fills_its_stats_and_shows_its_phases(
        streaming_session, tmp_path):
    stats = {}
    streaming_session.run_streaming(
        checkpoint_dir=str(tmp_path / "ckpt"), stats=stats)
    assert set(stats) == {"dispatch_s", "wait_s", "assembly_s",
                          "checkpoint_s", "checkpoint_bytes", "segments",
                          "pipeline", "checkpoint_format"}
    assert stats["segments"] == N_SLOTS // 2
    assert len(stats["checkpoint_bytes"]) == stats["segments"]
    for key in ("dispatch_s", "wait_s", "assembly_s", "checkpoint_s"):
        assert stats[key] > 0, key
