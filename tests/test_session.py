"""ArchesSession: one declarative call == each legacy entry point, bitwise.

The session API's contract has three legs, all asserted here:

* **Provenance** — ``CampaignSpec`` survives a JSON serialize/deserialize
  round trip (every campaign below is run from its *restored* spec) and
  hashes stably.
* **Dispatch equivalence** — ``ArchesSession(spec).run()`` reproduces,
  bitwise on mode trajectories (and physical KPM leaves where compared),
  the host loop, the open-loop batched engine, the closed loop, gated
  execution, and the perturbation sweep built by hand through the legacy
  entry points.
* **Per-UE heterogeneity** — a ``mixed_cell`` campaign where UEs run
  different channel schedules *and* different exported policies matches
  its per-UE host replay bitwise (the ROADMAP open item, retired).

Plus the satellite utilities: the deprecation shim on the old
``closed_loop=True`` kwarg constructor, ``ArchesRuntime.from_spec``, and
``suggest_gated_capacity``.
"""

import dataclasses
import warnings

import jax
import numpy as np
import pytest

from repro.core.closed_loop import SwitchConfig, host_replay_closed_loop
from repro.core.expert_bank import ExecutionMode
from repro.core.policy import ThresholdPolicy, profile_and_fit_tree
from repro.core.runtime import (
    ArchesRuntime,
    BatchedRunHistory,
    suggest_gated_capacity,
)
from repro.core.session import (
    ArchesSession,
    CampaignSpec,
    ExecutionPath,
    ExpertBankSpec,
    PolicySpec,
    SwitchSpec,
    spec_hash,
)
from repro.core.telemetry import SELECTED_KPMS
from repro.phy.ai_estimator import AiEstimatorConfig, init_params
from repro.phy.nr import SlotConfig
from repro.phy.pipeline import BatchedPuschPipeline, PuschPipeline
from repro.phy.scenario import good_poor_good_schedule

N_SLOTS, N_UES = 12, 2
POOR_ARGS = (("poor_start", 4), ("poor_end", 8))
SCHED = good_poor_good_schedule(poor_start=4, poor_end=8)
CFG = SlotConfig(n_prb=24)
NET = AiEstimatorConfig(channels=8, n_res_blocks=1)


def restored(spec: CampaignSpec) -> CampaignSpec:
    """Round-trip through JSON first — every campaign runs from provenance."""
    out = CampaignSpec.from_json(spec.to_json())
    assert out == spec
    assert spec_hash(out) == spec_hash(spec)
    return out


@pytest.fixture(scope="module")
def legacy_params():
    """What the spec defaults must reproduce: params from PRNGKey(0)."""
    return init_params(jax.random.PRNGKey(0), CFG, NET)


@pytest.fixture(scope="module")
def legacy_engine(legacy_params):
    return BatchedPuschPipeline(CFG, legacy_params, net=NET)


# -- spec round trip -----------------------------------------------------------


def test_spec_json_round_trip_full_nesting():
    spec = CampaignSpec(
        path="closed_loop",
        scenario="mixed_cell",
        scenario_args=(("poor_start", 3), ("poor_end", 7)),
        n_ues=4,
        n_slots=9,
        seed=11,
        modes=((0, 1, 1, 0),) * 9,
        bank=ExpertBankSpec(execution_mode="gated", gated_capacity=2),
        policies=(
            PolicySpec(kind="tree", depth=3, train_slots=6),
            PolicySpec(kind="threshold", feature="snr", threshold=17.5,
                       hysteresis=1.5),
        ),
        policy_assignment=(0, 1, 0, 1),
        switch=SwitchSpec(window_slots=3, hysteresis_slots=2, period_slots=2,
                          backend="ref"),
    )
    back = CampaignSpec.from_json(spec.to_json())
    assert back == spec
    assert back.to_json() == spec.to_json()
    assert spec_hash(back) == spec_hash(spec)
    # JSON lists became the frozen spec's tuples again
    assert isinstance(back.modes[0], tuple)
    assert isinstance(back.policies[0], PolicySpec)
    assert back.scenario_kwargs == {"poor_start": 3, "poor_end": 7}


def test_spec_json_round_trip_perturbed_and_defaults():
    for spec in (
        CampaignSpec(),
        CampaignSpec(path="perturbed", n_ues=3, n_slots=5,
                     rho=(0.0, 0.5, 1.0)),
    ):
        back = CampaignSpec.from_json(spec.to_json())
        assert back == spec and spec_hash(back) == spec_hash(spec)


def test_spec_validation():
    with pytest.raises(ValueError, match="execution path"):
        CampaignSpec(path="warp_drive")
    with pytest.raises(ValueError, match="policy kind"):
        PolicySpec(kind="oracle")
    with pytest.raises(ValueError, match="execution mode"):
        ExpertBankSpec(execution_mode="sometimes")
    with pytest.raises(ValueError, match="policy_assignment"):
        CampaignSpec(n_ues=2, policies=(PolicySpec(),),
                     policy_assignment=(0, 0, 0))
    with pytest.raises(ValueError, match="out of range"):
        CampaignSpec(n_ues=2, policies=(PolicySpec(),),
                     policy_assignment=(0, 1))
    with pytest.raises(ValueError, match="empty"):
        CampaignSpec(n_ues=2, policy_assignment=(3, 7))
    with pytest.raises(ValueError, match="one UE"):
        ArchesSession(CampaignSpec(path="host", n_ues=2,
                                   policies=(PolicySpec(),)))
    with pytest.raises(ValueError, match="rho"):
        ArchesSession(CampaignSpec(path="perturbed"))
    with pytest.raises(ValueError, match="PolicySpec"):
        ArchesSession(CampaignSpec(path="closed_loop"))
    # per-UE scenarios have no single schedule for the host slot loop
    with pytest.raises(ValueError, match="homogeneous"):
        ArchesSession(CampaignSpec(path="host", scenario="mixed_cell",
                                   n_ues=1, policies=(PolicySpec(),)))
    # several policies must say which UE runs which table — a silent
    # all-table-0 assignment would ignore the declared second policy
    with pytest.raises(ValueError, match="policy_assignment"):
        ArchesSession(CampaignSpec(
            path="closed_loop", n_ues=2,
            policies=(PolicySpec(), PolicySpec(kind="threshold")),
        ))


def test_heterogeneous_tree_training_ignores_foreign_scenario_args():
    """A per-UE campaign's scenario kwargs belong to its own factory; tree
    training must fall back to good_poor_good — with the poor window scaled
    into the short training horizon, so the labels stay two-class and the
    fitted tree is not a constant."""
    spec = CampaignSpec(
        path="closed_loop", scenario="mixed_cell",
        scenario_args=(("period", 8), ("burst_slots", 3)),
        n_ues=2, n_slots=6,
        policies=(PolicySpec(kind="tree", train_slots=6),),
        switch=SwitchSpec(window_slots=2, backend="ref"),
    )
    session = ArchesSession(spec)
    hist = session.run()
    assert hist.modes.shape == (6, 2)
    leaves = session.host_policies[0].tree.leaf_values
    assert {0.0, 1.0} <= set(np.asarray(leaves).tolist()), (
        "training fell back to a single-class window: constant tree"
    )


def test_train_scenario_args_reach_the_training_factory():
    spec = CampaignSpec(
        path="closed_loop", scenario="mixed_cell", n_ues=2, n_slots=6,
        policies=(PolicySpec(
            kind="tree", train_slots=6, train_scenario="good_poor_good",
            train_scenario_args=(("poor_start", 2), ("poor_end", 4)),
        ),),
        switch=SwitchSpec(window_slots=2, backend="ref"),
    )
    assert CampaignSpec.from_json(spec.to_json()) == spec
    sched = ArchesSession(spec)._train_schedule(spec.policies[0])
    assert [sched(s).interference for s in range(6)] == [
        False, False, True, True, False, False,
    ]


def test_spec_accepts_device_arrays():
    """modes/rho given as jax or numpy arrays normalize into the JSON-stable
    tuple form (the spec's provenance contract must survive any input the
    engine's normalize_modes would accept)."""
    import jax.numpy as jnp

    spec = CampaignSpec(path="batched", n_ues=2, n_slots=3,
                        modes=jnp.ones((3, 2), jnp.int32))
    assert spec.modes == ((1, 1),) * 3
    assert CampaignSpec.from_json(spec.to_json()) == spec


def test_spec_accepts_enum_members_and_stays_serializable():
    """Enum members normalize to their string value — provenance must not
    depend on whether the author wrote the enum or its JSON form."""
    spec = CampaignSpec(
        path=ExecutionPath.GATED,
        bank=ExpertBankSpec(execution_mode=ExecutionMode.GATED),
        n_ues=2, n_slots=2,
    )
    assert spec.path == "gated" and spec.bank.execution_mode == "gated"
    assert spec == CampaignSpec(
        path="gated", bank=ExpertBankSpec(execution_mode="gated"),
        n_ues=2, n_slots=2,
    )
    assert CampaignSpec.from_json(spec.to_json()) == spec


def test_host_replay_rejects_policy_idx_without_sequence():
    policy = ThresholdPolicy(feature_idx=0, threshold=0.0)
    cfg = SwitchConfig(feature_names=("f",), window_slots=1)
    feats = np.zeros((2, 2, 1), np.float32)
    with pytest.raises(ValueError, match="not a sequence"):
        host_replay_closed_loop(policy, feats, cfg, policy_idx=(0, 0))
    # negative indexes would silently wrap through Python list indexing
    with pytest.raises(ValueError, match="outside"):
        host_replay_closed_loop([policy, policy], feats, cfg,
                                policy_idx=(-1, 0))


def test_host_path_honors_policy_assignment(legacy_params):
    """The host UE may be assigned any declared table — a spec assigning
    policies[1] must not silently run policies[0]."""
    spec = CampaignSpec(
        path="host", scenario="good", n_ues=1, n_slots=4,
        policies=(
            PolicySpec(kind="threshold", feature="snr", threshold=18.0),
            # degenerate gate: anything below 99 dB -> AI (always mode 0)
            PolicySpec(kind="threshold", feature="snr", threshold=99.0),
        ),
        policy_assignment=(1,),
        switch=SwitchSpec(window_slots=1),
    )
    hist = ArchesSession(spec, ai_params=legacy_params).run()
    assert (hist.modes[1:, 0] == 0).all()  # the always-AI table ran


def test_host_path_rejects_silently_dropped_knobs():
    with pytest.raises(ValueError, match="hysteresis"):
        ArchesSession(CampaignSpec(
            path="host", n_ues=1, policies=(PolicySpec(),),
            switch=SwitchSpec(hysteresis_slots=3),
        ))


def test_gated_path_rejects_selected_only_bank():
    with pytest.raises(ValueError, match="un-gated"):
        ArchesSession(CampaignSpec(
            path="gated", n_ues=2, n_slots=2,
            bank=ExpertBankSpec(execution_mode="selected_only"),
        ))


def test_gated_path_normalizes_bank_without_mutating_spec():
    spec = CampaignSpec(path="gated", n_ues=2, n_slots=2)
    session = ArchesSession(spec)
    assert ExecutionMode.coerce(session.bank_spec.execution_mode) is (
        ExecutionMode.GATED
    )
    assert spec.bank.execution_mode == "concurrent"  # provenance untouched


# -- dispatch equivalence vs the legacy entry points ---------------------------


def test_batched_session_matches_legacy_engine(legacy_engine):
    modes = np.tile(np.asarray([[0, 1]], np.int32), (N_SLOTS, 1))
    spec = restored(CampaignSpec(
        path="batched", scenario="good_poor_good", scenario_args=POOR_ARGS,
        n_ues=N_UES, n_slots=N_SLOTS, seed=3,
        modes=tuple(map(tuple, modes)),
    ))
    hist = ArchesSession(spec).run()
    _, traj = legacy_engine.run(
        SCHED, modes, n_slots=N_SLOTS, n_ues=N_UES,
        key=jax.random.PRNGKey(3),
    )
    np.testing.assert_array_equal(hist.modes, modes)
    np.testing.assert_array_equal(
        hist.kpms["sinr"], np.asarray(traj["kpms"]["aerial"]["sinr"])
    )
    np.testing.assert_array_equal(
        hist.outputs["tb_ok"], np.asarray(traj["tb_ok"])
    )


def test_gated_session_matches_legacy_engine(legacy_params):
    modes = np.ones((N_SLOTS, N_UES), np.int32)
    modes[:, 0] = 0
    spec = restored(CampaignSpec(
        path="gated", scenario="good_poor_good", scenario_args=POOR_ARGS,
        n_ues=N_UES, n_slots=N_SLOTS, seed=3,
        modes=tuple(map(tuple, modes)),
        bank=ExpertBankSpec(execution_mode="gated", gated_capacity=1),
    ))
    hist = ArchesSession(spec).run()
    legacy = BatchedPuschPipeline(
        CFG, legacy_params, net=NET,
        execution_mode=ExecutionMode.GATED, gated_capacity=1,
    )
    _, traj = legacy.run(
        SCHED, modes, n_slots=N_SLOTS, n_ues=N_UES,
        key=jax.random.PRNGKey(3),
    )
    np.testing.assert_array_equal(
        hist.kpms["sinr"], np.asarray(traj["kpms"]["aerial"]["sinr"])
    )
    np.testing.assert_array_equal(
        hist.outputs["gated_overflow"], np.asarray(traj["gated_overflow"])
    )
    assert hist.overflow_slot_ues == 0


def test_closed_loop_session_matches_legacy_runtime(legacy_engine):
    spec = restored(CampaignSpec(
        path="closed_loop", scenario="good_poor_good",
        scenario_args=POOR_ARGS, n_ues=N_UES, n_slots=N_SLOTS, seed=7,
        policies=(PolicySpec(kind="tree", depth=2, train_ues=2),),
        switch=SwitchSpec(window_slots=2, backend="ref"),
    ))
    hist = ArchesSession(spec).run()

    # the legacy construction: hand-trained policy + kwarg-soup runtime
    policy = profile_and_fit_tree(
        legacy_engine, SCHED, n_slots=N_SLOTS, n_ues=2, depth=2
    )
    sw_cfg = SwitchConfig(
        feature_names=SELECTED_KPMS, window_slots=2, backend="ref"
    )
    with pytest.warns(DeprecationWarning, match="from_spec"):
        runtime = ArchesRuntime(
            closed_loop=True, engine=legacy_engine,
            device_policy=policy.to_device(), switch_config=sw_cfg,
        )
    legacy_hist = runtime.run_batched(
        SCHED, n_slots=N_SLOTS, n_ues=N_UES, key=jax.random.PRNGKey(7)
    )
    np.testing.assert_array_equal(hist.modes, legacy_hist.modes)
    np.testing.assert_array_equal(hist.decisions, legacy_hist.decisions)
    np.testing.assert_array_equal(hist.n_switches, legacy_hist.n_switches)
    # non-vacuous: the campaign actually switched
    assert hist.n_switches.sum() > 0


def test_host_session_matches_legacy_loop(legacy_params):
    from repro.core.dapp import DApp, connect_dapp
    from repro.core.e3 import E3Agent

    threshold = PolicySpec(kind="threshold", feature="snr", threshold=18.0,
                           hysteresis=2.0)
    spec = restored(CampaignSpec(
        path="host", scenario="good_poor_good", scenario_args=POOR_ARGS,
        n_ues=1, n_slots=10,
        policies=(threshold,),
        switch=SwitchSpec(window_slots=2, ttl_slots=8),
    ))
    hist = ArchesSession(spec).run()
    assert isinstance(hist, BatchedRunHistory)
    assert hist.modes.shape == (10, 1)

    pipe = PuschPipeline(CFG, legacy_params, net=NET)
    agent = E3Agent()
    policy = ThresholdPolicy(
        feature_idx=SELECTED_KPMS.index("snr"), threshold=18.0, hysteresis=2.0
    )
    dapp = DApp(policy, SELECTED_KPMS, window_slots=2)
    connect_dapp(agent, dapp)
    runtime = ArchesRuntime(
        pipe.make_slot_fn(SCHED), agent,
        default_mode=1, fail_safe_mode=1, ttl_slots=8, keep_outputs=True,
    )
    legacy_hist = runtime.run(range(10))
    np.testing.assert_array_equal(hist.modes[:, 0], legacy_hist.modes)
    np.testing.assert_array_equal(
        hist.kpms["snr"][:, 0], legacy_hist.kpm_series("snr")
    )


def test_perturbed_session_matches_legacy_engine(legacy_engine):
    rho = (0.0, 0.6)
    spec = restored(CampaignSpec(
        path="perturbed", scenario="good", n_ues=len(rho), n_slots=6,
        seed=5, rho=rho,
    ))
    hist = ArchesSession(spec).run()
    from repro.phy.scenario import make_schedule

    _, traj = legacy_engine.run_perturbed(
        make_schedule("good"), np.asarray(rho, np.float32),
        n_slots=6, key=jax.random.PRNGKey(5),
    )
    np.testing.assert_array_equal(
        hist.kpms["sinr"], np.asarray(traj["kpms"]["aerial"]["sinr"])
    )
    np.testing.assert_array_equal(
        hist.outputs["tb_ok"], np.asarray(traj["tb_ok"])
    )
    assert (hist.modes == 1).all()  # stage 1 is MMSE-only


# -- per-UE heterogeneity (the retired ROADMAP item) ---------------------------


def test_heterogeneous_scenario_and_policies_match_per_ue_replay():
    """Four UEs, per-UE channel schedules, two different policies — the
    device campaign must equal the per-UE host replay bitwise, and the two
    policy groups must actually behave differently (non-vacuous)."""
    spec = restored(CampaignSpec(
        path="closed_loop", scenario="mixed_cell", n_ues=4, n_slots=N_SLOTS,
        seed=1,
        policies=(
            PolicySpec(kind="threshold", feature="snr", threshold=18.0,
                       hysteresis=2.0),
            # degenerate gate: anything below 99 dB -> AI (always mode 0)
            PolicySpec(kind="threshold", feature="snr", threshold=99.0),
        ),
        policy_assignment=(0, 1, 0, 1),
        switch=SwitchSpec(window_slots=2, backend="ref"),
    ))
    session = ArchesSession(spec)
    hist = session.run()

    feats = np.stack(
        [hist.kpms[n] for n in spec.feature_names], axis=-1
    ).astype(np.float32)
    replay = host_replay_closed_loop(
        list(session.host_policies), feats,
        spec.switch.to_config(spec.feature_names),
        policy_idx=spec.policy_assignment,
    )
    np.testing.assert_array_equal(hist.modes, replay["active_mode"])
    np.testing.assert_array_equal(hist.decisions, replay["raw_decision"])
    # the packaged oracle reproduces the hand-built replay
    np.testing.assert_array_equal(
        session.host_replay(hist)["active_mode"], replay["active_mode"]
    )

    # policy 1 forces AI from its first committed decision onward; policy 0
    # on the clean UE 0 keeps MMSE — two UEs demonstrably ran different
    # policies in one scan
    assert (hist.modes[2:, 1] == 0).all() and (hist.modes[2:, 3] == 0).all()
    assert not np.array_equal(hist.modes[:, 0], hist.modes[:, 1])


def test_per_ue_schedules_match_solo_homogeneous_runs(legacy_engine):
    """Per-UE params preserve the engine's trajectory-identity contract:
    each UE of a heterogeneous campaign equals the same UE of a homogeneous
    campaign under its own schedule (same keys), bitwise."""
    from repro.phy.scenario import GOOD, POOR, constant_schedule

    key = jax.random.PRNGKey(3)
    good, poor = constant_schedule(GOOD), constant_schedule(POOR)
    _, het = legacy_engine.run(
        [good, poor], 1, n_slots=5, n_ues=2, key=key
    )
    _, hg = legacy_engine.run(good, 1, n_slots=5, n_ues=2, key=key)
    _, hp = legacy_engine.run(poor, 1, n_slots=5, n_ues=2, key=key)
    for leaf in ("tb_ok", "mcs"):
        np.testing.assert_array_equal(
            np.asarray(het[leaf])[:, 0], np.asarray(hg[leaf])[:, 0]
        )
        np.testing.assert_array_equal(
            np.asarray(het[leaf])[:, 1], np.asarray(hp[leaf])[:, 1]
        )
    sinr = lambda t: np.asarray(t["kpms"]["aerial"]["sinr"])
    np.testing.assert_array_equal(sinr(het)[:, 0], sinr(hg)[:, 0])
    np.testing.assert_array_equal(sinr(het)[:, 1], sinr(hp)[:, 1])


# -- runtime construction: from_spec + the deprecation shim --------------------


def test_legacy_closed_loop_kwargs_warn():
    with pytest.warns(DeprecationWarning, match="from_spec"):
        ArchesRuntime(
            closed_loop=True, engine=object(), device_policy=object(),
            switch_config=object(),
        )
    with pytest.warns(DeprecationWarning):
        with pytest.raises(ValueError, match="closed_loop"):
            ArchesRuntime(closed_loop=True)


def test_from_spec_builds_quietly_and_runs(legacy_engine):
    spec = CampaignSpec(
        path="closed_loop", scenario="good_poor_good",
        scenario_args=POOR_ARGS, n_ues=N_UES, n_slots=6, seed=7,
        policies=(PolicySpec(kind="threshold", feature="snr",
                             threshold=18.0, hysteresis=2.0),),
        switch=SwitchSpec(window_slots=2, backend="ref"),
    )
    session = ArchesSession(spec)
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        runtime = ArchesRuntime.from_spec(
            spec, engine=legacy_engine, device_policy=session.device_policy
        )
    assert runtime.closed_loop
    assert runtime.switch_config.feature_names == spec.feature_names
    assert runtime.switch_config.window_slots == 2
    hist = runtime.run_batched(
        SCHED, n_slots=6, n_ues=N_UES, key=jax.random.PRNGKey(7)
    )
    np.testing.assert_array_equal(hist.modes, ArchesSession(spec).run().modes)


# -- suggest_gated_capacity (dynamic capacity provisioning) --------------------


def _history_with_modes(modes: np.ndarray) -> BatchedRunHistory:
    return BatchedRunHistory(modes=np.asarray(modes, np.int32), kpms={},
                             outputs={})


def test_suggest_gated_capacity_quantiles():
    # per-slot AI demand: 0, 1, 3, 2 of 4 UEs
    modes = np.ones((4, 4), np.int32)
    modes[1, :1] = 0
    modes[2, :3] = 0
    modes[3, :2] = 0
    hist = _history_with_modes(modes)
    assert suggest_gated_capacity(hist) == 3  # peak demand
    assert suggest_gated_capacity(hist, quantile=0.5) == 2
    assert suggest_gated_capacity(hist, headroom=2) == 4  # clamped to n_ues
    assert suggest_gated_capacity(_history_with_modes(np.ones((3, 2)))) == 0
    with pytest.raises(ValueError, match="quantile"):
        suggest_gated_capacity(hist, quantile=1.5)


def test_suggest_gated_capacity_sharded_buildable():
    """Sharded suggestions must survive ``per_shard_capacity`` validation:
    compaction is shard-local, so the suggestion floors at one slot per
    shard and always splits evenly (the satellite-1 regression)."""
    from repro.core.topology import per_shard_capacity

    # zero demand used to suggest 0, which a sharded engine cannot build
    for n_shards in (2, 4):
        cap = suggest_gated_capacity(
            _history_with_modes(np.ones((5, 8), np.int32)), n_shards=n_shards
        )
        assert cap == n_shards
        assert per_shard_capacity(cap, n_shards) == 1
    # non-uniform demand: the worst shard sizes the whole campaign
    modes = np.ones((4, 8), np.int32)
    modes[:, 4:7] = 0  # shard 1 (UEs 4..7) peaks at 3; shard 0 at 0
    cap = suggest_gated_capacity(_history_with_modes(modes), n_shards=2)
    assert cap == 6 and per_shard_capacity(cap, 2) == 3
    # the n_ues clamp keeps divisibility (n_ues is a shard multiple)
    cap = suggest_gated_capacity(
        _history_with_modes(modes), n_shards=2, headroom=10
    )
    assert cap == 8 and per_shard_capacity(cap, 2) == 4
    # unsharded semantics unchanged: zero demand still suggests 0
    assert suggest_gated_capacity(
        _history_with_modes(np.ones((3, 4), np.int32))
    ) == 0
    with pytest.raises(ValueError, match="divide"):
        suggest_gated_capacity(_history_with_modes(modes), n_shards=3)


def test_suggest_gated_capacity_sharded_never_unbuildable():
    """Property sweep: every (demand, quantile, headroom, shards) draw
    yields a capacity ``per_shard_capacity`` accepts."""
    from repro.core.topology import per_shard_capacity

    rng = np.random.default_rng(0)
    for _ in range(50):
        n_shards = int(rng.choice([1, 2, 4, 8]))
        n_ues = n_shards * int(rng.integers(1, 4))
        modes = rng.integers(0, 2, size=(6, n_ues)).astype(np.int32)
        cap = suggest_gated_capacity(
            _history_with_modes(modes),
            quantile=float(rng.uniform(0.0, 1.0)),
            headroom=int(rng.integers(0, 3)),
            n_shards=n_shards,
        )
        assert 0 <= cap <= n_ues
        if n_shards > 1:
            per_shard_capacity(cap, n_shards)  # must not raise


def _history_with_residency(modes, attached) -> BatchedRunHistory:
    return BatchedRunHistory(
        modes=np.asarray(modes, np.int32), kpms={}, outputs={},
        attached=np.asarray(attached, bool),
    )


def test_suggest_gated_capacity_counts_resident_demand_only():
    """Streaming histories size from *resident* AI demand: a detached
    slot-UE's declared mode claims no gated capacity, so a churn campaign
    over an id universe wider than the bank is sized from concurrent
    residency, not the full stable-id axis."""
    modes = np.zeros((4, 6), np.int32)  # every id declares AI ...
    attached = np.zeros((4, 6), bool)
    attached[:, :2] = True  # ... but only 2 are resident
    attached[2, 2] = True  # one slot peaks at 3 residents
    hist = _history_with_residency(modes, attached)
    assert suggest_gated_capacity(hist) == 3
    assert suggest_gated_capacity(hist, quantile=0.5) == 2
    # an all-detached campaign claims no gated capacity at all
    assert suggest_gated_capacity(
        _history_with_residency(modes, np.zeros((4, 6), bool))
    ) == 0
    # plain histories (attached is None) keep the original semantics
    assert suggest_gated_capacity(_history_with_modes(modes)) == 6


def test_suggest_gated_capacity_resident_demand_property_sweep():
    """Property sweep beside the shard-divisibility one: masking by
    residency never raises the suggestion, stays buildable under shards,
    and at (quantile=1, headroom=0, n_shards=1) equals the realized peak
    resident AI demand exactly."""
    from repro.core.topology import per_shard_capacity

    rng = np.random.default_rng(1)
    for _ in range(50):
        n_shards = int(rng.choice([1, 2, 4, 8]))
        n_ues = n_shards * int(rng.integers(1, 4))
        modes = rng.integers(0, 2, size=(6, n_ues)).astype(np.int32)
        attached = rng.random((6, n_ues)) < 0.6
        kw = dict(
            quantile=float(rng.uniform(0.0, 1.0)),
            headroom=int(rng.integers(0, 3)),
            n_shards=n_shards,
        )
        cap_resident = suggest_gated_capacity(
            _history_with_residency(modes, attached), **kw
        )
        cap_plain = suggest_gated_capacity(_history_with_modes(modes), **kw)
        assert cap_resident <= cap_plain
        assert 0 <= cap_resident <= n_ues
        if n_shards > 1:
            per_shard_capacity(cap_resident, n_shards)  # must not raise
        peak = suggest_gated_capacity(
            _history_with_residency(modes, attached)
        )
        assert peak == int(((modes == 0) & attached).sum(axis=1).max())


def test_legacy_shim_defaults_match_from_spec(legacy_engine):
    """The deprecation shim must forward kwargs equivalently to
    ``from_spec``: the same resolved default/fail-safe modes (from the
    switch config, not a hard-coded 1) and bitwise-equal trajectories —
    warning exactly once."""
    spec = CampaignSpec(
        path="closed_loop", scenario="good_poor_good",
        scenario_args=POOR_ARGS, n_ues=N_UES, n_slots=6, seed=7,
        policies=(PolicySpec(kind="threshold", feature="snr",
                             threshold=18.0, hysteresis=2.0),),
        # default_mode=0 makes the forwarding observable: a shim that
        # hard-codes mode 1 diverges from from_spec here
        switch=SwitchSpec(window_slots=2, backend="ref", default_mode=0),
    )
    session = ArchesSession(spec)
    sw_cfg = spec.switch.to_config(spec.feature_names)
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        shim = ArchesRuntime(
            closed_loop=True, engine=legacy_engine,
            device_policy=session.device_policy, switch_config=sw_cfg,
        )
    dep = [w for w in caught if issubclass(w.category, DeprecationWarning)]
    assert len(dep) == 1, [str(w.message) for w in dep]
    via_spec = ArchesRuntime.from_spec(
        spec, engine=legacy_engine, device_policy=session.device_policy
    )
    assert shim.default_mode == via_spec.default_mode == 0
    assert shim.fail_safe_mode == via_spec.fail_safe_mode == 0
    h1 = shim.run_batched(
        SCHED, n_slots=6, n_ues=N_UES, key=jax.random.PRNGKey(7)
    )
    h2 = via_spec.run_batched(
        SCHED, n_slots=6, n_ues=N_UES, key=jax.random.PRNGKey(7)
    )
    np.testing.assert_array_equal(h1.modes, h2.modes)
    np.testing.assert_array_equal(h1.decisions, h2.decisions)
    np.testing.assert_array_equal(h1.n_switches, h2.n_switches)
    # host-loop construction keeps the historical mode-1 default
    host = ArchesRuntime(lambda m, c, s: (c, None, {}))
    assert host.default_mode == 1 and host.fail_safe_mode == 1


# -- fused / bf16 bank specs ---------------------------------------------------


def test_fused_session_matches_unfused_bitwise(legacy_params):
    modes = np.ones((N_SLOTS, N_UES), np.int32)
    modes[:, 0] = 0
    mk = lambda fused: restored(CampaignSpec(
        path="gated", scenario="good_poor_good", scenario_args=POOR_ARGS,
        n_ues=N_UES, n_slots=N_SLOTS, seed=3,
        modes=tuple(map(tuple, modes)),
        bank=ExpertBankSpec(execution_mode="gated", gated_capacity=1,
                            fused=fused),
    ))
    plain = ArchesSession(mk(False), ai_params=legacy_params).run()
    fused = ArchesSession(mk(True), ai_params=legacy_params).run()
    for k in plain.kpms:
        np.testing.assert_array_equal(plain.kpms[k], fused.kpms[k])
    for k in plain.outputs:
        np.testing.assert_array_equal(plain.outputs[k], fused.outputs[k])


def test_bf16_audited_session_runs_and_records(legacy_params):
    # The audit scores the expert output against the MMSE fail-safe, so the
    # NMSE at a given slot is data-dependent (here ~1-10 on the poor window):
    # a generous threshold must stay quiet, a vanishing one must trip every
    # AI-served slot-UE.
    modes = np.ones((6, N_UES), np.int32)
    modes[:, 0] = 0
    mk = lambda thr: restored(CampaignSpec(
        path="gated", scenario="good_poor_good", scenario_args=POOR_ARGS,
        n_ues=N_UES, n_slots=6, seed=3, modes=tuple(map(tuple, modes)),
        bank=ExpertBankSpec(execution_mode="gated", gated_capacity=1,
                            fused=True, dtype="bfloat16",
                            audit_nmse_threshold=thr),
    ))
    hist = ArchesSession(mk(100.0), ai_params=legacy_params).run()
    assert "audit_tripped" in hist.outputs
    assert hist.audit_tripped_slot_ues == 0  # generous threshold: quiet
    assert hist.overflow_slot_ues == 0
    strict = ArchesSession(mk(1e-12), ai_params=legacy_params).run()
    assert strict.audit_tripped_slot_ues == 6  # every AI-served slot-UE


def test_bank_spec_validates_fused_and_dtype():
    with pytest.raises(ValueError, match="fused"):
        ExpertBankSpec(fused=True)  # concurrent bank cannot fuse
    with pytest.raises(ValueError, match="dtype"):
        ExpertBankSpec(dtype="fp8")
    with pytest.raises(ValueError, match="gated"):
        ExpertBankSpec(audit_nmse_threshold=0.5)
    with pytest.raises(ValueError, match="> 0"):
        ExpertBankSpec(execution_mode="gated", audit_nmse_threshold=-1.0)


def test_suggest_gated_capacity_closes_overflow(legacy_params):
    """An under-provisioned campaign's own telemetry suggests the capacity
    that eliminates its overflow on a rerun."""
    modes = np.ones((4, 3), np.int32)
    modes[2, :3] = 0  # peak demand: all 3 UEs on AI at slot 2
    modes[3, :2] = 0

    def run_with(capacity):
        eng = BatchedPuschPipeline(
            CFG, legacy_params, net=NET,
            execution_mode=ExecutionMode.GATED, gated_capacity=capacity,
        )
        _, traj = eng.run(SCHED, modes, n_slots=4, n_ues=3,
                          key=jax.random.PRNGKey(0))
        return BatchedRunHistory.from_trajectory(modes, traj)

    starved = run_with(1)
    assert starved.overflow_slot_ues == 3  # 2 at slot 2, 1 at slot 3
    cap = suggest_gated_capacity(starved)
    assert cap == 3
    assert run_with(cap).overflow_slot_ues == 0


# -- spec-hash completeness (PR 8 satellite) -----------------------------------
#
# Every dataclass field of ``CampaignSpec`` and its sub-specs must flow
# into the canonical JSON and therefore perturb ``spec_hash`` — a field
# that doesn't is silent provenance loss (two different campaigns sharing
# one hash).  The perturbation table gives each field one *valid*
# alternate value; a new field without a table entry fails loudly.


def _hash_completeness_case():
    import dataclasses as dc

    from repro.core.faults import FaultSpec
    from repro.core.session import ExpertBankSpec, SwitchSpec
    from repro.core.streaming import ChurnSchedule
    from repro.core.topology import TopologySpec

    baseline = CampaignSpec(
        path="closed_loop", scenario="good_poor_good",
        scenario_args=(), n_ues=4, n_slots=8, n_prb=6,
        seed=0, modes=1,
        bank=ExpertBankSpec(execution_mode="gated", gated_capacity=2),
        policies=(PolicySpec(kind="threshold", feature="snr"),),
        policy_assignment=None,
        switch=SwitchSpec(window_slots=2, backend="ref"),
        topology=TopologySpec(n_cells=2),
        churn=ChurnSchedule(n_ue_ids=6, segment_slots=4, initial=(0, 1, 3)),
        faults=FaultSpec(decision_outages=((2, 4),), seed=3,
                         corruption_spans=((1, 3),),
                         telemetry_spans=((5, 6),)),
    )
    alternates = {
        ("CampaignSpec", "path"): "batched",
        ("CampaignSpec", "scenario"): "good",
        ("CampaignSpec", "scenario_args"): (("poor_start", 3),),
        ("CampaignSpec", "n_ues"): 6,
        ("CampaignSpec", "n_slots"): 12,
        ("CampaignSpec", "n_prb"): 12,
        ("CampaignSpec", "n_ant"): 2,
        ("CampaignSpec", "n_layers"): 2,
        ("CampaignSpec", "seed"): 1,
        ("CampaignSpec", "modes"): 0,
        ("CampaignSpec", "bank"): ExpertBankSpec(),
        ("CampaignSpec", "policies"): (
            PolicySpec(kind="threshold", feature="snr", threshold=5.0),
        ),
        ("CampaignSpec", "policy_assignment"): (0, 0, 0, 0),
        ("CampaignSpec", "switch"): SwitchSpec(window_slots=4,
                                               backend="ref"),
        ("CampaignSpec", "feature_names"): tuple(reversed(SELECTED_KPMS)),
        ("CampaignSpec", "rho"): (0.0, 0.25, 0.5, 0.75),
        ("CampaignSpec", "topology"): TopologySpec(n_cells=2, coupling=0.3),
        ("CampaignSpec", "churn"): ChurnSchedule(
            n_ue_ids=6, segment_slots=4, initial=(0, 1)),
        ("CampaignSpec", "faults"): FaultSpec(seed=9),
        ("ExpertBankSpec", "execution_mode"): "concurrent",
        ("ExpertBankSpec", "gated_capacity"): 3,
        ("ExpertBankSpec", "use_pallas_switch"): False,
        ("ExpertBankSpec", "channels"): 4,
        ("ExpertBankSpec", "n_res_blocks"): 2,
        ("ExpertBankSpec", "params_seed"): 1,
        ("ExpertBankSpec", "fused"): True,
        ("ExpertBankSpec", "dtype"): "bfloat16",
        ("ExpertBankSpec", "audit_nmse_threshold"): 0.5,
        ("PolicySpec", "kind"): "tree",
        ("PolicySpec", "depth"): 3,
        ("PolicySpec", "train_slots"): 4,
        ("PolicySpec", "train_ues"): 3,
        ("PolicySpec", "train_scenario"): "good",
        ("PolicySpec", "train_scenario_args"): (("poor_start", 2),),
        ("PolicySpec", "feature"): "rsrp",
        ("PolicySpec", "threshold"): 7.5,
        ("PolicySpec", "hysteresis"): 1.0,
        ("PolicySpec", "mode_above"): 0,
        ("PolicySpec", "mode_below"): 1,
        ("SwitchSpec", "window_slots"): 4,
        ("SwitchSpec", "hysteresis_slots"): 2,
        ("SwitchSpec", "period_slots"): 2,
        ("SwitchSpec", "default_mode"): 0,
        ("SwitchSpec", "backend"): "auto",
        ("SwitchSpec", "ttl_slots"): 8,
        ("TopologySpec", "n_cells"): 1,
        ("TopologySpec", "n_shards"): 1,
        ("TopologySpec", "coupling"): 0.25,
        ("TopologySpec", "cell_noise_offsets_db"): (0.0, 1.0),
        ("TopologySpec", "cell_inr_offsets_db"): (0.0, 1.0),
        ("ChurnSchedule", "n_ue_ids"): 4,
        ("ChurnSchedule", "segment_slots"): 2,
        ("ChurnSchedule", "initial"): (0, 1),
        ("ChurnSchedule", "events"): ((4, 4, "attach"),),
        ("FaultSpec", "seed"): 4,
        ("FaultSpec", "decision_outages"): ((2, 5),),
        ("FaultSpec", "decision_drop_prob"): 0.2,
        ("FaultSpec", "corruption_spans"): ((1, 4),),
        ("FaultSpec", "corruption_kind"): "inf",
        ("FaultSpec", "corruption_scale"): 10.0,
        ("FaultSpec", "corruption_prob"): 0.5,
        ("FaultSpec", "telemetry_spans"): ((5, 7),),
        ("FaultSpec", "telemetry_drop_prob"): 0.3,
        ("FaultSpec", "breaker_trips"): 4,
        ("FaultSpec", "breaker_window"): 5,
        ("FaultSpec", "breaker_cooldown"): 8,
    }
    return baseline, alternates


def test_spec_hash_every_field_perturbs():
    import dataclasses as dc

    baseline, alternates = _hash_completeness_case()
    h0 = spec_hash(baseline)
    sub_attr = {"ExpertBankSpec": "bank", "PolicySpec": None,
                "SwitchSpec": "switch", "TopologySpec": "topology",
                "ChurnSchedule": "churn", "FaultSpec": "faults"}

    def perturbed_spec(owner, field_name, alt):
        if (owner, field_name) == ("CampaignSpec", "policy_assignment"):
            # per-UE assignment is rejected under churn: perturb against
            # a churn-free variant of the baseline instead
            ref = dc.replace(baseline, churn=None)
            spec2 = dc.replace(ref, policy_assignment=alt)
            assert spec_hash(spec2) != spec_hash(ref), (owner, field_name)
            return spec2
        if owner == "CampaignSpec":
            return dc.replace(baseline, **{field_name: alt})
        if owner == "PolicySpec":
            pol = dc.replace(baseline.policies[0], **{field_name: alt})
            return dc.replace(baseline, policies=(pol,))
        attr = sub_attr[owner]
        sub = dc.replace(getattr(baseline, attr), **{field_name: alt})
        return dc.replace(baseline, **{attr: sub})

    from repro.core.faults import FaultSpec
    from repro.core.session import ExpertBankSpec, SwitchSpec
    from repro.core.streaming import ChurnSchedule
    from repro.core.topology import TopologySpec

    for cls in (CampaignSpec, ExpertBankSpec, PolicySpec, SwitchSpec,
                TopologySpec, ChurnSchedule, FaultSpec):
        for f in dc.fields(cls):
            key = (cls.__name__, f.name)
            assert key in alternates, f"no perturbation case for {key}"
            spec2 = perturbed_spec(cls.__name__, f.name, alternates[key])
            # a valid alternate must actually differ from the baseline
            assert spec2 != baseline, key
            assert spec_hash(spec2) != h0, (
                f"{key} does not perturb spec_hash: provenance loss"
            )


def test_spec_hash_canonical_dict_is_field_complete():
    """Structural half of the same guarantee: the canonical dict feeding
    ``spec_hash`` carries every field of every (sub-)spec dataclass."""
    import dataclasses as dc

    baseline, _ = _hash_completeness_case()
    d = baseline.to_dict()
    assert set(d) == {f.name for f in dc.fields(CampaignSpec)}
    for key, obj in (("bank", baseline.bank), ("switch", baseline.switch),
                     ("topology", baseline.topology),
                     ("churn", baseline.churn),
                     ("faults", baseline.faults)):
        assert set(d[key]) == {f.name for f in dc.fields(type(obj))}, key
    assert set(d["policies"][0]) == {
        f.name for f in dc.fields(PolicySpec)
    }
