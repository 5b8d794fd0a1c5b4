"""ArchesSession: one declarative entry point for every campaign shape.

The repo grew four ways to run the switched PHY — ``PuschPipeline.run_slot``
host loops, ``BatchedPuschPipeline.run`` / ``run_closed_loop`` /
``run_perturbed``, and an ``ArchesRuntime`` whose constructor wanted a
different kwarg bundle per mode.  This module replaces that sprawl with a
single declarative surface:

    spec = CampaignSpec(path="closed_loop", scenario="good_poor_good",
                        n_ues=4, n_slots=30,
                        policies=(PolicySpec(kind="tree"),))
    hist = ArchesSession(spec).run()          # -> BatchedRunHistory

``CampaignSpec`` is a frozen dataclass tree (scenario name + args, campaign
shape, expert-bank config, execution path, switch/policy config, seeds)
that round-trips to/from JSON (``to_json`` / ``from_json``; ``spec_hash``
fingerprints it) so benchmark snapshots carry full provenance.
``ArchesSession`` compiles the spec — AI params, expert bank, scenario
schedules from the registry (``repro.phy.scenario``), trained/exported
policies — and dispatches ``run()`` to one of five execution paths:

* ``host`` — the seed architecture: per-slot Python loop, decisions travel
  E3 agent -> dApp -> control inbox (single UE).
* ``batched`` — open-loop multi-UE scan with a declared mode plan.
* ``closed_loop`` — the decision path compiled into the scan
  (``ArchesRuntime.from_spec``); supports per-UE policy heterogeneity via
  ``policies`` + ``policy_assignment`` (a ``PerUEPolicy`` table bank).
* ``gated`` — open-loop batched with compaction-gated expert execution.
* ``perturbed`` — the methodology stage-1 sweep (``rho`` rides the UE axis).

A spec with a ``topology`` (``repro.core.topology.TopologySpec``) runs the
same campaign as ``n_cells`` cells sharded over a 1-D UE device mesh: the
batched/gated/closed-loop/perturbed paths dispatch to the ``shard_map``
entries (per-shard gated compaction, per-cell channel offsets + inter-cell
coupling), and the history gains the per-cell reductions.  On a 1-device
mesh the sharded program is bitwise-equal to the unsharded one.

Every path returns the same ``BatchedRunHistory`` result type, and each is
bitwise-equal on mode trajectories to its legacy entry point (the session
builds the identical program; the test suite asserts it).
"""

from __future__ import annotations

import dataclasses
import enum
import hashlib
import json
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.closed_loop import SwitchConfig, per_ue_policy
from repro.core.expert_bank import ExecutionMode, coerce_enum
from repro.core.faults import FaultSpec
from repro.core.runtime import (
    ArchesRuntime,
    BatchedRunHistory,
    suggest_gated_capacity,
)
from repro.core.streaming import ChurnSchedule
from repro.core.telemetry import SELECTED_KPMS
from repro.core.topology import CellTopology, TopologySpec, per_shard_capacity

# -- execution paths -----------------------------------------------------------


class ExecutionPath(enum.Enum):
    """The campaign shapes ``ArchesSession.run`` dispatches over."""

    HOST = "host"
    BATCHED = "batched"
    CLOSED_LOOP = "closed_loop"
    GATED = "gated"
    PERTURBED = "perturbed"

    @classmethod
    def coerce(cls, value: "ExecutionPath | str") -> "ExecutionPath":
        return coerce_enum(cls, value, "execution path")


# -- spec tree -----------------------------------------------------------------


def _tuplify(x):
    """Recursively normalize to the spec's JSON-stable form: lists/arrays
    become tuples, numpy scalars become Python scalars."""
    if isinstance(x, (list, tuple)):
        return tuple(_tuplify(v) for v in x)
    if isinstance(x, (np.ndarray, jax.Array)):
        return _tuplify(np.asarray(x).tolist())
    if isinstance(x, np.generic):
        return x.item()
    return x


@dataclasses.dataclass(frozen=True)
class ExpertBankSpec:
    """Expert-bank + AI-estimator configuration (one bank per campaign).

    ``execution_mode`` is the bank's ``ExecutionMode`` value
    (``concurrent`` / ``gated`` / ``selected_only``); ``gated_capacity``
    sizes the compacted sub-batch (``None`` == full batch).  The AI expert
    is the paper's ResNet estimator with ``channels`` / ``n_res_blocks``
    and freshly initialized parameters from ``params_seed`` (campaigns
    study switching, not estimator quality; pass trained params to
    ``ArchesSession(ai_params=...)`` to override).

    ``fused=True`` (gated banks) runs the compact -> expert -> scatter hot
    path as one kernel (``repro.kernels.gated_expert``) on a TPU — the same
    expert to f32 rounding, with fewer launches and no materialized
    sub-batch; off the chip it runs the unfused triple.  ``dtype`` selects the AI expert's GEMM operand precision
    (``"float32"`` — bitwise baseline — or ``"bfloat16"``), and
    ``audit_nmse_threshold`` arms the in-scan accuracy audit: a served
    UE whose gated output diverges from the fail-safe baseline by more
    than this NMSE (or goes NaN) reverts to the baseline and is flagged in
    the trajectory's ``audit_tripped`` leaf — the guard rail that makes
    reduced precision deployable.
    """

    execution_mode: str = "concurrent"
    gated_capacity: int | None = None
    use_pallas_switch: bool = True
    channels: int = 8
    n_res_blocks: int = 1
    params_seed: int = 0
    fused: bool = False
    dtype: str = "float32"
    audit_nmse_threshold: float | None = None

    def __post_init__(self):
        # normalize enum members to their JSON-stable string value
        object.__setattr__(
            self,
            "execution_mode",
            ExecutionMode.coerce(self.execution_mode).value,
        )
        if self.dtype not in ("float32", "bfloat16"):
            raise ValueError(
                f"dtype {self.dtype!r}; one of 'float32', 'bfloat16'"
            )
        mode = ExecutionMode.coerce(self.execution_mode)
        if self.fused and mode is not ExecutionMode.GATED:
            raise ValueError("fused=True requires execution_mode='gated'")
        if self.audit_nmse_threshold is not None:
            if mode is not ExecutionMode.GATED:
                raise ValueError(
                    "audit_nmse_threshold requires execution_mode='gated'"
                )
            if not self.audit_nmse_threshold > 0:
                raise ValueError(
                    f"audit_nmse_threshold {self.audit_nmse_threshold} "
                    "must be > 0"
                )


@dataclasses.dataclass(frozen=True)
class PolicySpec:
    """One switching policy, declaratively.

    ``kind="tree"`` — the paper's Gini decision tree, trained by profiling
    both experts on ``train_scenario`` + ``train_scenario_args`` for
    ``train_slots`` x ``train_ues`` slots per expert; deterministic given
    the spec (the profiling campaign uses the engine's fixed key
    derivation).  ``train_scenario=None`` defaults to the campaign
    scenario when that is homogeneous; for per-UE campaigns it falls back
    to ``good_poor_good`` with its poor window scaled into the training
    horizon (so short campaigns still see both labels — training on a
    single condition class yields a constant, never-switching tree).

    ``kind="threshold"`` — the single-KPM gate with hysteresis: ``feature``
    compared against ``threshold`` +- ``hysteresis``.
    """

    kind: str = "tree"
    depth: int = 2
    train_slots: int | None = None  # default: the campaign's n_slots
    train_ues: int = 2
    train_scenario: str | None = None
    train_scenario_args: tuple = ()
    feature: str = "snr"
    threshold: float = 18.0
    hysteresis: float = 0.0
    mode_above: int = 1
    mode_below: int = 0

    def __post_init__(self):
        if self.kind not in ("tree", "threshold"):
            raise ValueError(f"unknown policy kind {self.kind!r}")
        object.__setattr__(
            self, "train_scenario_args", _tuplify(self.train_scenario_args)
        )


@dataclasses.dataclass(frozen=True)
class SwitchSpec:
    """Declarative form of ``SwitchConfig`` (+ the host loop's TTL).

    ``backend`` selects the in-scan tree evaluator (device paths only; the
    host dApp calls the policy object directly).  ``hysteresis_slots`` is
    an in-scan capability: the host path rejects values > 1 rather than
    silently ignoring them.
    """

    window_slots: int = 8
    hysteresis_slots: int = 1
    period_slots: int = 1
    default_mode: int = 1
    backend: str = "auto"
    # fail-safe decay horizon: the host loop's SlotSwitchState TTL, and —
    # under a FaultSpec — the device decision-age counter's decay threshold
    ttl_slots: int = 16

    def to_config(self, feature_names: Sequence[str]) -> SwitchConfig:
        return SwitchConfig(
            feature_names=tuple(feature_names),
            window_slots=self.window_slots,
            hysteresis_slots=self.hysteresis_slots,
            period_slots=self.period_slots,
            default_mode=self.default_mode,
            backend=self.backend,
            ttl_slots=self.ttl_slots,
        )


@dataclasses.dataclass(frozen=True)
class CampaignSpec:
    """A whole campaign as data: serialize it, hash it, run it.

    ``scenario`` names a registry entry (``repro.phy.scenario``);
    ``scenario_args`` are its factory kwargs as ``(key, value)`` pairs
    (kept as pairs so the spec stays hashable and JSON-stable).  ``modes``
    is the open-loop mode plan for the batched/gated paths — a scalar or a
    nested tuple accepted by ``normalize_modes``.  ``policies`` +
    ``policy_assignment`` declare the decision side: one entry == every UE
    runs it; several + an ``(n_ues,)`` assignment == per-UE heterogeneity
    in the closed loop.  ``rho`` is the perturbation grid of the
    methodology path (it rides the UE axis, so ``n_ues == len(rho)``).
    ``topology`` (a ``TopologySpec`` or its dict form) shards the campaign
    as a multi-cell layout over the UE device mesh.

    ``churn`` (a ``repro.core.streaming.ChurnSchedule`` or its dict form)
    turns the campaign into an epoch-chunked *streaming* run: ``n_ues``
    becomes the bank capacity, the UE axis of the history becomes the
    schedule's stable-id universe, and ``run()`` dispatches to
    ``ArchesSession.run_streaming``.

    ``faults`` (a ``repro.core.faults.FaultSpec`` or its dict form) injects
    control-plane decision loss, expert-output corruption and telemetry
    loss into the device paths (batched / gated / closed loop, monolithic
    or streaming), arming the in-scan degradation ladder: TTL fail-safe
    decay, ``isfinite`` health screen + circuit breaker, and rolling-window
    masking.  A zero-fault spec is bitwise-identical to ``faults=None``.
    """

    path: str = "batched"
    scenario: str = "good_poor_good"
    scenario_args: tuple = ()
    n_ues: int = 4
    n_slots: int = 30
    n_prb: int = 24
    # the radio: gNB receive antennas and the UE's uplink layers (1 or 2:
    # DMRS ports 0-1 of CDM group 0, repro.phy.dmrs)
    n_ant: int = 4
    n_layers: int = 1
    seed: int = 0
    modes: Any = 1
    bank: ExpertBankSpec = dataclasses.field(default_factory=ExpertBankSpec)
    policies: tuple = ()
    policy_assignment: tuple | None = None
    switch: SwitchSpec = dataclasses.field(default_factory=SwitchSpec)
    feature_names: tuple = SELECTED_KPMS
    rho: tuple | None = None
    # multi-cell sharded layout (None == single cell on one device)
    topology: TopologySpec | None = None
    # attach/detach schedule (None == monolithic fixed-grid campaign)
    churn: ChurnSchedule | None = None
    # fault-injection campaign (None == happy path, no fault machinery)
    faults: FaultSpec | None = None

    def __post_init__(self):
        # normalize an enum member to its JSON-stable string value
        object.__setattr__(self, "path", ExecutionPath.coerce(self.path).value)
        if self.topology is not None and not isinstance(
            self.topology, TopologySpec
        ):
            object.__setattr__(
                self, "topology", TopologySpec(**dict(self.topology))
            )
        if self.churn is not None and not isinstance(
            self.churn, ChurnSchedule
        ):
            object.__setattr__(
                self, "churn", ChurnSchedule(**dict(self.churn))
            )
        if self.faults is not None and not isinstance(
            self.faults, FaultSpec
        ):
            object.__setattr__(
                self, "faults", FaultSpec(**dict(self.faults))
            )
        for name in ("scenario_args", "policies", "feature_names"):
            object.__setattr__(self, name, _tuplify(getattr(self, name)))
        object.__setattr__(self, "modes", _tuplify(self.modes))
        for name in ("policy_assignment", "rho"):
            v = getattr(self, name)
            if v is not None:
                object.__setattr__(self, name, _tuplify(v))
        if self.n_ues < 1 or self.n_slots < 1:
            raise ValueError("n_ues and n_slots must be >= 1")
        if self.n_ant < 1:
            raise ValueError(f"n_ant={self.n_ant}: must be >= 1")
        if self.n_layers not in (1, 2):
            raise ValueError(
                f"n_layers={self.n_layers}: one of 1, 2 (more layers need "
                "DMRS CDM group 1, which is not built)"
            )
        if self.n_layers > self.n_ant:
            raise ValueError(
                f"n_layers={self.n_layers} exceeds n_ant={self.n_ant}: the "
                "receiver cannot separate more layers than it has antennas"
            )
        for k, _ in self.scenario_args:
            if not isinstance(k, str):
                raise ValueError("scenario_args must be (name, value) pairs")
        if self.policy_assignment is not None:
            if not self.policies:
                raise ValueError(
                    "policy_assignment indexes spec.policies, which is empty"
                )
            if len(self.policy_assignment) != self.n_ues:
                raise ValueError(
                    f"policy_assignment has {len(self.policy_assignment)} "
                    f"entries for n_ues={self.n_ues}"
                )
            if not all(
                0 <= int(i) < len(self.policies)
                for i in self.policy_assignment
            ):
                raise ValueError("policy_assignment indexes out of range")
        # path/bank mismatches fail at spec construction (so also at
        # ``from_json``) with a clear message instead of a trace-time shape
        # error or a silently mispriced campaign
        bank_mode = ExecutionMode.coerce(self.bank.execution_mode)
        path = self.execution_path
        if path is ExecutionPath.GATED and bank_mode is (
            ExecutionMode.SELECTED_ONLY
        ):
            raise ValueError(
                "path='gated' with a 'selected_only' bank would silently "
                "run un-gated at the concurrent cost envelope; declare the "
                "bank 'gated' (or 'concurrent', which the path normalizes)"
            )
        if path is ExecutionPath.PERTURBED and bank_mode is not (
            ExecutionMode.CONCURRENT
        ):
            raise ValueError(
                f"path='perturbed' ignores the expert bank (stage 1 is "
                f"MMSE-only by construction); a {bank_mode.value!r} bank "
                "spec would never take effect — drop it"
            )
        if path is ExecutionPath.HOST and bank_mode is ExecutionMode.GATED:
            raise ValueError(
                "gated execution is the batched path: the host loop serves "
                "one UE and has no sub-batch to compact"
            )
        if self.topology is not None:
            if path is ExecutionPath.HOST:
                raise ValueError(
                    "a sharded topology needs a batched path: the host "
                    "loop serves one UE on one device"
                )
            if self.n_ues % self.topology.n_cells:
                raise ValueError(
                    f"topology n_cells={self.topology.n_cells} does not "
                    f"divide n_ues={self.n_ues}"
                )
        if self.churn is not None:
            if path not in (
                ExecutionPath.BATCHED,
                ExecutionPath.GATED,
                ExecutionPath.CLOSED_LOOP,
            ):
                raise ValueError(
                    f"churn campaigns stream the batched scan; "
                    f"path={self.path!r} has no segmented form (the host "
                    "loop serves one pinned UE, the perturbed sweep has no "
                    "notion of churn)"
                )
            if self.policy_assignment is not None:
                raise ValueError(
                    "policy_assignment is bank-slot-indexed; a churn "
                    "campaign re-packs bank slots, so per-UE policy "
                    "heterogeneity under churn is not supported — declare "
                    "one shared policy"
                )
            # capacity/divisibility/consistency all fail at spec-compile
            # time, never as a scan shape error mid-campaign
            self.churn.validate(
                self.n_slots,
                self.n_ues,
                n_cells=(
                    1 if self.topology is None else self.topology.n_cells
                ),
            )
        if self.faults is not None and path not in (
            ExecutionPath.BATCHED,
            ExecutionPath.GATED,
            ExecutionPath.CLOSED_LOOP,
        ):
            raise ValueError(
                f"fault injection targets the device scan; "
                f"path={self.path!r} has no in-scan fault machinery (the "
                "host loop models dApp failure via DApp.fail(), the "
                "perturbed sweep is MMSE-only)"
            )

    # -- derived views --------------------------------------------------------

    @property
    def execution_path(self) -> ExecutionPath:
        return ExecutionPath.coerce(self.path)

    @property
    def scenario_kwargs(self) -> dict:
        return dict(self.scenario_args)

    # -- JSON round trip -------------------------------------------------------

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: dict) -> "CampaignSpec":
        d = dict(d)
        if "bank" in d and not isinstance(d["bank"], ExpertBankSpec):
            d["bank"] = ExpertBankSpec(**d["bank"])
        if "switch" in d and not isinstance(d["switch"], SwitchSpec):
            d["switch"] = SwitchSpec(**d["switch"])
        if d.get("topology") is not None and not isinstance(
            d["topology"], TopologySpec
        ):
            d["topology"] = TopologySpec(**d["topology"])
        if d.get("churn") is not None and not isinstance(
            d["churn"], ChurnSchedule
        ):
            d["churn"] = ChurnSchedule(**d["churn"])
        if d.get("faults") is not None and not isinstance(
            d["faults"], FaultSpec
        ):
            d["faults"] = FaultSpec.from_dict(d["faults"])
        if "policies" in d:
            d["policies"] = tuple(
                p if isinstance(p, PolicySpec) else PolicySpec(**p)
                for p in d["policies"]
            )
        return cls(**d)

    def to_json(self) -> str:
        """Canonical JSON (sorted keys) — the provenance string."""
        return json.dumps(self.to_dict(), sort_keys=True)

    @classmethod
    def from_json(cls, s: str) -> "CampaignSpec":
        return cls.from_dict(json.loads(s))


def spec_hash(spec: CampaignSpec) -> str:
    """Short stable fingerprint of a spec's canonical JSON."""
    return hashlib.sha256(spec.to_json().encode()).hexdigest()[:16]


def as_streaming_spec(
    spec: CampaignSpec, *, max_segment_slots: int = 8
) -> CampaignSpec:
    """Lift a monolithic campaign spec into its streaming form.

    A spec that already declares ``churn`` is returned unchanged.  A
    churn-free batched/gated/closed-loop spec gains a synthesized
    full-residency ``ChurnSchedule`` (every bank slot attached at slot 0,
    no events) whose segment length is the largest divisor of ``n_slots``
    that is ``<= max_segment_slots`` — so the epoch-chunked driver can
    execute it in checkpointable segments while staying bitwise-equal to
    the monolithic ``ArchesSession.run()`` on every leaf (the zero-churn
    contract).  This is how ``repro.service.CampaignService`` makes every
    submitted campaign crash-resumable, churn or not.
    """
    if spec.churn is not None:
        return spec
    if spec.execution_path not in (
        ExecutionPath.BATCHED, ExecutionPath.GATED, ExecutionPath.CLOSED_LOOP
    ):
        raise ValueError(
            f"path={spec.path!r} has no streaming form (the host loop "
            "serves one pinned UE, the perturbed sweep has no segmented "
            "driver)"
        )
    if max_segment_slots < 1:
        raise ValueError(f"max_segment_slots {max_segment_slots} must be >= 1")
    seg = max(
        d for d in range(1, min(max_segment_slots, spec.n_slots) + 1)
        if spec.n_slots % d == 0
    )
    return dataclasses.replace(
        spec,
        churn=ChurnSchedule(
            n_ue_ids=spec.n_ues,
            segment_slots=seg,
            initial=tuple(range(spec.n_ues)),
        ),
    )


# -- the session façade --------------------------------------------------------


class ArchesSession:
    """Compile a ``CampaignSpec`` into runnable components and run it.

    Construction is lazy-but-cached: the slot config and scenario resolve
    immediately (cheap, and validation fails fast); AI params, engines and
    trained policies build on first use and are reused across ``run()``
    calls.  ``run()`` always returns a ``BatchedRunHistory`` — host-loop
    campaigns are lifted to the ``(n_slots, 1)`` shape — so downstream
    tooling (KPM series, ``suggest_gated_capacity``, benchmark snapshots)
    is path-agnostic.
    """

    def __init__(
        self,
        spec: CampaignSpec,
        *,
        ai_params: Any = None,
        host_policies: Sequence | None = None,
        engine: Any = None,
    ):
        """Overrides (all optional) let a caller reuse pre-built components:
        trained ``ai_params``, already-fitted ``host_policies``, or a
        compiled ``engine`` (which must match the spec's bank — the session
        trusts it)."""
        from repro.phy.nr import SlotConfig
        from repro.phy.scenario import get_scenario

        self.spec = spec
        self.path = spec.execution_path
        #: resolved sharded layout (None == single-device, single-cell)
        self.cell_topology = (
            CellTopology.build(spec.topology, spec.n_ues)
            if spec.topology is not None
            else None
        )
        self._validate()
        self.cfg = SlotConfig(
            n_prb=spec.n_prb, n_ant=spec.n_ant, n_layers=spec.n_layers
        )
        scenario = get_scenario(spec.scenario)
        # streaming campaigns instantiate per-UE scenarios over the
        # *stable-id* universe: channel conditions follow the UE identity,
        # not the bank slot it happens to be packed into
        n_scenario_ues = (
            spec.churn.n_ue_ids if spec.churn is not None else spec.n_ues
        )
        self.schedule = scenario.schedule(
            n_ues=n_scenario_ues if scenario.per_ue else None,
            **spec.scenario_kwargs,
        )
        self._ai_params = ai_params
        self._host_policies = (
            tuple(host_policies) if host_policies is not None else None
        )
        self._engine = engine
        self._train_engine = None
        self._pipeline = None
        self._device_policy = None

    # -- validation ------------------------------------------------------------

    def _validate(self) -> None:
        from repro.phy.scenario import get_scenario

        spec, path = self.spec, self.path
        bank_mode = ExecutionMode.coerce(spec.bank.execution_mode)
        if len(spec.policies) > 1 and spec.policy_assignment is None:
            raise ValueError(
                "several policies need an explicit policy_assignment "
                "(which UE runs which table)"
            )
        if path is ExecutionPath.HOST:
            if spec.n_ues != 1:
                raise ValueError("the host loop serves one UE: n_ues must be 1")
            if not spec.policies:
                raise ValueError("the host loop needs one PolicySpec")
            if get_scenario(spec.scenario).per_ue:
                raise ValueError(
                    f"scenario {spec.scenario!r} is per-UE; the host path "
                    "needs a homogeneous scenario"
                )
            if spec.switch.hysteresis_slots != 1:
                raise ValueError(
                    "the host E3/dApp loop has no hysteresis streak; "
                    "hysteresis_slots > 1 needs the closed_loop path"
                )
        if path is ExecutionPath.CLOSED_LOOP and not spec.policies:
            raise ValueError("closed_loop needs at least one PolicySpec")
        if path is ExecutionPath.PERTURBED:
            if spec.rho is None:
                raise ValueError("perturbed needs a rho grid")
            if len(spec.rho) != spec.n_ues:
                raise ValueError(
                    f"rho rides the UE axis: len(rho)={len(spec.rho)} "
                    f"must equal n_ues={spec.n_ues}"
                )
        # the path name is the declaration: "gated" implies a gated bank
        # (normalized on the session, never mutating the user's spec)
        self.bank_spec = (
            dataclasses.replace(spec.bank, execution_mode="gated")
            if path is ExecutionPath.GATED
            and bank_mode is ExecutionMode.CONCURRENT
            else spec.bank
        )
        # path='gated' + selected_only already raised in CampaignSpec
        # __post_init__, so after normalization the gated path always
        # carries a gated bank
        assert (
            path is not ExecutionPath.GATED
            or ExecutionMode.coerce(self.bank_spec.execution_mode)
            is ExecutionMode.GATED
        )
        if self.cell_topology is not None:
            topo = self.cell_topology
            if (
                ExecutionMode.coerce(self.bank_spec.execution_mode)
                is ExecutionMode.GATED
                and self.bank_spec.gated_capacity is not None
            ):
                # fail at spec-compile time, not as a scan shape error
                per_shard_capacity(
                    self.bank_spec.gated_capacity, topo.n_shards
                )
            declared_cells = spec.scenario_kwargs.get("n_cells")
            if declared_cells is None:
                # a cell-aware scenario factory not passed n_cells uses its
                # own default — that count must agree with the topology too
                import inspect

                p = inspect.signature(
                    get_scenario(spec.scenario).factory
                ).parameters.get("n_cells")
                if p is not None and p.default is not inspect.Parameter.empty:
                    declared_cells = p.default
            if declared_cells is not None and declared_cells != topo.n_cells:
                raise ValueError(
                    f"scenario lays out n_cells={declared_cells} but the "
                    f"topology lays out {topo.n_cells} cells — one cell "
                    "count per campaign (pass n_cells in scenario_args)"
                )

    # -- compiled components ---------------------------------------------------

    @property
    def net(self):
        from repro.phy.ai_estimator import AiEstimatorConfig

        return AiEstimatorConfig(
            channels=self.bank_spec.channels,
            n_res_blocks=self.bank_spec.n_res_blocks,
        )

    @property
    def ai_params(self):
        if self._ai_params is None:
            from repro.phy.ai_estimator import init_params

            self._ai_params = init_params(
                jax.random.PRNGKey(self.bank_spec.params_seed), self.cfg, self.net
            )
        return self._ai_params

    def _engine_capacity(self, campaign_capacity: int | None) -> int | None:
        """The engine-level gated capacity for a campaign-wide one.

        Compaction is shard-local under a topology, so the engine's
        capacity is the per-shard share of the campaign capacity.
        """
        if (
            campaign_capacity is None
            or self.cell_topology is None
            or ExecutionMode.coerce(self.bank_spec.execution_mode)
            is not ExecutionMode.GATED
        ):
            return campaign_capacity
        return per_shard_capacity(
            campaign_capacity, self.cell_topology.n_shards
        )

    def _build_engine(self, campaign_capacity: int | None):
        from repro.phy.pipeline import BatchedPuschPipeline

        bank = self.bank_spec
        return BatchedPuschPipeline(
            self.cfg,
            self.ai_params,
            net=self.net,
            execution_mode=ExecutionMode.coerce(bank.execution_mode),
            use_pallas_switch=bank.use_pallas_switch,
            gated_capacity=self._engine_capacity(campaign_capacity),
            fused_gated=bank.fused,
            expert_dtype=bank.dtype,
            audit_nmse_threshold=bank.audit_nmse_threshold,
        )

    @property
    def engine(self):
        """The batched multi-UE engine configured per the bank spec."""
        if self._engine is None:
            self._engine = self._build_engine(self.bank_spec.gated_capacity)
        return self._engine

    @property
    def pipeline(self):
        """The single-UE host pipeline (host path only)."""
        if self._pipeline is None:
            from repro.phy.pipeline import PuschPipeline

            bank = self.bank_spec
            self._pipeline = PuschPipeline(
                self.cfg,
                self.ai_params,
                net=self.net,
                execution_mode=ExecutionMode.coerce(bank.execution_mode),
                use_pallas_switch=bank.use_pallas_switch,
            )
        return self._pipeline

    def _training_engine(self):
        """A concurrent engine for expert profiling (shared when possible)."""
        mode = ExecutionMode.coerce(self.bank_spec.execution_mode)
        if mode is ExecutionMode.CONCURRENT:
            return self.engine
        if self._train_engine is None:
            from repro.phy.pipeline import BatchedPuschPipeline

            self._train_engine = BatchedPuschPipeline(
                self.cfg,
                self.ai_params,
                net=self.net,
                execution_mode=ExecutionMode.CONCURRENT,
                use_pallas_switch=self.bank_spec.use_pallas_switch,
            )
        return self._train_engine

    def _train_schedule(self, ps: PolicySpec):
        from repro.phy.scenario import get_scenario, good_poor_good_schedule

        if ps.train_scenario is not None:
            sc = get_scenario(ps.train_scenario)
            if sc.per_ue:
                raise ValueError(
                    f"train_scenario {ps.train_scenario!r} is per-UE; "
                    "policies train on one labelled condition stream"
                )
            return sc.schedule(**dict(ps.train_scenario_args))
        if callable(self.schedule):  # homogeneous campaign scenario
            return self.schedule
        # heterogeneous campaign: fall back to the paper's Fig. 9 stream
        # with the poor window scaled into the training horizon — the
        # default 100..200 window would sit past a short campaign's end and
        # label every slot 'good', training a constant tree
        n = ps.train_slots or self.spec.n_slots
        return good_poor_good_schedule(poor_start=n // 3, poor_end=2 * n // 3)

    @property
    def host_policies(self) -> tuple:
        """The host policy objects, trained/built per ``spec.policies``."""
        if self._host_policies is None:
            from repro.core.policy import ThresholdPolicy, profile_and_fit_tree

            built = []
            for ps in self.spec.policies:
                if ps.kind == "threshold":
                    built.append(
                        ThresholdPolicy(
                            feature_idx=self.spec.feature_names.index(ps.feature),
                            threshold=ps.threshold,
                            hysteresis=ps.hysteresis,
                            mode_above=ps.mode_above,
                            mode_below=ps.mode_below,
                        )
                    )
                else:
                    built.append(
                        profile_and_fit_tree(
                            self._training_engine(),
                            self._train_schedule(ps),
                            n_slots=ps.train_slots or self.spec.n_slots,
                            n_ues=ps.train_ues,
                            depth=ps.depth,
                            feature_names=self.spec.feature_names,
                        )
                    )
            self._host_policies = tuple(built)
        return self._host_policies

    @property
    def device_policy(self):
        """Exported device tables: one table, or a per-UE ``PerUEPolicy``."""
        if self._device_policy is None:
            spec = self.spec
            tables = tuple(p.to_device() for p in self.host_policies)
            if len(tables) == 1 and spec.policy_assignment is None:
                self._device_policy = tables[0]
            else:
                if spec.policy_assignment is None:
                    # only reachable via a host_policies override longer
                    # than spec.policies (spec-level specs validate earlier)
                    raise ValueError(
                        "several policies need an explicit policy_assignment"
                    )
                self._device_policy = per_ue_policy(
                    tables, spec.policy_assignment
                )
        return self._device_policy

    def host_replay(self, hist: BatchedRunHistory) -> dict:
        """Replay a closed-loop history through the host policy objects.

        The equivalence oracle, packaged with the session's own feature
        order, switch config and per-UE assignment so callers (quickstart,
        benchmarks) cannot drift from the in-scan stacking: returns
        ``host_replay_closed_loop``'s dict; compare ``hist.modes`` against
        ``result["active_mode"]`` for the bitwise contract.
        """
        from repro.core.closed_loop import host_replay_closed_loop

        spec = self.spec
        feats = np.stack(
            [hist.kpms[n] for n in spec.feature_names], axis=-1
        ).astype(np.float32)
        sw_cfg = spec.switch.to_config(spec.feature_names)
        trips = None
        if spec.faults is not None:
            # the device's recorded health/audit trips feed the oracle's
            # circuit breaker — the trip *predicate* runs on device (it
            # needs the expert outputs); the breaker state machine replays
            # on the host from the recorded trip record
            trips = np.zeros(hist.modes.shape, bool)
            for k in ("health_tripped", "audit_tripped"):
                if k in hist.outputs:
                    trips |= np.asarray(hist.outputs[k]) > 0
        attached = getattr(hist, "attached", None)
        if len(self.host_policies) == 1 and spec.policy_assignment is None:
            return host_replay_closed_loop(
                self.host_policies[0], feats, sw_cfg,
                faults=spec.faults, trips=trips, attached=attached,
            )
        assignment = (
            spec.policy_assignment
            if spec.policy_assignment is not None
            else (0,) * spec.n_ues
        )
        return host_replay_closed_loop(
            list(self.host_policies), feats, sw_cfg, policy_idx=assignment,
            faults=spec.faults, trips=trips, attached=attached,
        )

    # -- execution -------------------------------------------------------------

    def run(self, *, auto_capacity: bool = False) -> BatchedRunHistory:
        """Execute the campaign; one result type for every path.

        ``auto_capacity=True`` (gated banks only) sizes ``gated_capacity``
        from the campaign's own demand before the main run instead of
        trusting the declared knob: open-loop paths read peak demand
        straight off the declared mode plan (no extra compile); the closed
        loop runs a full-capacity pre-pass and feeds its realized demand to
        ``suggest_gated_capacity`` (two compiles, both host-driven).  The
        gated bank is re-provisioned with the chosen campaign-wide capacity
        ``K`` (rounded up to a per-shard-equal split under a topology) and
        the history records it in ``provisioned_capacity``.
        """
        if auto_capacity:
            return self._run_auto_capacity()
        if self.spec.churn is not None:
            return self.run_streaming()
        runner = {
            ExecutionPath.HOST: self._run_host,
            ExecutionPath.BATCHED: self._run_open_loop,
            ExecutionPath.GATED: self._run_open_loop,
            ExecutionPath.CLOSED_LOOP: self._run_closed_loop,
            ExecutionPath.PERTURBED: self._run_perturbed,
        }[self.path]
        return runner()

    def _run_auto_capacity(self) -> BatchedRunHistory:
        spec = self.spec
        if ExecutionMode.coerce(self.bank_spec.execution_mode) is not (
            ExecutionMode.GATED
        ):
            raise ValueError(
                "auto_capacity sizes a gated bank; this campaign's bank is "
                f"{self.bank_spec.execution_mode!r}"
            )
        if self.path in (ExecutionPath.GATED, ExecutionPath.BATCHED):
            # open loop: demand is the declared plan — no pre-pass needed.
            # A churn campaign's plan lives on the stable-id axis and only
            # *resident* slot-UEs claim capacity: the residency leaf rides
            # the demand history so suggest_gated_capacity counts resident
            # demand, not the (possibly much wider) id universe.
            from repro.phy.pipeline import normalize_modes

            n_axis = (
                spec.churn.n_ue_ids if spec.churn is not None else spec.n_ues
            )
            demand_hist = BatchedRunHistory(
                modes=np.asarray(
                    normalize_modes(
                        np.asarray(spec.modes, np.int32),
                        spec.n_slots, n_axis,
                    )
                ),
                kpms={}, outputs={},
                attached=(
                    None
                    if spec.churn is None
                    else spec.churn.residency(spec.n_slots)
                ),
            )
        elif self.path is ExecutionPath.CLOSED_LOOP:
            # pre-pass at full capacity (overflow impossible), then size
            # from the demand the decisions actually realized
            pre_spec = dataclasses.replace(
                spec,
                bank=dataclasses.replace(spec.bank, gated_capacity=None),
            )
            pre = ArchesSession(
                pre_spec,
                ai_params=self.ai_params,
                host_policies=self.host_policies,
            )
            demand_hist = pre.run()
        else:
            raise ValueError(
                f"auto_capacity does not apply to path={spec.path!r}"
            )
        n_shards = (
            1 if self.cell_topology is None else self.cell_topology.n_shards
        )
        if spec.churn is not None:
            # streaming: the demand axis is the stable-id universe, whose
            # width need not split across bank shards — size from the
            # campaign-wide *resident* demand, round up to a
            # per-shard-equal split and clip to the bank.  A shard-local
            # spike beyond its split overflows to the fail-safe expert,
            # the gated path's standing safe degradation.
            cap = suggest_gated_capacity(demand_hist)
            cap = min(
                max(-(-cap // n_shards), 1) * n_shards, spec.n_ues
            )
        else:
            # compaction is shard-local: provisioning covers the worst
            # *shard's* peak demand (a shard-local spike overflows even
            # when the campaign-wide count would fit), with >= 1 slot per
            # shard
            cap = max(
                suggest_gated_capacity(demand_hist, n_shards=n_shards),
                n_shards,
            )
        self._engine = self._build_engine(cap)
        if spec.churn is not None:
            runner = self.run_streaming
        elif self.path is ExecutionPath.CLOSED_LOOP:
            runner = self._run_closed_loop
        else:
            runner = self._run_open_loop
        return dataclasses.replace(runner(), provisioned_capacity=cap)

    def run_streaming(
        self,
        churn=None,
        *,
        checkpoint_dir=None,
        resume_from=None,
        max_segments=None,
        on_segment=None,
        pipeline=True,
        checkpoint_format="delta",
        stats=None,
    ) -> BatchedRunHistory:
        """Epoch-chunked streaming campaign: attach/detach under churn.

        Executes the compiled scan in fixed-length segments over the
        ``n_ues``-slot bank with a host-side admission pass at segment
        boundaries (``repro.core.streaming``).  ``churn`` overrides the
        spec's schedule for this run (a ``ChurnSchedule`` or its dict
        form); with a different schedule the campaign is re-validated and
        re-instantiated against it while reusing this session's compiled
        components (AI params, engine, trained policies) — the compiled
        segment program depends only on shapes, not on the schedule.

        Crash resumability: ``checkpoint_dir`` snapshots the loop state
        atomically after every completed segment — as O(segment)
        manifest-chained deltas by default, or the legacy O(campaign)
        full snapshot with ``checkpoint_format="monolithic"``;
        ``resume_from`` restarts from the latest complete checkpoint in
        that directory (delta chains replayed, legacy monolithic
        directories loadable unchanged), bitwise-equal to the
        uninterrupted run.  ``max_segments`` stops early after that many
        segments (the deterministic kill hook the resume tests use).
        ``on_segment`` receives a ``repro.core.streaming.SegmentEvent``
        after every completed (and, when armed, checkpointed) segment;
        returning truthy stops the drive loop at that boundary — the
        graceful-drain primitive ``repro.service.CampaignService`` builds
        on.  ``pipeline=False`` selects the serial reference executor
        (default: device scans overlap host assembly/checkpointing,
        bitwise-identical either way); ``stats`` (a dict) receives the
        per-phase wall-time breakdown.

        Returns a ``BatchedRunHistory`` on the *stable-id* axis: detached
        slot-UEs carry the ``-1`` mode sentinel and zeroed KPMs/outputs,
        and the ``attached`` / ``bank_slot`` leaves record residency and
        the serving bank slot per (slot, id).
        """
        from repro.core import streaming

        kw = dict(
            checkpoint_dir=checkpoint_dir,
            resume_from=resume_from,
            max_segments=max_segments,
            on_segment=on_segment,
            pipeline=pipeline,
            checkpoint_format=checkpoint_format,
            stats=stats,
        )
        if churn is not None:
            if not isinstance(churn, streaming.ChurnSchedule):
                churn = streaming.ChurnSchedule(**dict(churn))
            if churn != self.spec.churn:
                spec = dataclasses.replace(self.spec, churn=churn)
                fresh = ArchesSession(
                    spec,
                    ai_params=self._ai_params,
                    host_policies=self._host_policies,
                    engine=self._engine,
                )
                return streaming.run_streaming(fresh, **kw)
        if self.spec.churn is None:
            raise ValueError(
                "run_streaming needs a ChurnSchedule: set spec.churn or "
                "pass churn=..."
            )
        return streaming.run_streaming(self, **kw)

    def _run_host(self) -> BatchedRunHistory:
        from repro.core.dapp import DApp, connect_dapp
        from repro.core.e3 import E3Agent

        spec = self.spec
        agent = E3Agent()
        # the single UE may still be assigned any declared policy table
        pol = spec.policy_assignment[0] if spec.policy_assignment else 0
        dapp = DApp(
            self.host_policies[pol],
            spec.feature_names,
            window_slots=spec.switch.window_slots,
            period_slots=spec.switch.period_slots,
        )
        connect_dapp(agent, dapp)
        runtime = ArchesRuntime(
            self.pipeline.make_slot_fn(self.schedule),
            agent,
            default_mode=spec.switch.default_mode,
            fail_safe_mode=spec.switch.default_mode,
            ttl_slots=spec.switch.ttl_slots,
            keep_outputs=True,
        )
        return BatchedRunHistory.from_host(runtime.run(range(spec.n_slots)))

    @property
    def _cells(self):
        return (
            None
            if self.cell_topology is None
            else self.cell_topology.cell_of_ue
        )

    def _run_open_loop(self) -> BatchedRunHistory:
        from repro.phy.pipeline import normalize_modes

        spec = self.spec
        modes = normalize_modes(
            np.asarray(spec.modes, np.int32), spec.n_slots, spec.n_ues
        )
        if self.cell_topology is not None:
            from repro.core.topology import run_sharded

            _, traj = run_sharded(
                self.engine,
                self.cell_topology,
                self.schedule,
                modes,
                n_slots=spec.n_slots,
                key=jax.random.PRNGKey(spec.seed),
                faults=spec.faults,
            )
        else:
            _, traj = self.engine.run(
                self.schedule,
                modes,
                n_slots=spec.n_slots,
                n_ues=spec.n_ues,
                key=jax.random.PRNGKey(spec.seed),
                faults=spec.faults,
            )
        return BatchedRunHistory.from_trajectory(
            modes, traj, cell_of_ue=self._cells
        )

    def _run_closed_loop(self) -> BatchedRunHistory:
        spec = self.spec
        if self.cell_topology is not None:
            from repro.core.topology import run_closed_loop_sharded

            _, final_switch, traj = run_closed_loop_sharded(
                self.engine,
                self.cell_topology,
                self.schedule,
                self.device_policy,
                spec.switch.to_config(spec.feature_names),
                n_slots=spec.n_slots,
                key=jax.random.PRNGKey(spec.seed),
                faults=spec.faults,
            )
            return BatchedRunHistory.from_closed_loop(
                traj, final_switch, cell_of_ue=self._cells
            )
        runtime = ArchesRuntime.from_spec(
            spec, engine=self.engine, device_policy=self.device_policy
        )
        return runtime.run_batched(
            self.schedule,
            n_slots=spec.n_slots,
            n_ues=spec.n_ues,
            key=jax.random.PRNGKey(spec.seed),
            faults=spec.faults,
        )

    def _run_perturbed(self) -> BatchedRunHistory:
        spec = self.spec
        rho = jnp.asarray(spec.rho, jnp.float32)
        if self.cell_topology is not None:
            from repro.core.topology import run_perturbed_sharded

            _, traj = run_perturbed_sharded(
                self.engine,
                self.cell_topology,
                self.schedule,
                rho,
                n_slots=spec.n_slots,
                key=jax.random.PRNGKey(spec.seed),
            )
        else:
            _, traj = self.engine.run_perturbed(
                self.schedule,
                rho,
                n_slots=spec.n_slots,
                key=jax.random.PRNGKey(spec.seed),
            )
        # stage 1 is MMSE-only by construction: the mode grid is all-1
        modes = np.ones((spec.n_slots, spec.n_ues), np.int32)
        return BatchedRunHistory.from_trajectory(
            modes, traj, cell_of_ue=self._cells
        )
