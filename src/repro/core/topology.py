"""Sharded multi-cell campaign topology: the UE axis across devices.

The batched engine (`repro.phy.pipeline.BatchedPuschPipeline`) runs one
cell's UE batch on one device.  This module lays a ``(n_slots, n_ues)``
campaign out as ``n_cells`` cells partitioned over a 1-D ``ues`` device
mesh and runs every batched execution path — open-loop, closed-loop, gated
and the perturbation sweep — under ``shard_map``:

* **Layout** — ``TopologySpec`` is the declarative (JSON-stable) form:
  cell count, shard count, per-cell channel offsets, inter-cell coupling.
  ``CellTopology.build`` resolves it against a concrete UE count and the
  available devices (``make_ue_mesh`` degrades gracefully to a 1-device
  mesh on a single-device container, so the sharded entry is always
  runnable).
* **Per-shard compaction** — each shard gates its own capacity-K sub-batch:
  the bank's cumsum partition / stable argsort / ``switch_scatter`` all see
  only the shard-local UE slice, so gated execution never performs a
  cross-device gather inside the scan body.  The engine's
  ``gated_capacity`` is therefore the *per-shard* capacity when the engine
  runs under a multi-shard topology (``ArchesSession`` divides a campaign
  capacity by the shard count).
* **Cell coupling** — per-cell noise/interference offsets plus inter-cell
  leakage enter the channel layer through ``repro.phy.channel.CellParams``;
  the per-cell mean load is the scan's *only* cross-shard collective (one
  ``psum`` of exact {0,1} counts, so the value — and hence the whole
  trajectory — is independent of the sharding).

The tested contract extends the repo's standing one: on a 1-device mesh
every sharded path is bitwise-equal on all physical trajectory leaves to
the unsharded engine, and on a forced multi-device CPU mesh
(``XLA_FLAGS=--xla_force_host_platform_device_count=N``) closed-loop mode
trajectories replay bitwise through ``host_replay_closed_loop``.

The production training meshes (``make_production_mesh`` /
``make_cpu_mesh``) are consolidated here from the orphaned
``repro.launch.mesh`` (which now re-exports them).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P

UE_AXIS = "ues"


# -- mesh factories ------------------------------------------------------------


def make_ue_mesh(n_shards: int | None = None, *, n_ues: int | None = None):
    """A 1-D ``("ues",)`` mesh over the local devices.

    ``n_shards=None`` (auto) takes every available device; an explicit
    request is capped at the available device count — the CI container has
    one CPU device, so every topology degrades to a 1-device mesh there
    (force more with ``XLA_FLAGS=--xla_force_host_platform_device_count``).
    With ``n_ues`` given, the shard count is additionally reduced to the
    largest divisor of the UE count so every shard carries the same number
    of UEs (the static-shape discipline the scan engine requires).
    """
    devices = jax.devices()
    n = len(devices) if n_shards is None else max(1, min(n_shards, len(devices)))
    if n_ues is not None:
        while n_ues % n:
            n -= 1
    return jax.make_mesh((n,), (UE_AXIS,), devices=devices[:n])


def make_production_mesh(*, multi_pod: bool = False):
    """Production training meshes (multi-pod dry-run spec).

      single-pod: (16, 16)    = 256 chips, axes ("data", "model")
      multi-pod:  (2, 16, 16) = 512 chips, axes ("pod", "data", "model")

    Physical mapping on the v5e target: "model" follows the ICI torus minor
    dimension (TP collectives stay on-chip-neighbour links), "data" the
    major dimension, "pod" crosses the inter-pod DCN — which is why the
    default sharding rules put only pure-DP gradient reductions on the pod
    axis (DESIGN.md, distributed/sharding.py).
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_cpu_mesh(n_data: int = 1, n_model: int = 1):
    """Tiny mesh for CPU integration tests (requires forced host devices)."""
    return jax.make_mesh((n_data, n_model), ("data", "model"))


# -- declarative topology ------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class TopologySpec:
    """A campaign's cell/shard layout as data (JSON-stable, hashed).

    ``n_cells`` partitions the UE axis into equal contiguous cells (UE
    ``u`` belongs to cell ``u // (n_ues / n_cells)``); ``n_shards`` is the
    device-mesh request (``None`` == every local device; always degraded to
    what the host offers and to a divisor of ``n_ues``).
    ``cell_noise_offsets_db`` / ``cell_inr_offsets_db`` shift each cell's
    thermal noise / interference power (empty == no offset; else one entry
    per cell), and ``coupling`` sets the inter-cell leakage coefficient —
    see ``repro.phy.channel.CellParams``.
    """

    n_cells: int = 1
    n_shards: int | None = None
    coupling: float = 0.0
    cell_noise_offsets_db: tuple = ()
    cell_inr_offsets_db: tuple = ()

    def __post_init__(self):
        for name in ("cell_noise_offsets_db", "cell_inr_offsets_db"):
            v = getattr(self, name)
            object.__setattr__(
                self, name, tuple(float(x) for x in v)
            )
            v = getattr(self, name)
            if v and len(v) != self.n_cells:
                raise ValueError(
                    f"{name} has {len(v)} entries for n_cells={self.n_cells}"
                )
        if self.n_cells < 1:
            raise ValueError(f"n_cells {self.n_cells} must be >= 1")
        if self.n_shards is not None and self.n_shards < 1:
            raise ValueError(f"n_shards {self.n_shards} must be >= 1")


@dataclasses.dataclass(frozen=True)
class CellTopology:
    """A ``TopologySpec`` resolved against a UE count and the local devices.

    Carries everything the sharded entries need: the 1-D UE mesh, the
    global cell-id vector, and the traced ``CellParams`` pytree.
    """

    spec: TopologySpec
    n_ues: int
    n_shards: int
    mesh: Any
    cell_of_ue: np.ndarray  # (n_ues,) int32 global cell ids
    cell_params: Any  # repro.phy.channel.CellParams
    # jitted scan callables, keyed by (engine, kind, statics): jax's jit
    # cache is keyed on function identity, so re-wrapping a fresh closure
    # per run() call would recompile the whole scan every time
    _fn_cache: dict = dataclasses.field(
        default_factory=dict, repr=False, compare=False
    )

    @classmethod
    def build(
        cls, spec: TopologySpec, n_ues: int, *, mesh=None
    ) -> "CellTopology":
        from repro.phy.channel import cell_params

        if n_ues % spec.n_cells:
            raise ValueError(
                f"n_cells={spec.n_cells} does not divide n_ues={n_ues}: "
                "cells partition the UE axis into equal sub-batches"
            )
        if spec.n_shards is not None and n_ues % spec.n_shards:
            raise ValueError(
                f"n_shards={spec.n_shards} does not divide n_ues={n_ues}: "
                "every shard must carry the same number of UEs"
            )
        if mesh is None:
            mesh = make_ue_mesh(spec.n_shards, n_ues=n_ues)
        ues_per_cell = n_ues // spec.n_cells
        return cls(
            spec=spec,
            n_ues=n_ues,
            n_shards=mesh.shape[UE_AXIS],
            mesh=mesh,
            cell_of_ue=(np.arange(n_ues) // ues_per_cell).astype(np.int32),
            cell_params=cell_params(
                spec.n_cells,
                ues_per_cell,
                noise_offsets_db=spec.cell_noise_offsets_db,
                inr_offsets_db=spec.cell_inr_offsets_db,
                coupling=spec.coupling,
            ),
        )

    @property
    def n_cells(self) -> int:
        return self.spec.n_cells

    @property
    def ues_per_shard(self) -> int:
        return self.n_ues // self.n_shards


def per_shard_capacity(capacity: int, n_shards: int) -> int:
    """Split a campaign-wide gated capacity across shards.

    Compaction is shard-local, so the engine's ``gated_capacity`` under a
    sharded topology is the per-shard sub-batch size.  The campaign
    capacity must split evenly and leave at least one slot per shard —
    misconfiguration raises here (spec-compile time) instead of surfacing
    as a shape error deep in the scan.
    """
    if capacity % n_shards:
        raise ValueError(
            f"gated_capacity={capacity} does not divide across "
            f"n_shards={n_shards}: per-shard compaction needs an equal "
            "capacity-K sub-batch on every shard"
        )
    per_shard = capacity // n_shards
    if per_shard < 1:
        raise ValueError(
            f"gated_capacity={capacity} is < 1 per shard on "
            f"n_shards={n_shards}: every shard needs capacity for at "
            "least one UE (raise the capacity or lower the shard count)"
        )
    return per_shard


# -- sharded execution entries -------------------------------------------------
#
# Each entry mirrors the corresponding ``BatchedPuschPipeline`` method: the
# host-side preparation (schedule lowering, PRNG derivation, state init) is
# identical — the same per-UE fold_in keys regardless of the sharding — and
# the compiled scan is wrapped in ``shard_map`` over the UE mesh axis.  With
# ``sharded=False`` the same cell-coupled program runs unpartitioned (the
# bitwise reference the 1-device contract is tested against).


def _prepare(engine, topo: CellTopology, schedule, n_slots: int, key, ue_keys):
    from repro.phy.channel import broadcast_params_to_ues
    from repro.phy.pipeline import init_device_link, resolve_schedule

    if key is None:
        key = jax.random.PRNGKey(0)
    profile, params = resolve_schedule(
        engine.cfg, schedule, n_slots, topo.n_ues
    )
    params = broadcast_params_to_ues(params, topo.n_ues)
    if ue_keys is None:
        ue_keys = jax.vmap(lambda u: jax.random.fold_in(key, u))(
            jnp.arange(topo.n_ues)
        )
    elif ue_keys.shape[0] != topo.n_ues:
        raise ValueError(f"ue_keys {ue_keys.shape} vs n_ues {topo.n_ues}")
    link0 = init_device_link(topo.n_ues)
    return profile, params, ue_keys, link0


def _cached_jit(
    topo: CellTopology, key: tuple, build, *, donate_argnums: tuple = ()
) -> Any:
    """One jitted callable per (engine, program kind, statics) per topology.

    ``donate_argnums`` configures carry donation on the cached executable
    (streaming drivers donate their scan carries); callers that donate must
    put a marker in ``key`` so donating and non-donating programs cache
    separately.
    """
    fn = topo._fn_cache.get(key)
    if fn is None:
        fn = topo._fn_cache[key] = jax.jit(
            build(), donate_argnums=tuple(donate_argnums)
        )
    return fn


def _policy_spec(policy):
    """Per-leaf partition specs for a device policy pytree.

    Exported tables are replicated onto every shard; a ``PerUEPolicy``'s
    per-UE assignment vector is the one policy leaf that shards with its
    UEs.
    """
    from repro.core.closed_loop import PerUEPolicy

    if isinstance(policy, PerUEPolicy):
        return PerUEPolicy(
            tables=jax.tree.map(lambda _: P(), policy.tables),
            policy_idx=P(UE_AXIS),
        )
    return jax.tree.map(lambda _: P(), policy)


def open_loop_fn(
    engine, topo: CellTopology, profile, *, sharded: bool = True, faults=None
):
    """The (shard_map-wrapped) open-loop scan callable.

    Exposed separately from ``run_sharded`` so tests can inspect its jaxpr
    / lowered HLO for the collective contract (one psum for the cell mean,
    no gathers in the compaction path).  With a ``FaultSpec`` the callable
    grows a ``corrupt`` mask operand (``(S, U)``, sharded over its UEs —
    fault masking is element-local, no new collective).
    """
    axis = UE_AXIS if sharded else None

    if faults is None:
        def call(link0, ue_keys, modes, params, cell_of_ue, cell_params):
            return engine._run_scan(
                profile, link0, ue_keys, modes, params,
                cell_of_ue, cell_params, cell_axis=axis,
            )

        extra_specs = ()
    else:
        def call(link0, ue_keys, modes, params, cell_of_ue, cell_params,
                 corrupt):
            return engine._run_scan(
                profile, link0, ue_keys, modes, params,
                cell_of_ue, cell_params, cell_axis=axis,
                faults=faults, corrupt=corrupt,
            )

        extra_specs = (P(None, UE_AXIS),)

    if not sharded:
        return call
    return shard_map(
        call,
        mesh=topo.mesh,
        in_specs=(P(UE_AXIS), P(UE_AXIS), P(None, UE_AXIS), P(None, UE_AXIS),
                  P(UE_AXIS), P()) + extra_specs,
        out_specs=(P(UE_AXIS), P(None, UE_AXIS)),
        check_vma=False,
    )


def run_sharded(
    engine,
    topo: CellTopology,
    schedule,
    modes,
    *,
    n_slots: int,
    key=None,
    ue_keys=None,
    sharded: bool = True,
    faults=None,
):
    """Open-loop campaign over the sharded topology.

    The sharded analogue of ``BatchedPuschPipeline.run`` (scan path): same
    schedule/modes/key/faults semantics; ``(final_link, trajectory)`` out.
    """
    from repro.phy.pipeline import normalize_modes

    profile, params, ue_keys, link0 = _prepare(
        engine, topo, schedule, n_slots, key, ue_keys
    )
    modes = normalize_modes(modes, n_slots, topo.n_ues)
    fn = _cached_jit(
        topo, (engine, "open_loop", profile, sharded, faults),
        lambda: open_loop_fn(
            engine, topo, profile, sharded=sharded, faults=faults
        ),
    )
    args = (
        link0, ue_keys, modes, params,
        jnp.asarray(topo.cell_of_ue), topo.cell_params,
    )
    if faults is not None:
        corrupt = jnp.asarray(faults.resolve(n_slots, topo.n_ues).corrupt)
        args = args + (corrupt,)
    return fn(*args)


def closed_loop_fn(
    engine, topo: CellTopology, profile, sw_cfg, policy,
    *, sharded: bool = True, faults=None,
):
    """The (shard_map-wrapped) closed-loop scan callable (jaxpr-inspectable).

    With a ``FaultSpec`` the callable grows a ``fault_masks`` operand (the
    ``(decision_valid, corrupt, telemetry_valid)`` triple of ``(S, U)``
    masks, each sharded over its UEs) — the degradation ladder is
    UE-element-local, so the single cell-mean ``psum`` stays the scan's
    only cross-shard collective.
    """
    axis = UE_AXIS if sharded else None

    if faults is None:
        def call(link0, sw0, ue_keys, params, policy, cell_of_ue,
                 cell_params):
            return engine._run_closed_scan(
                profile, sw_cfg, link0, sw0, ue_keys, params, policy,
                cell_of_ue, cell_params, cell_axis=axis,
            )

        extra_specs = ()
    else:
        def call(link0, sw0, ue_keys, params, policy, cell_of_ue,
                 cell_params, fault_masks):
            return engine._run_closed_scan(
                profile, sw_cfg, link0, sw0, ue_keys, params, policy,
                cell_of_ue, cell_params, cell_axis=axis,
                faults=faults, fault_masks=fault_masks,
            )

        extra_specs = (P(None, UE_AXIS),)

    if not sharded:
        return call
    return shard_map(
        call,
        mesh=topo.mesh,
        in_specs=(P(UE_AXIS), P(UE_AXIS), P(UE_AXIS), P(None, UE_AXIS),
                  _policy_spec(policy), P(UE_AXIS), P()) + extra_specs,
        out_specs=(P(UE_AXIS), P(UE_AXIS), P(None, UE_AXIS)),
        check_vma=False,
    )


def streaming_open_loop_fn(
    engine, topo: CellTopology, profile, *, sharded: bool = True, faults=None
):
    """Streaming-segment open-loop scan callable (jaxpr/HLO-inspectable).

    The sharded entry the epoch-chunked driver calls once per segment: the
    same program as ``open_loop_fn`` plus the two streaming operands —
    the replicated global segment start ``slot0`` (so per-slot PRNG folds
    stay keyed by the *campaign* slot index across segments) and the
    per-bank-slot ``active`` mask, which shards with its UEs.  The
    collective contract is unchanged through re-packs: the cell-mean
    ``psum`` stays the scan's only cross-shard collective (detached lanes
    are masked out of the summed load before it), and admission re-packing
    happens host-side *between* segments, cell-block-aligned, so no gather
    ever enters the compiled program.
    """
    axis = UE_AXIS if sharded else None

    if faults is None:
        def call(link0, ue_keys, modes, params, cell_of_ue, cell_params,
                 slot0, active):
            return engine._run_scan(
                profile, link0, ue_keys, modes, params,
                cell_of_ue, cell_params, cell_axis=axis,
                slot0=slot0, active=active,
            )

        extra_specs = ()
    else:
        def call(link0, ue_keys, modes, params, cell_of_ue, cell_params,
                 slot0, active, corrupt):
            return engine._run_scan(
                profile, link0, ue_keys, modes, params,
                cell_of_ue, cell_params, cell_axis=axis,
                slot0=slot0, active=active,
                faults=faults, corrupt=corrupt,
            )

        extra_specs = (P(None, UE_AXIS),)

    if not sharded:
        return call
    return shard_map(
        call,
        mesh=topo.mesh,
        in_specs=(P(UE_AXIS), P(UE_AXIS), P(None, UE_AXIS), P(None, UE_AXIS),
                  P(UE_AXIS), P(), P(), P(UE_AXIS)) + extra_specs,
        out_specs=(P(UE_AXIS), P(None, UE_AXIS)),
        check_vma=False,
    )


def streaming_closed_loop_fn(
    engine, topo: CellTopology, profile, sw_cfg, policy,
    *, sharded: bool = True, faults=None,
):
    """Streaming-segment closed-loop scan callable.

    ``closed_loop_fn`` plus the streaming operands (see
    ``streaming_open_loop_fn``); the per-UE switch state shards with its
    UEs and is gathered/cold-started host-side at segment boundaries.
    """
    axis = UE_AXIS if sharded else None

    if faults is None:
        def call(link0, sw0, ue_keys, params, policy, cell_of_ue,
                 cell_params, slot0, active):
            return engine._run_closed_scan(
                profile, sw_cfg, link0, sw0, ue_keys, params, policy,
                cell_of_ue, cell_params, cell_axis=axis,
                slot0=slot0, active=active,
            )

        extra_specs = ()
    else:
        def call(link0, sw0, ue_keys, params, policy, cell_of_ue,
                 cell_params, slot0, active, fault_masks):
            return engine._run_closed_scan(
                profile, sw_cfg, link0, sw0, ue_keys, params, policy,
                cell_of_ue, cell_params, cell_axis=axis,
                slot0=slot0, active=active,
                faults=faults, fault_masks=fault_masks,
            )

        extra_specs = (P(None, UE_AXIS),)

    if not sharded:
        return call
    return shard_map(
        call,
        mesh=topo.mesh,
        in_specs=(P(UE_AXIS), P(UE_AXIS), P(UE_AXIS), P(None, UE_AXIS),
                  _policy_spec(policy), P(UE_AXIS), P(), P(), P(UE_AXIS))
                 + extra_specs,
        out_specs=(P(UE_AXIS), P(UE_AXIS), P(None, UE_AXIS)),
        check_vma=False,
    )


def run_closed_loop_sharded(
    engine,
    topo: CellTopology,
    schedule,
    policy,
    sw_cfg,
    *,
    n_slots: int,
    key=None,
    ue_keys=None,
    sharded: bool = True,
    faults=None,
):
    """Closed-loop campaign over the sharded topology.

    Mirrors ``BatchedPuschPipeline.run_closed_loop`` (scan path): the
    per-UE decision state shards with its UEs, exported policy tables are
    replicated, and the whole loop stays one compiled program per shard.
    Returns ``(final_link, final_switch_state, trajectory)``.
    """
    from repro.core.closed_loop import init_device_switch

    profile, params, ue_keys, link0 = _prepare(
        engine, topo, schedule, n_slots, key, ue_keys
    )
    sw0 = init_device_switch(
        topo.n_ues, len(sw_cfg.feature_names), sw_cfg, faults
    )
    fn = _cached_jit(
        topo,
        (engine, "closed_loop", profile, sw_cfg,
         jax.tree.structure(policy), sharded, faults),
        lambda: closed_loop_fn(
            engine, topo, profile, sw_cfg, policy, sharded=sharded,
            faults=faults,
        ),
    )
    args = (
        link0, sw0, ue_keys, params, policy,
        jnp.asarray(topo.cell_of_ue), topo.cell_params,
    )
    if faults is not None:
        rf = faults.resolve(n_slots, topo.n_ues)
        args = args + ((
            jnp.asarray(rf.decision_valid),
            jnp.asarray(rf.corrupt),
            jnp.asarray(rf.telemetry_valid),
        ),)
    return fn(*args)


def run_perturbed_sharded(
    engine,
    topo: CellTopology,
    schedule,
    rho,
    *,
    n_slots: int,
    key=None,
    ue_keys=None,
    sharded: bool = True,
):
    """Methodology stage-1 sweep over the sharded topology.

    Mirrors ``BatchedPuschPipeline.run_perturbed``: the rho grid rides the
    UE axis, so it shards with the UEs.
    """
    axis = UE_AXIS if sharded else None
    rho = jnp.asarray(rho, jnp.float32)
    if rho.shape[0] != topo.n_ues:
        raise ValueError(f"rho {rho.shape} vs topology n_ues {topo.n_ues}")
    profile, params, ue_keys, link0 = _prepare(
        engine, topo, schedule, n_slots, key, ue_keys
    )

    def build():
        def call(link0, ue_keys, rho, params, cell_of_ue, cell_params):
            return engine._run_perturbed_scan(
                profile, link0, ue_keys, rho, params,
                cell_of_ue, cell_params, cell_axis=axis,
            )

        if not sharded:
            return call
        return shard_map(
            call,
            mesh=topo.mesh,
            in_specs=(P(UE_AXIS), P(UE_AXIS), P(UE_AXIS), P(None, UE_AXIS),
                      P(UE_AXIS), P()),
            out_specs=(P(UE_AXIS), P(None, UE_AXIS)),
            check_vma=False,
        )

    fn = _cached_jit(topo, (engine, "perturbed", profile, sharded), build)
    return fn(
        link0, ue_keys, rho, params,
        jnp.asarray(topo.cell_of_ue), topo.cell_params,
    )
