"""In-scan closed-loop expert switching: the E3/dApp decision path on device.

The host control loop (``ArchesRuntime`` + ``DApp``) bounces every slot's
KPMs through Python and pays the paper's ~135 us framework overhead per
decision.  This module compiles the *whole* loop — telemetry window, policy
inference, hysteresis, switch register — into the slot scan, so the mode a
UE runs in slot ``n+1`` is derived on device from slot ``n``'s telemetry
with zero host involvement.

Pieces:

* ``DeviceTreePolicy`` / ``DeviceThresholdPolicy`` — host policies exported
  to flat device arrays (feature index / threshold / leaf-mode tables, plus
  the ``PackedTree`` MXU operands for the Pallas ``tree_infer`` kernel).
* ``PerUEPolicy`` — a stacked bank of exported tables with a ``(U,)``
  policy-index axis: UE ``u`` runs table ``policy_idx[u]`` inside the same
  scan (per-UE policy heterogeneity; ``per_ue_policy`` builds one).
* ``DeviceSwitchState`` — the scan-carry pytree: a per-UE rolling KPM window
  (``KPMRing`` vmapped over the UE axis), hysteresis streak counters, and
  the switch register (``pending_mode``) holding the mode that takes effect
  at the next slot boundary.
* ``switch_update`` / ``switch_boundary`` — the two phases of the paper's
  timing contract (3.3): a decision made *during* slot ``n`` is committed to
  the register; only the boundary into slot ``n+1`` copies it to
  ``active_mode``.  Mid-slot flips are impossible by construction.
* ``host_replay_closed_loop`` — the equivalence oracle: a slot-by-slot host
  loop feeding the same KPM window through the literal host policy
  (``DecisionTreePolicy.__call__`` -> ``tree_infer_ref`` walk).  Device and
  host mode trajectories must match bitwise; the test suite asserts it.

Policy *training* (Gini tree fitting) and the clustering methodology stay
offline/host-side, exactly as in the paper — only *inference* moves into
the scan.
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.telemetry import KPMRing, ring_push, ring_window_mean
from repro.kernels.tree_infer import (
    PackedTree,
    pack_tree,
    tree_infer,
    tree_infer_ref,
)

# -- device policy tables -----------------------------------------------------


class DeviceTreePolicy(NamedTuple):
    """A fitted decision tree as flat device arrays.

    ``feature``/``threshold`` are the level-order internal-node tables
    (children of node ``n`` are ``2n+1``/``2n+2``; go right if
    ``x[feature] > threshold``); ``leaf_modes`` holds the int mode each of
    the ``2**depth`` leaves decides.  ``packed`` carries the same tree as
    the MXU operands ``repro.kernels.tree_infer`` consumes.  Depth is not
    stored: it is recovered statically from ``feature.shape``.
    """

    feature: jax.Array  # (2**d - 1,) int32
    threshold: jax.Array  # (2**d - 1,) float32
    leaf_modes: jax.Array  # (2**d,) float32
    packed: PackedTree

    @property
    def depth(self) -> int:
        return int(self.feature.shape[0] + 1).bit_length() - 1


class DeviceThresholdPolicy(NamedTuple):
    """``ThresholdPolicy`` as flat device scalars (single-KPM gate + band)."""

    feature_idx: jax.Array  # int32
    lo: jax.Array  # float32 — threshold - hysteresis
    hi: jax.Array  # float32 — threshold + hysteresis
    mode_above: jax.Array  # int32
    mode_below: jax.Array  # int32


class PerUEPolicy(NamedTuple):
    """Per-UE policy heterogeneity: a bank of exported tables + assignment.

    ``tables`` stacks the exported device policies (trees and/or threshold
    gates, any mix); ``policy_idx (U,)`` assigns each UE its table.
    ``policy_infer`` evaluates every table on the full ``(U, F)`` feature
    matrix and selects along the policy-index axis — all shapes static, so
    the heterogeneous decision path compiles into the slot scan unchanged,
    and each table's evaluation stays bitwise-identical to running that
    table alone.  Retires the ROADMAP open item: different UEs in one
    closed-loop campaign now run different exported policies.
    """

    tables: tuple  # tuple[DeviceTreePolicy | DeviceThresholdPolicy, ...]
    policy_idx: jax.Array  # (U,) int32 — table index per UE


def per_ue_policy(tables: "Sequence", assignment) -> PerUEPolicy:
    """Build a validated ``PerUEPolicy`` from tables + per-UE assignment."""
    tables = tuple(tables)
    if not tables:
        raise ValueError("per-UE policy needs at least one table")
    idx = np.asarray(assignment, np.int32)
    if idx.ndim != 1:
        raise ValueError(f"assignment must be (n_ues,), got {idx.shape}")
    if idx.min() < 0 or idx.max() >= len(tables):
        raise ValueError(
            f"assignment references tables outside [0, {len(tables)})"
        )
    return PerUEPolicy(tables=tables, policy_idx=jnp.asarray(idx))


DevicePolicy = DeviceTreePolicy | DeviceThresholdPolicy | PerUEPolicy


def export_tree_tables(
    feature: np.ndarray,
    threshold: np.ndarray,
    leaf_values: np.ndarray,
    n_features: int,
    depth: int,
) -> DeviceTreePolicy:
    """Densify level-order tree arrays into a ``DeviceTreePolicy``."""
    return DeviceTreePolicy(
        feature=jnp.asarray(feature, jnp.int32),
        threshold=jnp.asarray(threshold, jnp.float32),
        leaf_modes=jnp.asarray(leaf_values, jnp.float32),
        packed=pack_tree(
            np.asarray(feature), np.asarray(threshold), np.asarray(leaf_values),
            n_features, depth,
        ),
    )


def policy_infer(
    policy: DevicePolicy,
    x: jax.Array,
    prev_mode: jax.Array,
    *,
    backend: str = "auto",
) -> jax.Array:
    """Evaluate a device policy on ``x (U, F)`` -> int32 modes ``(U,)``.

    ``backend`` selects the tree evaluator: ``"pallas"`` runs the
    ``tree_infer`` MXU kernel, ``"ref"`` the vectorized literal walk, and
    ``"auto"`` picks the kernel on a TPU and the ref walk off the chip.
    Both are bitwise-equivalent (the kernel's one-hot feature gather is an
    exact matmul); the kernel tests assert it.  ``prev_mode`` only matters
    for the threshold policy's keep-band.

    A ``PerUEPolicy`` evaluates each stacked table on the full batch and
    gathers along its ``(U,)`` policy-index axis — UE ``u`` gets table
    ``policy_idx[u]``'s decision, bitwise-equal to evaluating that table
    alone (selection never touches the per-table arithmetic).
    """
    if isinstance(policy, PerUEPolicy):
        outs = jnp.stack(
            [
                policy_infer(t, x, prev_mode, backend=backend)
                for t in policy.tables
            ],
            axis=0,
        )  # (P, U)
        return jnp.take_along_axis(
            outs, policy.policy_idx[None, :], axis=0
        )[0].astype(jnp.int32)
    if isinstance(policy, DeviceThresholdPolicy):
        v = x[:, policy.feature_idx]
        above = v > policy.hi
        below = v < policy.lo
        keep = jnp.logical_not(jnp.logical_or(above, below))
        return jnp.where(
            keep,
            jnp.asarray(prev_mode, jnp.int32),
            jnp.where(above, policy.mode_above, policy.mode_below),
        ).astype(jnp.int32)
    if backend == "auto":
        backend = "pallas" if jax.default_backend() == "tpu" else "ref"
    if backend == "pallas":
        out = tree_infer(x.astype(jnp.float32), policy.packed)
    elif backend == "ref":
        out = tree_infer_ref(
            x.astype(jnp.float32),
            policy.feature,
            policy.threshold,
            policy.leaf_modes,
            policy.depth,
        )
    else:
        raise ValueError(f"unknown policy backend {backend!r}")
    return out.astype(jnp.int32)


# -- switch-register state ----------------------------------------------------


@dataclasses.dataclass(frozen=True)
class SwitchConfig:
    """Static configuration of the in-scan control loop.

    ``window_slots`` mirrors the dApp's telemetry window (decision input is
    the mean over the last ``window_slots`` slots, partial at cold start);
    ``hysteresis_slots`` is the number of *consecutive* disagreeing raw
    decisions required before the register is rewritten (1 == every
    decision commits, the paper's behaviour).  ``period_slots`` mirrors the
    dApp's decision periodicity: the policy is evaluated on slots where
    ``slot % period_slots == 0`` and the register holds its value in
    between (telemetry keeps accumulating every slot).  The register defers
    application to the next boundary regardless.

    ``ttl_slots`` is the fail-safe decay horizon under fault injection: a
    UE whose decision age (slots since the last *valid* decision slot)
    reaches it is forced to ``default_mode`` at the boundary, mirroring the
    host ``slot_boundary`` TTL exactly.  Only enforced when the campaign
    carries a ``FaultSpec``; a healthy loop needs ``ttl_slots >=
    period_slots`` to never age out (the zero-fault identity contract).
    """

    feature_names: tuple[str, ...]
    window_slots: int = 8
    hysteresis_slots: int = 1
    period_slots: int = 1
    default_mode: int = 1
    backend: str = "auto"  # "auto" | "pallas" | "ref"
    ttl_slots: int = 16

    def __post_init__(self):
        object.__setattr__(self, "feature_names", tuple(self.feature_names))
        if self.window_slots < 1:
            raise ValueError("window_slots must be >= 1")
        if self.hysteresis_slots < 1:
            raise ValueError("hysteresis_slots must be >= 1")
        if self.period_slots < 1:
            raise ValueError("period_slots must be >= 1")
        if self.ttl_slots < 1:
            raise ValueError("ttl_slots must be >= 1")


class DeviceSwitchState(NamedTuple):
    """Per-UE control-loop state riding the slot scan's carry.

    ``rings`` is a ``KPMRing`` with every leaf vmapped over a leading UE
    axis (all UEs push in lockstep, one slot per push).  ``active_mode`` is
    what the pipeline consumes this slot; ``pending_mode`` is the switch
    register (the mode that takes effect at the next boundary);
    ``streak`` counts consecutive raw decisions disagreeing with the
    register (hysteresis); ``n_switches`` counts boundary transitions.

    The three fault-path leaves ride along even without a ``FaultSpec``
    (untouched then, so XLA dead-code-eliminates them): ``decision_age``
    counts slots since the last valid decision slot (the device twin of the
    host ``SlotSwitchState.slots_since_decision``), ``trip_ring`` is the
    circuit breaker's per-UE rolling trip window (width
    ``FaultSpec.breaker_window``; 1 when no faults), and ``quarantine`` is
    the per-UE cooldown countdown (``> 0`` == the AI expert is quarantined
    and the UE is served by the default expert).
    """

    rings: KPMRing  # buf (U, W, F) / idx (U,) / count (U,)
    active_mode: jax.Array  # (U,) int32
    pending_mode: jax.Array  # (U,) int32
    streak: jax.Array  # (U,) int32
    n_switches: jax.Array  # (U,) int32
    decision_age: jax.Array  # (U,) int32
    trip_ring: jax.Array  # (U, breaker_window) int32
    quarantine: jax.Array  # (U,) int32


def init_device_switch(
    n_ues: int, n_features: int, cfg: SwitchConfig, faults=None
) -> DeviceSwitchState:
    d = jnp.full((n_ues,), cfg.default_mode, jnp.int32)
    z = jnp.zeros((n_ues,), jnp.int32)
    breaker_window = 1 if faults is None else faults.breaker_window
    return DeviceSwitchState(
        rings=KPMRing(
            buf=jnp.zeros((n_ues, cfg.window_slots, n_features), jnp.float32),
            idx=z,
            count=z,
        ),
        active_mode=d,
        pending_mode=d,
        streak=z,
        n_switches=z,
        decision_age=z,
        trip_ring=jnp.zeros((n_ues, breaker_window), jnp.int32),
        quarantine=z,
    )


def switch_update(
    state: DeviceSwitchState,
    kpm_vecs: jax.Array,
    policy: DevicePolicy,
    cfg: SwitchConfig,
    *,
    decide: jax.Array | bool = True,
    decision_valid: jax.Array | None = None,
    telemetry_valid: jax.Array | None = None,
) -> tuple[DeviceSwitchState, jax.Array]:
    """Decision phase of slot ``n``: window push -> policy -> register.

    ``kpm_vecs (U, F)`` is slot ``n``'s telemetry in ``cfg.feature_names``
    order.  Returns the updated state (register possibly rewritten — but
    ``active_mode`` untouched: application waits for ``switch_boundary``)
    and the raw per-UE policy decision.

    ``decide`` implements ``SwitchConfig.period_slots``: on hold slots
    (``decide`` false) the telemetry still enters the window but the policy
    is not consulted — register *and* hysteresis streak are frozen (a hold
    slot neither advances nor resets the streak, so ``hysteresis_slots``
    counts disagreeing *decision* slots) and the raw decision reported is
    the held register.

    The fault masks (``(U,)`` bool, both-or-neither) inject the
    ``FaultSpec`` failure classes: where ``telemetry_valid`` is False the
    slot's KPM sample never enters the rolling window (the ring simply
    does not advance for that UE), and where ``decision_valid`` is False
    the control plane lost this slot's decision — register, streak and raw
    decision freeze exactly like a hold slot, and the decision age is not
    reset.  ``decision_age`` resets on every decision slot that actually
    arrived (valid + decide), regardless of hysteresis: a heard "stay"
    refreshes the TTL just like the host loop's ``commit_decision``.
    """
    pushed = jax.vmap(ring_push)(state.rings, kpm_vecs)
    if telemetry_valid is not None:
        tv = telemetry_valid
        rings = jax.tree.map(
            lambda n, o: jnp.where(
                tv.reshape(tv.shape + (1,) * (n.ndim - 1)), n, o
            ),
            pushed,
            state.rings,
        )
    else:
        rings = pushed
    window = jax.vmap(lambda r: ring_window_mean(r, cfg.window_slots))(rings)
    raw = policy_infer(policy, window, state.pending_mode, backend=cfg.backend)
    agree = raw == state.pending_mode
    streak = jnp.where(agree, 0, state.streak + 1)
    commit = streak >= jnp.int32(cfg.hysteresis_slots)
    pending = jnp.where(commit, raw, state.pending_mode)
    streak = jnp.where(commit, 0, streak)
    if decide is not True:  # periodic decisions: freeze between decision slots
        raw = jnp.where(decide, raw, state.pending_mode)
        pending = jnp.where(decide, pending, state.pending_mode)
        streak = jnp.where(decide, streak, state.streak)
    age = state.decision_age
    if decision_valid is not None:
        dv = decision_valid
        raw = jnp.where(dv, raw, state.pending_mode)
        pending = jnp.where(dv, pending, state.pending_mode)
        streak = jnp.where(dv, streak, state.streak)
        received = dv if decide is True else jnp.logical_and(dv, decide)
        age = jnp.where(received, 0, age)
    return (
        state._replace(
            rings=rings, pending_mode=pending, streak=streak,
            decision_age=age,
        ),
        raw,
    )


def switch_boundary(
    state: DeviceSwitchState,
    *,
    ttl_slots: int | None = None,
    fail_safe_mode: int | None = None,
) -> DeviceSwitchState:
    """Boundary into slot ``n+1``: the register becomes the active mode.

    With ``ttl_slots`` (fault campaigns only) the boundary also runs the
    fail-safe TTL decay, mirroring the host ``slot_boundary`` exactly: a
    UE whose decision age has *reached* ``ttl_slots`` (checked before the
    age increments) has both its active mode and its register forced to
    ``fail_safe_mode``; the age then advances one slot for everyone.
    """
    pending = state.pending_mode
    age = state.decision_age
    if ttl_slots is not None:
        stale = age >= jnp.int32(ttl_slots)
        pending = jnp.where(stale, jnp.int32(fail_safe_mode), pending)
        age = age + 1
    switched = (pending != state.active_mode).astype(jnp.int32)
    return state._replace(
        active_mode=pending,
        pending_mode=pending,
        decision_age=age,
        n_switches=state.n_switches + switched,
    )


def breaker_update(
    state: DeviceSwitchState,
    trip: jax.Array,
    slot_idx: jax.Array,
    faults,
) -> DeviceSwitchState:
    """Circuit breaker: M trips in a window quarantine the AI expert.

    ``trip (U,)`` bool flags this slot's health-screen / audit trips.  The
    per-UE trip window is a rolling ring written at ``slot_idx %
    breaker_window``; when a UE not already quarantined accumulates
    ``breaker_trips`` trips inside the window, it enters quarantine for
    ``breaker_cooldown`` slots *with a cleared trip window* — so the
    hysteresis re-probe after cooldown starts from a clean slate rather
    than instantly re-tripping on stale history.  While quarantined the
    countdown decrements; the AI expert is re-probed the first slot the
    countdown hits zero.
    """
    window = state.trip_ring.shape[1]
    onehot = jnp.arange(window) == (slot_idx % jnp.int32(window))
    ring = jnp.where(
        onehot[None, :], trip.astype(jnp.int32)[:, None], state.trip_ring
    )
    count = ring.sum(axis=1)
    in_quar = state.quarantine > 0
    newly = jnp.logical_and(
        jnp.logical_not(in_quar), count >= jnp.int32(faults.breaker_trips)
    )
    ring = jnp.where(newly[:, None], 0, ring)
    quar = jnp.where(
        newly,
        jnp.int32(faults.breaker_cooldown),
        jnp.maximum(state.quarantine - 1, 0),
    )
    return state._replace(trip_ring=ring, quarantine=quar)


# -- host equivalence oracle ---------------------------------------------------


def host_replay_closed_loop(
    host_policy,
    features: np.ndarray,
    cfg: SwitchConfig,
    *,
    policy_idx=None,
    attached: np.ndarray | None = None,
    faults=None,
    trips: np.ndarray | None = None,
) -> dict[str, np.ndarray]:
    """Replay the closed loop on host, slot by slot, per UE.

    ``host_policy`` is the *host* object (``DecisionTreePolicy`` — called
    per KPM vector, i.e. the literal ``tree_infer_ref`` walk — or
    ``ThresholdPolicy``); ``features (S, U, F)`` is the device trajectory's
    telemetry in ``cfg.feature_names`` order.  Windowing reuses the same
    ``KPMRing`` arithmetic the scan carries (eagerly, one slot at a time),
    so any float matches bitwise; the control flow (hysteresis streak,
    switch register, boundary application) is plain Python ints.

    Per-UE heterogeneous campaigns (device side: ``PerUEPolicy``) replay by
    passing a *sequence* of host policies plus ``policy_idx`` — the same
    ``(n_ues,)`` table assignment the device ran; UE ``u`` is replayed
    through ``host_policy[policy_idx[u]]``.

    Streaming (churn) campaigns replay by passing ``attached (S, U)`` — the
    history's residency leaf.  While detached a UE is skipped entirely (no
    ring push, no decision, no boundary transition) and its history entries
    carry the ``-1`` sentinel; at every (re)attach boundary the UE
    cold-starts exactly like the device admission pass: fresh ``KPMRing``,
    register and active mode back at ``default_mode``, hysteresis streak
    cleared.  No stale pre-detach telemetry can leak into the first
    post-attach decision — the churn-boundary tests pin this at ring,
    ``DeviceSwitchState`` and host-replay layers.

    Fault campaigns replay by passing the same ``FaultSpec`` the device
    ran (``faults``): the spec is re-resolved here, producing the *same*
    mask arrays the scan consumed (the resolution is a pure function of
    the spec and the shape), and the oracle mirrors the device ordering —
    drop the KPM push where telemetry is invalid, hold the register where
    the decision was lost, reset the decision age on heard decision slots,
    run the TTL decay and the circuit breaker at the boundary.  ``trips``
    optionally supplies the device history's per-(slot, UE) health/audit
    trip flags (``health_tripped + audit_tripped``) to drive the breaker;
    without it the oracle derives trips from the corruption masks (exact
    for the NaN/Inf kinds, which always trip the in-scan health screen).

    Returns ``{"active_mode", "raw_decision", "pending_mode",
    "quarantined", "n_switches"}`` with ``(S, U)`` int arrays
    (``n_switches``: ``(U,)``).
    """
    from repro.core.policy import ThresholdPolicy
    from repro.core.telemetry import ring_init

    features = np.asarray(features, np.float32)
    n_slots, n_ues, n_feat = features.shape
    if n_feat != len(cfg.feature_names):
        raise ValueError(
            f"features carry {n_feat} KPMs, config names {len(cfg.feature_names)}"
        )
    if isinstance(host_policy, (list, tuple)):
        if policy_idx is None:
            raise ValueError("a per-UE policy sequence needs policy_idx")
        idx = np.asarray(policy_idx, int)
        if idx.shape != (n_ues,):
            raise ValueError(f"policy_idx {idx.shape} vs n_ues {n_ues}")
        if idx.size and (idx.min() < 0 or idx.max() >= len(host_policy)):
            # mirror per_ue_policy: negatives would silently wrap here
            raise ValueError(
                f"policy_idx references policies outside [0, {len(host_policy)})"
            )
        policy_for_ue = [host_policy[int(i)] for i in idx]
    else:
        if policy_idx is not None:
            raise ValueError(
                "policy_idx given but host_policy is not a sequence — pass "
                "the per-UE policy list the device campaign ran"
            )
        policy_for_ue = [host_policy] * n_ues

    if attached is not None:
        attached = np.asarray(attached, bool)
        if attached.shape != (n_slots, n_ues):
            raise ValueError(
                f"attached {attached.shape} vs features {(n_slots, n_ues)}"
            )

    resolved = None
    if faults is not None:
        resolved = faults.resolve(n_slots, n_ues)
    if trips is not None:
        trips = np.asarray(trips).astype(bool)
        if trips.shape != (n_slots, n_ues):
            raise ValueError(
                f"trips {trips.shape} vs features {(n_slots, n_ues)}"
            )

    rings = [ring_init(cfg.window_slots, n_feat) for _ in range(n_ues)]
    active = [cfg.default_mode] * n_ues
    pending = [cfg.default_mode] * n_ues
    streak = [0] * n_ues
    n_switches = [0] * n_ues
    age = [0] * n_ues
    trip_ring = (
        np.zeros((n_ues, faults.breaker_window), np.int32)
        if faults is not None
        else None
    )
    quarantine = [0] * n_ues
    active_hist = np.zeros((n_slots, n_ues), np.int32)
    raw_hist = np.zeros((n_slots, n_ues), np.int32)
    pending_hist = np.zeros((n_slots, n_ues), np.int32)
    quar_hist = np.zeros((n_slots, n_ues), np.int32)

    for s in range(n_slots):
        for u in range(n_ues):
            if attached is not None:
                if not attached[s, u]:
                    # detached: no telemetry, no decision, no boundary —
                    # the streaming history's sentinel marks the gap
                    active_hist[s, u] = -1
                    raw_hist[s, u] = -1
                    pending_hist[s, u] = -1
                    quar_hist[s, u] = -1
                    continue
                if s == 0 or not attached[s - 1, u]:
                    # (re)attach cold start, mirroring the device
                    # admission pass: fresh ring, default register,
                    # cleared hysteresis streak — and a clean fault
                    # state (age, trip window, quarantine)
                    rings[u] = ring_init(cfg.window_slots, n_feat)
                    active[u] = cfg.default_mode
                    pending[u] = cfg.default_mode
                    streak[u] = 0
                    age[u] = 0
                    quarantine[u] = 0
                    if trip_ring is not None:
                        trip_ring[u] = 0
            in_quar = quarantine[u] > 0
            active_hist[s, u] = active[u]
            quar_hist[s, u] = 1 if in_quar else 0
            if resolved is None or resolved.telemetry_valid[s, u]:
                rings[u] = ring_push(rings[u], jnp.asarray(features[s, u]))
            window = ring_window_mean(rings[u], cfg.window_slots)
            decide = s % cfg.period_slots == 0
            heard = decide and (
                resolved is None or resolved.decision_valid[s, u]
            )
            if not heard:
                # hold / lost-decision slot: register and streak frozen,
                # held raw reported, decision age keeps aging
                raw = pending[u]
            else:
                pol = policy_for_ue[u]
                if isinstance(pol, ThresholdPolicy):
                    raw = int(pol(window, prev_mode=pending[u]))
                else:
                    raw = int(pol(window))
                if raw == pending[u]:
                    streak[u] = 0
                else:
                    streak[u] += 1
                    if streak[u] >= cfg.hysteresis_slots:
                        pending[u] = raw
                        streak[u] = 0
                if resolved is not None:
                    age[u] = 0  # a heard decision refreshes the TTL
            raw_hist[s, u] = raw
            pending_hist[s, u] = pending[u]
            # boundary into slot s+1 (with the TTL decay under faults)
            nxt = pending[u]
            if resolved is not None:
                if age[u] >= cfg.ttl_slots:
                    nxt = cfg.default_mode
                    pending[u] = cfg.default_mode
                age[u] += 1
            if nxt != active[u]:
                n_switches[u] += 1
            active[u] = nxt
            if resolved is not None:
                # circuit breaker: this slot's health/audit trip enters
                # the rolling window; M trips quarantine the AI expert
                if trips is not None:
                    trip = bool(trips[s, u])
                else:
                    exec_mode = cfg.default_mode if in_quar else (
                        active_hist[s, u]
                    )
                    trip = bool(
                        resolved.corrupt[s, u]
                        and exec_mode == 0
                        and faults.corruption_kind in ("nan", "inf")
                    )
                trip_ring[u, s % faults.breaker_window] = int(trip)
                newly = (
                    not in_quar
                    and int(trip_ring[u].sum()) >= faults.breaker_trips
                )
                if newly:
                    trip_ring[u] = 0
                    quarantine[u] = faults.breaker_cooldown
                else:
                    quarantine[u] = max(quarantine[u] - 1, 0)

    return {
        "active_mode": active_hist,
        "raw_decision": raw_hist,
        "pending_mode": pending_hist,
        "quarantined": quar_hist,
        "n_switches": np.asarray(n_switches, np.int32),
    }
