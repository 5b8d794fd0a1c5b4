"""Switchable expert bank (paper 2, 3.1).

A bank of N experts executes on the same input; a switch selects the
designated output.  Three execution modes:

* ``CONCURRENT`` — every expert runs each slot and the Pallas switch kernel
  (``repro.kernels.switch_select``) selects the output.  Zero switching
  latency; exposes all expert outputs for online benchmarking (this is the
  mode the paper uses for all experiments).
* ``SELECTED_ONLY`` — only the active expert executes, via ``jax.lax.switch``
  (XLA conditional: exactly one branch runs).  Saves compute/energy at the
  cost of at least a one-slot activation delay — quantified by the
  ``cost_model`` below.
* ``GATED`` — the batched multi-UE compromise between the two: the cheap
  non-designated experts run densely on every UE, while the designated
  (expensive) expert runs only on the UEs whose mode selects it, compacted
  into a dense capacity-``K`` sub-batch (stable cumsum partition, static
  shapes), then scattered back over the cheap baseline by the fused
  ``switch_scatter`` pass.  Compute scales with the *selected* expert mix —
  the performance-per-watt posture the paper's Fig. 11 argues for — and the
  output is bitwise-equal to ``CONCURRENT`` on the same mode vector as long
  as no UE overflows the capacity.  UEs past capacity fall back to the
  fail-safe ``default_mode`` expert for that slot (the real-time analogue of
  the paper's slot-boundary guarantee) and are flagged in
  ``BankOutput.overflow``.

Mode numbering follows the paper: the bank is constructed with the
*designated* expert first (mode 0 == its output is already in the downstream
buffer; for the channel-estimation case study that is the AI estimator) and
the fail-safe conventional expert is whatever index the caller passes as
``default_mode`` (mode 1 == MMSE in the case study).
"""

from __future__ import annotations

import dataclasses
import enum
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp

from repro.kernels.switch_select import switch_scatter, switch_select


def coerce_enum(cls: type, value, noun: str):
    """Accept an enum member or its string value (the spec/JSON form).

    Shared by the spec-facing enums (``ExecutionMode`` here,
    ``ExecutionPath`` in ``repro.core.session``) so their coercion and
    error shape cannot drift apart.
    """
    if isinstance(value, cls):
        return value
    try:
        return cls(str(value).lower())
    except ValueError:
        raise ValueError(
            f"unknown {noun} {value!r}; one of {[m.value for m in cls]}"
        ) from None


class ExecutionMode(enum.Enum):
    CONCURRENT = "concurrent"
    SELECTED_ONLY = "selected_only"
    GATED = "gated"

    @classmethod
    def coerce(cls, value: "ExecutionMode | str") -> "ExecutionMode":
        return coerce_enum(cls, value, "execution mode")


@dataclasses.dataclass(frozen=True)
class Expert:
    """One entry of the bank.

    ``fn(params, *inputs) -> output`` must return structurally identical
    pytrees across all experts in a bank (the uniform downstream interface).
    ``flops``/``bytes_hbm`` are static per-call costs used by the
    energy/utilization proxy (DESIGN.md 2).  In the batched multi-UE engine
    a "call" serves one UE-slot, so these are per-UE-slot costs and the
    executed-cost accounting below multiplies by served-UE counts.
    """

    name: str
    fn: Callable[..., Any]
    params: Any = None
    flops: float = 0.0
    bytes_hbm: float = 0.0


def _batched_nmse(selected, baseline) -> jax.Array:
    """Per-UE NMSE of ``selected`` vs ``baseline`` across all leaves.

    Both are pytrees of ``(n_ues, ...)`` leaves; returns ``(n_ues,)`` f32
    ``sum |sel - base|^2 / sum |base|^2`` (sums over every non-UE axis and
    every leaf).  The in-scan accuracy audit for reduced-precision gated
    experts: no ground truth exists inside the scan, so divergence is
    measured against the always-computed fail-safe baseline.
    """

    def powers(s, b):
        d = s - b
        axes = tuple(range(1, d.ndim))
        err = jnp.sum(jnp.abs(d).astype(jnp.float32) ** 2, axis=axes)
        ref = jnp.sum(jnp.abs(b).astype(jnp.float32) ** 2, axis=axes)
        return err, ref

    pairs = jax.tree.leaves(jax.tree.map(powers, selected, baseline),
                            is_leaf=lambda x: isinstance(x, tuple))
    err = sum(p[0] for p in pairs)
    ref = sum(p[1] for p in pairs)
    return err / jnp.maximum(ref, jnp.float32(1e-30))


@dataclasses.dataclass(frozen=True)
class BankOutput:
    selected: Any  # pytree — contents of the designated buffer post-switch
    all_outputs: tuple | None  # per-expert outputs (concurrent mode only)
    mode: jax.Array
    # -- executed-cost accounting (traced; ride the slot scan) --------------
    # UEs each expert actually served this call ((n_experts,) int32).  In
    # CONCURRENT mode every expert serves every UE; in GATED mode the
    # designated expert serves only the compacted (capacity-capped) UEs.
    executed_ue: jax.Array | None = None
    # expert index that produced each UE's output ((n_ues,) int32; batched
    # calls only).  Differs from ``mode`` exactly on capacity overflow.
    served_by: jax.Array | None = None
    # capacity-overflow flags ((n_ues,) bool; GATED only): UE selected the
    # gated expert but fell back to ``default_mode`` this slot.
    overflow: jax.Array | None = None
    # accuracy-audit flags ((n_ues,) bool; GATED + audit_threshold only):
    # the gated expert served this UE but its output failed the in-scan
    # NMSE audit vs the dense fail-safe baseline, so the baseline was kept.
    # The expert still *executed* for the UE (cost accounting counts it).
    audit_tripped: jax.Array | None = None
    # fail-safe baseline output (pytree of (n_ues, ...) leaves; batched calls
    # only): the densely-run default expert's output, the revert target for
    # the in-scan health screen (fault injection) and the NMSE audit.
    baseline: Any = None


class ExpertBank:
    """N-expert switchable bank with a uniform downstream interface."""

    def __init__(
        self,
        experts: Sequence[Expert],
        *,
        default_mode: int = 1,
        execution_mode: ExecutionMode = ExecutionMode.CONCURRENT,
        use_pallas_switch: bool = True,
        gated_capacity: int | None = None,
        gated_fused_apply: Callable[..., Any] | None = None,
        audit_threshold: float | None = None,
    ):
        if len(experts) < 2:
            raise ValueError("an expert bank needs at least 2 experts")
        if not 0 <= default_mode < len(experts):
            raise ValueError(f"default_mode {default_mode} out of range")
        if execution_mode is ExecutionMode.GATED and default_mode == 0:
            raise ValueError(
                "GATED gates the designated expert (mode 0); the fail-safe "
                "default_mode must be a different, cheap expert"
            )
        if gated_capacity is not None and gated_capacity < 0:
            raise ValueError(f"gated_capacity {gated_capacity} must be >= 0")
        if gated_fused_apply is not None and (
            execution_mode is not ExecutionMode.GATED
        ):
            raise ValueError("gated_fused_apply requires GATED execution")
        if audit_threshold is not None:
            if execution_mode is not ExecutionMode.GATED:
                raise ValueError(
                    "audit_threshold requires GATED execution (the audit "
                    "compares against the densely-run fail-safe baseline)"
                )
            if not audit_threshold > 0:
                raise ValueError(
                    f"audit_threshold {audit_threshold} must be > 0"
                )
        self.experts = tuple(experts)
        self.default_mode = default_mode
        self.execution_mode = execution_mode
        self.use_pallas_switch = use_pallas_switch
        #: dense sub-batch size for GATED execution; ``None`` == full batch
        #: (no overflow possible), ``0`` == gated expert never runs.
        self.gated_capacity = gated_capacity
        #: optional fused hot path for GATED: ``(idx, src, base, *inputs) ->
        #: selected`` replaces the gather / expert-fn / scatter triple with
        #: one kernel (``repro.kernels.gated_expert``).  Must keep every
        #: non-served UE's baseline bitwise and compute the same expert
        #: (to f32 rounding) for served ones.
        self.gated_fused_apply = gated_fused_apply
        #: optional in-scan accuracy audit for GATED: per-UE NMSE of the
        #: gated expert's output vs the fail-safe baseline; UEs whose NMSE
        #: exceeds the threshold (or is NaN/inf) revert to the baseline and
        #: are flagged in ``BankOutput.audit_tripped``.
        self.audit_threshold = audit_threshold

    @property
    def n_experts(self) -> int:
        return len(self.experts)

    @property
    def names(self) -> tuple[str, ...]:
        return tuple(e.name for e in self.experts)

    def __call__(self, mode: jax.Array, *inputs) -> BankOutput:
        """Run the bank.

        ``mode`` is an int32 scalar, or an ``(n_ues,)`` vector for the
        batched multi-UE engine — in which case every expert output must
        carry a leading UE axis and UE ``u`` receives expert ``mode[u]``'s
        output (different UEs can run different experts in the same slot).
        """
        mode = jnp.asarray(mode, jnp.int32)
        if self.execution_mode is ExecutionMode.GATED:
            if mode.ndim != 1:
                raise ValueError(
                    "GATED execution is the batched path: mode must be an "
                    "(n_ues,) vector (use SELECTED_ONLY for scalar gating)"
                )
            return self._run_gated(mode, *inputs)
        if self.execution_mode is ExecutionMode.CONCURRENT:
            return self._run_concurrent(mode, *inputs)
        return self._run_selected(mode, *inputs)

    def _run_concurrent(self, mode: jax.Array, *inputs) -> BankOutput:
        outputs = tuple(e.fn(e.params, *inputs) for e in self.experts)
        if self.use_pallas_switch:
            selected = switch_select(mode, list(outputs))
        elif mode.ndim == 1:  # batched oracle path
            from repro.kernels.switch_select.ref import (
                switch_select_batched_tree_ref,
            )

            selected = switch_select_batched_tree_ref(mode, list(outputs))
        else:  # oracle path (used by the property tests)
            stacked = jax.tree.map(lambda *ls: jnp.stack(ls, 0), *outputs)
            selected = jax.tree.map(lambda s: jnp.take(s, mode, axis=0), stacked)
        n_served = (
            jnp.full((self.n_experts,), mode.shape[0], jnp.int32)
            if mode.ndim == 1
            else jnp.ones((self.n_experts,), jnp.int32)
        )
        return BankOutput(
            selected=selected,
            all_outputs=outputs,
            mode=mode,
            executed_ue=n_served,
            served_by=mode if mode.ndim == 1 else None,
            baseline=outputs[self.default_mode] if mode.ndim == 1 else None,
        )

    def _run_selected(self, mode: jax.Array, *inputs) -> BankOutput:
        if mode.ndim == 1:
            # Per-UE modes make "run only the selected expert" ill-posed:
            # any expert some UE selects must execute.  Degenerate to the
            # concurrent cost envelope and gather per UE (predication), but
            # keep the SELECTED_ONLY interface (no all_outputs exposure).
            # GATED execution is the cost-scaling alternative.
            from repro.kernels.switch_select.ref import (
                switch_select_batched_tree_ref,
            )

            outputs = [e.fn(e.params, *inputs) for e in self.experts]
            selected = switch_select_batched_tree_ref(mode, outputs)
            return BankOutput(
                selected=selected,
                all_outputs=None,
                mode=mode,
                executed_ue=jnp.full((self.n_experts,), mode.shape[0], jnp.int32),
                served_by=mode,
                baseline=outputs[self.default_mode],
            )
        branches = [
            (lambda e: (lambda *xs: e.fn(e.params, *xs)))(e) for e in self.experts
        ]
        selected = jax.lax.switch(mode, branches, *inputs)
        return BankOutput(
            selected=selected,
            all_outputs=None,
            mode=mode,
            executed_ue=(jnp.arange(self.n_experts) == mode).astype(jnp.int32),
        )

    def _run_gated(self, mode: jax.Array, *inputs) -> BankOutput:
        """Compaction-gated execution: pay only for selected experts.

        Every input leaf must carry a leading ``(n_ues,)`` axis.  The
        cumsum-based stable partition and the static ``[:K]`` slice keep all
        shapes static, so this path compiles inside a ``lax.scan`` body.
        """
        n_ues = mode.shape[0]
        capacity = self.gated_capacity
        capacity = n_ues if capacity is None else min(capacity, n_ues)

        is_gated = mode == 0
        # stable partition: each selected UE's row in the compact sub-batch
        pos = jnp.cumsum(is_gated.astype(jnp.int32)) - 1
        within = jnp.logical_and(is_gated, pos < capacity)
        overflow = jnp.logical_and(is_gated, jnp.logical_not(within))
        src = jnp.where(within, pos, -1).astype(jnp.int32)
        # overflow UEs fall back to the fail-safe expert for this slot
        eff_mode = jnp.where(overflow, jnp.int32(self.default_mode), mode)

        # cheap experts run densely on all UEs
        alt_outputs = [e.fn(e.params, *inputs) for e in self.experts[1:]]
        if len(alt_outputs) == 1:
            base = alt_outputs[0]
        else:
            from repro.kernels.switch_select.ref import (
                switch_select_batched_tree_ref,
            )

            # values at gated UEs are placeholders (overwritten below)
            base = switch_select_batched_tree_ref(
                jnp.maximum(eff_mode, 1) - 1, alt_outputs
            )

        if capacity > 0:
            # gather the selected UEs' inputs to the front, stable order
            order = jnp.argsort(jnp.logical_not(is_gated).astype(jnp.int32),
                                stable=True)
            idx = order[:capacity]
            if self.gated_fused_apply is not None:
                # fused hot path: one kernel does gather + expert + scatter
                selected = self.gated_fused_apply(idx, src, base, *inputs)
            else:
                compact_inputs = jax.tree.map(
                    lambda x: jnp.take(x, idx, axis=0), inputs
                )
                gated = self.experts[0]
                compact_out = gated.fn(gated.params, *compact_inputs)
                selected = switch_scatter(
                    src, compact_out, base,
                    backend="auto" if self.use_pallas_switch else "ref",
                )
        else:
            selected = base

        served_by = jnp.where(within, 0, eff_mode).astype(jnp.int32)
        audit_tripped = None
        if self.audit_threshold is not None and capacity > 0:
            nmse = _batched_nmse(selected, base)
            # NaN/inf-safe trip: anything NOT provably within the threshold
            # trips (a diverged bf16 forward must not pass the audit)
            tripped = jnp.logical_and(
                within, jnp.logical_not(nmse <= self.audit_threshold)
            )
            selected = jax.tree.map(
                lambda s, b: jnp.where(
                    tripped.reshape((-1,) + (1,) * (s.ndim - 1)), b, s
                ),
                selected,
                base,
            )
            served_by = jnp.where(
                tripped, jnp.int32(self.default_mode), served_by
            )
            audit_tripped = tripped

        n_gated = jnp.sum(within.astype(jnp.int32))
        executed = jnp.concatenate(
            [n_gated[None], jnp.full((self.n_experts - 1,), n_ues, jnp.int32)]
        )
        return BankOutput(
            selected=selected,
            all_outputs=None,
            mode=mode,
            executed_ue=executed,
            served_by=served_by,
            overflow=overflow,
            audit_tripped=audit_tripped,
            baseline=base,
        )

    # ---- static cost model (drives the energy/utilization proxy) ----
    def flops_for(self, mode: int | None = None) -> float:
        """FLOPs per slot: all experts (concurrent) or one (selected-only)."""
        if self.execution_mode is ExecutionMode.CONCURRENT:
            return float(sum(e.flops for e in self.experts))
        if self.execution_mode is ExecutionMode.GATED:
            raise ValueError(
                "GATED cost depends on the realized mode mix: use "
                "executed_flops(out) / executed_flops_per_ue(out)"
            )
        assert mode is not None
        return float(self.experts[mode].flops)

    def bytes_for(self, mode: int | None = None) -> float:
        if self.execution_mode is ExecutionMode.CONCURRENT:
            return float(sum(e.bytes_hbm for e in self.experts))
        if self.execution_mode is ExecutionMode.GATED:
            raise ValueError(
                "GATED cost depends on the realized mode mix: use "
                "executed_bytes(out)"
            )
        assert mode is not None
        return float(self.experts[mode].bytes_hbm)

    # ---- executed cost model (scales with the realized expert mix) ----

    def _executed(self, out: BankOutput, costs: jax.Array) -> jax.Array:
        if out.executed_ue is None:
            raise ValueError("BankOutput carries no executed_ue counts")
        return jnp.sum(out.executed_ue.astype(jnp.float32) * costs)

    def executed_flops(self, out: BankOutput) -> jax.Array:
        """FLOPs this call actually executed (traced scalar).

        ``sum_e served_ues[e] * flops[e]`` — in CONCURRENT mode this equals
        ``n_ues * flops_for()``; in GATED mode the designated expert
        contributes only its capacity-capped served count, so the total
        scales linearly with the realized AI share.
        """
        return self._executed(
            out, jnp.asarray([e.flops for e in self.experts], jnp.float32)
        )

    def executed_bytes(self, out: BankOutput) -> jax.Array:
        """HBM bytes this call actually moved (traced scalar)."""
        return self._executed(
            out, jnp.asarray([e.bytes_hbm for e in self.experts], jnp.float32)
        )

    def provisioned_flops(self, n_ues: int) -> float:
        """Static per-slot FLOPs the hardware is provisioned for (GATED).

        The compact sub-batch has static capacity ``K``, so the gated
        expert's GEMMs always process ``K`` rows — ``executed_flops`` counts
        the *served* rows (the useful fraction); the difference is padding
        waste when fewer UEs select the gated expert than ``K``.
        """
        if self.execution_mode is ExecutionMode.CONCURRENT:
            return float(n_ues * sum(e.flops for e in self.experts))
        if self.execution_mode is not ExecutionMode.GATED:
            raise ValueError("provisioned cost is per-mode in SELECTED_ONLY: "
                             "use n_ues * flops_for(mode)")
        cap = n_ues if self.gated_capacity is None else min(
            self.gated_capacity, n_ues
        )
        return float(
            cap * self.experts[0].flops
            + n_ues * sum(e.flops for e in self.experts[1:])
        )

    def executed_flops_per_ue(self, out: BankOutput) -> jax.Array:
        """Per-UE executed FLOPs ((n_ues,) float32; batched calls only).

        A UE's slot cost is every densely-run expert plus — under gating —
        the designated expert only if it actually served this UE.  Summing
        over UEs reproduces ``executed_flops``.
        """
        if out.served_by is None:
            raise ValueError("per-UE accounting needs a batched (vector) call")
        flops = jnp.asarray([e.flops for e in self.experts], jnp.float32)
        if self.execution_mode is ExecutionMode.GATED:
            dense = jnp.sum(flops[1:])
            ai_ran = out.served_by == 0
            if out.audit_tripped is not None:
                # audit-tripped UEs were *served* by the fail-safe but the
                # gated expert still executed for them — the cost is real
                ai_ran = jnp.logical_or(ai_ran, out.audit_tripped)
            return dense + flops[0] * ai_ran.astype(jnp.float32)
        # concurrent / degenerate selected-only: every expert ran every UE
        return jnp.full(out.served_by.shape, jnp.sum(flops), jnp.float32)
