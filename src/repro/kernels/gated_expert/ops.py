"""Public wrapper for the fused gated expert hot path.

(Not jit'd at this level — ``folded`` carries static ints and the op always
runs inside the engine's already-jitted scan body.)

Handles what the raw kernel does not: complex-to-real viewing and the
layout transposes between the engine's ``(U, ant, S, Np)`` LS input /
``(U, ant, 1, n_sc, S)`` estimate contract and the kernel's per-antenna
2-D views (rows ``re/im * S + symbol``, lanes = pilot index, output split
by subcarrier parity), plus backend dispatch.  On a TPU ``"auto"`` runs the
compiled kernel; elsewhere it runs the unfused jnp composition
(``ref.py``), because a Pallas kernel off the chip only exists in interpret
mode, which is a test vehicle and not a serving path.  The view plumbing
is pure data movement: kept UEs round-trip their baseline bytes untouched.
Computed UEs carry the kernel's own forward, which agrees with the unfused
XLA forward to f32 rounding (its GEMMs are blocked per antenna).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.gated_expert import gated_expert as _k
from repro.kernels.gated_expert.ref import gated_expert_apply_ref
from repro.kernels.switch_select.ops import _use_interpret


def _ls_view(h_ls: jax.Array) -> jax.Array:
    """``(U, ant, S, Np)`` complex -> kernel input ``(U, ant, R_in, Hp)``."""
    n_ues, n_ant, n_sym, n_p = h_ls.shape
    x = jnp.stack([h_ls.real, h_ls.imag], axis=2).astype(jnp.float32)
    x = x.reshape(n_ues, n_ant, 2 * n_sym, n_p)  # rows re/im * S + symbol
    return jnp.pad(x, ((0, 0), (0, 0),
                       (0, _k.pad_rows(2 * n_sym) - 2 * n_sym),
                       (0, _k.pad_lanes(n_p) - n_p)))


def _estimate_view(h: jax.Array, n_p: int) -> jax.Array:
    """``(U, ant, 1, 2*Np, S)`` complex -> kernel buffer ``(U, ant, 2, R_out,
    Hp)``: subcarrier ``2k + j`` lands on parity ``j``, lane ``k``."""
    n_ues, n_ant, _, n_sc, n_sym = h.shape
    if n_sc != 2 * n_p:
        raise ValueError(f"{n_sc} subcarriers for {n_p} comb-2 pilots")
    b = h[:, :, 0].reshape(n_ues, n_ant, n_p, 2, n_sym)  # (.., k, j, s)
    v = jnp.stack([b.real, b.imag], axis=3).astype(jnp.float32)
    v = jnp.transpose(v, (0, 1, 4, 3, 5, 2))  # (U, ant, j, re/im, s, k)
    v = v.reshape(n_ues, n_ant, 2, 2 * n_sym, n_p)
    return jnp.pad(v, ((0, 0), (0, 0), (0, 0),
                       (0, _k.pad_rows(2 * n_sym) - 2 * n_sym),
                       (0, _k.pad_lanes(n_p) - n_p)))


def _from_estimate_view(v: jax.Array, n_p: int, n_sym: int) -> jax.Array:
    """Inverse of ``_estimate_view``."""
    n_ues, n_ant = v.shape[:2]
    v = v[..., : 2 * n_sym, :n_p].reshape(n_ues, n_ant, 2, 2, n_sym, n_p)
    h = (v[:, :, :, 0] + 1j * v[:, :, :, 1]).astype(jnp.complex64)
    h = jnp.transpose(h, (0, 1, 4, 2, 3))  # (U, ant, k, j, s)
    return h.reshape(n_ues, n_ant, 2 * n_p, n_sym)[:, :, None]


def gated_expert_apply(
    idx,
    src,
    h_ls,
    designated,
    folded,
    *,
    compute_dtype=None,
    backend: str = "auto",
    interpret: bool | None = None,
):
    """Run the gated AI expert fused: compact -> expert -> scatter.

    One kernel replaces the unfused gather / expert / ``switch_scatter``
    triple: the compaction index vector steers the input DMA (no
    materialized capacity-``K`` sub-batch in HBM) and the output aliases
    the baseline buffers (the scatter is the output DMA).  Under the
    sharded engine this runs inside ``shard_map`` on shard-local operands —
    per-shard compaction means no collective (the distributed tests audit
    the lowered HLO).

    Args:
      idx: ``(capacity,)`` int32 — UE index of each compact row (a slice of
        a permutation; rows past the last selected UE name arbitrary
        distinct non-selected UEs and are treated as padding).
      src: ``(n_ues,)`` int32 — UE -> compact-row map; negative keeps the
        baseline.  ``valid`` padding flags are derived as ``src[idx] >= 0``.
      h_ls: ``(n_ues, n_ant, n_dmrs_sym, n_pilot_sc)`` complex LS input.
      designated: ``(n_ues, n_ant, 1, n_sc, n_dmrs_sym)`` complex baseline
        estimates (aliased through the kernel path).
      folded: pre-folded expert params (``fold_ai_params``).
      compute_dtype: ``None`` (f32) or ``jnp.bfloat16`` (half the GEMM
        operand bytes, f32 accumulation).
      backend: ``"pallas"`` (fused kernel), ``"ref"`` (unfused jnp) or
        ``"auto"`` — the kernel on a TPU, the unfused jnp path elsewhere.
      interpret: force Pallas interpret mode (tests); default = non-TPU.

    Returns:
      The baseline pytree with the gated expert's outputs scattered in.
    """
    idx = jnp.asarray(idx, jnp.int32)
    src = jnp.asarray(src, jnp.int32)
    if backend == "auto":
        backend = "pallas" if jax.default_backend() == "tpu" else "ref"
    if backend == "ref":
        return gated_expert_apply_ref(
            idx, src, h_ls, designated, folded, compute_dtype=compute_dtype
        )
    if backend != "pallas":
        raise ValueError(f"unknown gated_expert_apply backend {backend!r}")
    if interpret is None:
        interpret = _use_interpret()

    n_p, n_sym = h_ls.shape[3], h_ls.shape[2]
    valid = (jnp.take(src, idx) >= 0).astype(jnp.int32)
    out = _k.gated_expert_fused(
        idx, valid, _ls_view(h_ls), _estimate_view(designated, n_p),
        _k.kernel_params(folded), n_valid=n_p,
        compute_dtype=compute_dtype, interpret=interpret,
    )
    return _from_estimate_view(out, n_p, n_sym)
