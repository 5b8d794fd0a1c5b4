"""Jit'd public wrappers for the ARCHES switch kernel.

Handles what the raw 2-D kernel does not: arbitrary shapes (flatten + pad to
tile multiples), complex dtypes (viewed as float32 pairs), and per-expert
pytrees (leaf-wise switching).  On non-TPU backends the kernel runs in Pallas
interpret mode so the whole framework is testable on CPU.
"""

from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp

from repro.kernels.switch_select import switch_select as _k

_PAD_BLOCK_ROWS = 128
_PAD_BLOCK_COLS = 512
_PAD_ELEMS = _PAD_BLOCK_ROWS * _PAD_BLOCK_COLS


def _use_interpret() -> bool:
    return jax.default_backend() != "tpu"


def _to_real_view(x: jax.Array):
    """View complex leaves as trailing float pairs; return (array, undo)."""
    if jnp.issubdtype(x.dtype, jnp.complexfloating):
        real_dtype = jnp.float32 if x.dtype == jnp.complex64 else jnp.float64
        y = jnp.stack([x.real, x.imag], axis=-1).astype(real_dtype)

        def undo(z):
            z = z.reshape(x.shape + (2,))
            return (z[..., 0] + 1j * z[..., 1]).astype(x.dtype)

        return y, undo
    return x, lambda z: z.reshape(x.shape)


def switch_select_leaf(
    mode: jax.Array,
    alternatives: Sequence[jax.Array],
    designated: jax.Array,
    *,
    interpret: bool | None = None,
) -> jax.Array:
    """Switch a single array leaf. ``mode==0`` keeps ``designated``."""
    if interpret is None:
        interpret = _use_interpret()
    des_view, undo = _to_real_view(designated)
    alt_views = [_to_real_view(a)[0] for a in alternatives]

    flat = des_view.reshape(-1)
    n = flat.shape[0]
    pad = (-n) % _PAD_ELEMS
    rows = (n + pad) // _PAD_BLOCK_COLS

    def prep(v):
        f = v.reshape(-1)
        f = jnp.pad(f, (0, pad))
        return f.reshape(rows, _PAD_BLOCK_COLS)

    des2 = prep(des_view)
    alt2 = jnp.stack([prep(a) for a in alt_views], axis=0)
    out2 = _k.switch_select_2d(
        mode,
        alt2,
        des2,
        block_rows=min(_PAD_BLOCK_ROWS, rows),
        block_cols=_PAD_BLOCK_COLS,
        interpret=interpret,
    )
    return undo(out2.reshape(-1)[:n])


def _batched_tile_prep(n: int):
    """Padding plan for per-UE payloads of ``n`` scalars each.

    Per-UE payloads are typically far smaller than the scalar-path pad
    quantum; pad rows to the float32 sublane minimum (8) for small leaves
    and to the full block height for large ones so the tile always divides.
    Returns ``(rows, cols, prep)`` where ``prep(v, lead)`` reshapes a
    ``(lead, ...)`` real view to the padded ``(lead, rows, cols)`` layout.
    """
    cols = _PAD_BLOCK_COLS
    pad = (-n) % cols
    rows = (n + pad) // cols
    row_quantum = 8 if rows <= _PAD_BLOCK_ROWS else _PAD_BLOCK_ROWS
    row_pad = (-rows) % row_quantum
    rows = rows + row_pad

    def prep(v, lead):
        f = v.reshape(lead, -1)
        f = jnp.pad(f, ((0, 0), (0, pad + row_pad * cols)))
        return f.reshape(lead, rows, cols)

    return rows, cols, prep


def switch_select_batched_leaf(
    modes: jax.Array,
    alternatives: Sequence[jax.Array],
    designated: jax.Array,
    *,
    interpret: bool | None = None,
) -> jax.Array:
    """Per-UE switch of one leaf with a leading UE axis.

    ``modes`` is ``(n_ues,)``; every leaf is ``(n_ues, ...)`` and UE ``u``'s
    slice keeps the designated output (``modes[u]==0``) or takes alternative
    ``modes[u]-1``.
    """
    if interpret is None:
        interpret = _use_interpret()
    n_ues = designated.shape[0]
    des_view, undo = _to_real_view(designated)
    alt_views = [_to_real_view(a)[0] for a in alternatives]

    n = des_view.reshape(n_ues, -1).shape[1]
    rows, cols, prep = _batched_tile_prep(n)
    des2 = prep(des_view, n_ues)
    alt2 = jnp.stack([prep(a, n_ues) for a in alt_views], axis=0)
    out2 = _k.switch_select_batched_2d(
        modes,
        alt2,
        des2,
        block_rows=min(_PAD_BLOCK_ROWS, rows),
        block_cols=cols,
        interpret=interpret,
    )
    return undo(out2.reshape(n_ues, -1)[:, :n])


def switch_gather_batched_leaf(
    src: jax.Array,
    compact: jax.Array,
    designated: jax.Array,
    *,
    interpret: bool | None = None,
) -> jax.Array:
    """Scatter one leaf's compact sub-batch back over the full UE batch.

    ``src`` is ``(n_ues,)``; ``designated`` is ``(n_ues, ...)`` (the dense
    baseline), ``compact`` ``(capacity, ...)`` with matching trailing shape.
    UE ``u`` receives compact row ``src[u]`` when ``src[u] >= 0`` and keeps
    its baseline otherwise.
    """
    if interpret is None:
        interpret = _use_interpret()
    n_ues = designated.shape[0]
    capacity = compact.shape[0]
    des_view, undo = _to_real_view(designated)
    comp_view = _to_real_view(compact)[0]

    n = des_view.reshape(n_ues, -1).shape[1]
    rows, cols, prep = _batched_tile_prep(n)
    out2 = _k.switch_gather_batched_2d(
        src,
        prep(comp_view, capacity),
        prep(des_view, n_ues),
        block_rows=min(_PAD_BLOCK_ROWS, rows),
        block_cols=cols,
        interpret=interpret,
    )
    return undo(out2.reshape(n_ues, -1)[:, :n])


@functools.partial(jax.jit, static_argnames=("backend",))
def switch_scatter(src, compact, designated, *, backend: str = "auto"):
    """Fused un-compaction over per-expert pytrees (gated execution path).

    The gated bank runs the expensive expert on a dense capacity-``K``
    sub-batch only; this op scatters those results back over the
    cheap-expert baseline in one pass per leaf: UE ``u`` takes compact row
    ``src[u]`` when ``src[u] >= 0`` and keeps its baseline buffer otherwise.

    Shape discipline: every index in ``src`` addresses a row of *this
    call's* ``compact`` operand — there is no global UE numbering.  Under
    the sharded multi-cell engine (``repro.core.topology``) the op runs
    inside ``shard_map`` with ``n_ues`` == the shard-local UE slice and
    ``capacity`` == the per-shard gated capacity, so the scatter is a
    purely local data movement (no cross-device collective; the
    distributed tests audit the lowered HLO for this).

    Args:
      src: ``(n_ues,)`` int32 compact-row indices (negative == keep).
      compact: pytree of ``(capacity, ...)`` leaves (``capacity >= 1``).
      designated: structurally identical pytree of ``(n_ues, ...)`` leaves,
        aliased to the output on the kernel path.
      backend: ``"pallas"`` (TPU kernel), ``"ref"`` (pure-jnp gather/select)
        or ``"auto"`` — the kernel on a TPU, ref off the chip.  Both are
        bitwise-equal by construction: neither path does arithmetic on the
        payload.

    Returns:
      The un-compacted pytree (baseline with gated results scattered in).
    """
    src = jnp.asarray(src, jnp.int32)
    if backend == "auto":
        backend = "pallas" if jax.default_backend() == "tpu" else "ref"
    if backend == "ref":
        from repro.kernels.switch_select.ref import switch_gather_batched_tree_ref

        return switch_gather_batched_tree_ref(src, compact, designated)
    if backend != "pallas":
        raise ValueError(f"unknown switch_scatter backend {backend!r}")
    return jax.tree.map(
        lambda c, d: switch_gather_batched_leaf(src, c, d, interpret=False),
        compact,
        designated,
    )


@functools.partial(jax.jit, static_argnames=("interpret",))
def switch_select(mode, outputs: Sequence, designated_idx: int = 0, *, interpret=None):
    """Switch over a list of per-expert pytrees (paper's N-expert bank).

    Args:
      mode: int32 scalar (``0`` selects ``outputs[designated_idx]`` — no-op
        path; ``k>0`` selects the k-th non-designated expert in bank order)
        OR an ``(n_ues,)`` int32 vector for the batched multi-UE engine, in
        which case every leaf must carry a leading UE axis and UE ``u``
        independently follows ``mode[u]``.
      outputs: list of structurally identical pytrees, one per expert, with
        the designated expert first (``designated_idx`` must be 0 — the bank
        reorders before calling).

    Returns:
      The selected pytree, aliased onto the designated buffers.
    """
    if designated_idx != 0:
        raise ValueError("bank must place the designated expert first")
    mode = jnp.asarray(mode, jnp.int32)
    designated, *alternatives = outputs
    if mode.ndim == 1:
        return jax.tree.map(
            lambda d, *alts: switch_select_batched_leaf(
                mode, alts, d, interpret=interpret
            ),
            designated,
            *alternatives,
        )
    return jax.tree.map(
        lambda d, *alts: switch_select_leaf(mode, alts, d, interpret=interpret),
        designated,
        *alternatives,
    )
