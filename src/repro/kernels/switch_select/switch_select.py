"""Pallas TPU switch kernel — the ARCHES zero-gap output selector (paper 3.2).

CUDA original (GH200): N experts write to per-expert buffers; downstream
stages always read one *designated* buffer (memory aliasing).  The switch
kernel is a **no-op** when the designated expert is active (``mode == 0``)
and a **coalesced copy** of the alternative expert's output otherwise
(measured 3.36 us vs 4.89 us in the paper, Fig. 8).

TPU adaptation (DESIGN.md 2): a Pallas kernel whose output *aliases* the
designated buffer via ``input_output_aliases`` (so downstream modules keep
reading a single fixed buffer regardless of how many experts exist), with the
``mode`` scalar *prefetched to SMEM* so it can steer the BlockSpec index maps
before the grid runs:

* ``mode == 0`` (designated expert active): every grid step maps input and
  output to tile ``(0, 0)`` and rewrites that tile with its own contents.
  Pallas only issues DMAs when a block index changes between grid steps, so
  the entire call costs a single-tile round-trip — the TPU analogue of the
  paper's no-op path (a pure no-op cannot be expressed through the Pallas
  output pipeline, which always writes its output blocks back).
* ``mode == k > 0``: tile ``(i, j)`` of alternative expert ``k-1`` is copied
  into the designated buffer through VMEM in lane-aligned ``(block_rows,
  block_cols)`` tiles — the analogue of the paper's coalesced-copy path.

The structural asymmetry of the CUDA kernel (cheap when AI is active,
full-tensor copy when the conventional expert is active) is therefore
preserved, tile-for-warp.

Layout contract: operands are 2-D ``(rows, cols)`` real arrays with
``rows % block_rows == 0`` and ``cols % block_cols == 0``; ``ops.py`` handles
flattening / complex-viewing / padding for arbitrary pytrees.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_ROWS = 256
DEFAULT_BLOCK_COLS = 256


def _switch_kernel(mode_ref, alt_ref, des_ref, out_ref):
    """Copy-or-refresh one tile, depending on the prefetched mode scalar."""
    mode = mode_ref[0]

    @pl.when(mode == 0)
    def _noop_path():
        # Identity rewrite of tile (0, 0) of the designated buffer; with the
        # constant index maps below this is the only tile ever touched.
        out_ref[...] = des_ref[...]

    @pl.when(mode != 0)
    def _copy_path():
        out_ref[...] = alt_ref[0]


def switch_select_2d(
    mode: jax.Array,
    alternatives: jax.Array,
    designated: jax.Array,
    *,
    block_rows: int = DEFAULT_BLOCK_ROWS,
    block_cols: int = DEFAULT_BLOCK_COLS,
    interpret: bool = False,
) -> jax.Array:
    """Select the active expert's output into the designated buffer.

    Args:
      mode: int32 scalar (or shape ``(1,)``); ``0`` selects ``designated``
        (no-op path), ``k > 0`` selects ``alternatives[k - 1]`` (copy path).
      alternatives: ``(n_alt, rows, cols)`` stacked non-designated expert
        outputs.
      designated: ``(rows, cols)`` designated buffer (donated / aliased to
        the output).
      block_rows / block_cols: VMEM tile shape; rows/cols must divide evenly.
      interpret: run in Pallas interpret mode (CPU validation).

    Returns:
      ``(rows, cols)`` array aliased onto ``designated``.
    """
    rows, cols = designated.shape
    n_alt = alternatives.shape[0]
    if alternatives.shape[1:] != (rows, cols):
        raise ValueError(
            f"alternatives {alternatives.shape} vs designated {designated.shape}"
        )
    block_rows = min(block_rows, rows)
    block_cols = min(block_cols, cols)
    if rows % block_rows or cols % block_cols:
        raise ValueError(
            f"shape ({rows},{cols}) not divisible by block "
            f"({block_rows},{block_cols}); use ops.switch_select for padding"
        )

    mode = jnp.asarray(mode, jnp.int32).reshape((1,))
    grid = (rows // block_rows, cols // block_cols)

    def _sel(mode_ref, i, j):
        z = jnp.zeros_like(i)
        keep = mode_ref[0] == 0
        return jnp.where(keep, z, i), jnp.where(keep, z, j)

    def alt_index(i, j, mode_ref):
        k = jnp.maximum(mode_ref[0] - 1, 0)
        bi, bj = _sel(mode_ref, i, j)
        return (k, bi, bj)

    def des_index(i, j, mode_ref):
        del i, j, mode_ref
        return (0, 0)

    def out_index(i, j, mode_ref):
        return _sel(mode_ref, i, j)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_rows, block_cols), alt_index),
            pl.BlockSpec((block_rows, block_cols), des_index),
        ],
        out_specs=pl.BlockSpec((block_rows, block_cols), out_index),
    )

    return pl.pallas_call(
        _switch_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((rows, cols), designated.dtype),
        input_output_aliases={2: 0},  # designated buffer -> output (zero-gap)
        interpret=interpret,
        name="switch_select",
    )(mode, alternatives, designated)


# -- batched multi-UE variant -------------------------------------------------


def _switch_kernel_batched(modes_ref, alt_ref, des_ref, out_ref):
    """Per-UE copy-or-refresh: grid dim 0 walks UEs, dims 1-2 walk tiles."""
    u = pl.program_id(0)
    mode = modes_ref[u]

    @pl.when(mode == 0)
    def _noop_path():
        out_ref[...] = des_ref[...]

    @pl.when(mode != 0)
    def _copy_path():
        out_ref[...] = alt_ref[0]


def switch_select_batched_2d(
    modes: jax.Array,
    alternatives: jax.Array,
    designated: jax.Array,
    *,
    block_rows: int = DEFAULT_BLOCK_ROWS,
    block_cols: int = DEFAULT_BLOCK_COLS,
    interpret: bool = False,
) -> jax.Array:
    """Per-UE switch: UE ``u`` keeps or copies according to ``modes[u]``.

    The multi-UE slot engine runs different experts for different UEs in the
    same slot; this kernel extends the scalar-mode contract with a leading UE
    axis.  Grid dimension 0 walks UEs, so each UE independently takes the
    no-op path (``modes[u] == 0``: only tile ``(u, 0, 0)`` is round-tripped)
    or the coalesced-copy path (``modes[u] == k > 0``: expert ``k-1``'s
    slice is copied tile-by-tile into UE ``u``'s designated buffer).

    Args:
      modes: ``(n_ues,)`` int32 per-UE mode vector.
      alternatives: ``(n_alt, n_ues, rows, cols)`` stacked non-designated
        expert outputs.
      designated: ``(n_ues, rows, cols)`` designated buffers (aliased to the
        output).

    Returns:
      ``(n_ues, rows, cols)`` array aliased onto ``designated``.
    """
    n_ues, rows, cols = designated.shape
    if alternatives.shape[1:] != (n_ues, rows, cols):
        raise ValueError(
            f"alternatives {alternatives.shape} vs designated {designated.shape}"
        )
    if modes.shape != (n_ues,):
        raise ValueError(f"modes {modes.shape} vs n_ues {n_ues}")
    block_rows = min(block_rows, rows)
    block_cols = min(block_cols, cols)
    if rows % block_rows or cols % block_cols:
        raise ValueError(
            f"shape ({rows},{cols}) not divisible by block "
            f"({block_rows},{block_cols}); use ops.switch_select for padding"
        )

    modes = jnp.asarray(modes, jnp.int32)
    grid = (n_ues, rows // block_rows, cols // block_cols)

    def _sel(modes_ref, u, i, j):
        z = jnp.zeros_like(i)
        keep = modes_ref[u] == 0
        return jnp.where(keep, z, i), jnp.where(keep, z, j)

    def alt_index(u, i, j, modes_ref):
        k = jnp.maximum(modes_ref[u] - 1, 0)
        bi, bj = _sel(modes_ref, u, i, j)
        return (k, u, bi, bj)

    def des_index(u, i, j, modes_ref):
        del i, j, modes_ref
        return (u, 0, 0)

    def out_index(u, i, j, modes_ref):
        bi, bj = _sel(modes_ref, u, i, j)
        return (u, bi, bj)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_rows, block_cols), alt_index),
            pl.BlockSpec((1, block_rows, block_cols), des_index),
        ],
        out_specs=pl.BlockSpec((1, block_rows, block_cols), out_index),
    )

    return pl.pallas_call(
        _switch_kernel_batched,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_ues, rows, cols), designated.dtype),
        input_output_aliases={2: 0},
        interpret=interpret,
        name="switch_select_batched",
    )(modes, alternatives, designated)


# -- compaction-gated variant -------------------------------------------------


def _gather_kernel_batched(src_ref, compact_ref, des_ref, out_ref):
    """Per-UE un-compaction: copy a compact-sub-batch row or keep the buffer."""
    u = pl.program_id(0)
    src = src_ref[u]

    @pl.when(src < 0)
    def _noop_path():
        out_ref[...] = des_ref[...]

    @pl.when(src >= 0)
    def _copy_path():
        out_ref[...] = compact_ref[...]


def switch_gather_batched_2d(
    src: jax.Array,
    compact: jax.Array,
    designated: jax.Array,
    *,
    block_rows: int = DEFAULT_BLOCK_ROWS,
    block_cols: int = DEFAULT_BLOCK_COLS,
    interpret: bool = False,
) -> jax.Array:
    """Scatter a dense capacity-``K`` sub-batch back over the full UE batch.

    The gated execution path runs the expensive expert only on the UEs that
    selected it, compacted into ``compact``'s leading axis; this kernel fuses
    selection and un-compaction into one pass over the designated buffers:
    UE ``u`` keeps its buffer (the cheap-expert baseline) when
    ``src[u] < 0`` — same single-tile no-op path as the scalar kernel — or
    receives row ``src[u]`` of the compact sub-batch otherwise (coalesced
    copy, tile-for-warp the paper's switch semantics with a gather
    indirection steering the DMA source).

    Args:
      src: ``(n_ues,)`` int32; ``src[u] >= 0`` is UE ``u``'s row in the
        compact sub-batch, ``src[u] < 0`` keeps the designated buffer.
      compact: ``(capacity, rows, cols)`` dense sub-batch of the gated
        expert's outputs (``capacity >= 1``; rows past the last selected UE
        are padding and must never be referenced by ``src``).
      designated: ``(n_ues, rows, cols)`` designated buffers holding the
        baseline expert's outputs (aliased to the output).

    Returns:
      ``(n_ues, rows, cols)`` array aliased onto ``designated``.
    """
    n_ues, rows, cols = designated.shape
    capacity = compact.shape[0]
    if compact.shape[1:] != (rows, cols):
        raise ValueError(f"compact {compact.shape} vs designated {designated.shape}")
    if capacity < 1:
        raise ValueError("capacity must be >= 1 (skip the kernel when 0)")
    if src.shape != (n_ues,):
        raise ValueError(f"src {src.shape} vs n_ues {n_ues}")
    block_rows = min(block_rows, rows)
    block_cols = min(block_cols, cols)
    if rows % block_rows or cols % block_cols:
        raise ValueError(
            f"shape ({rows},{cols}) not divisible by block "
            f"({block_rows},{block_cols}); use ops.switch_scatter for padding"
        )

    src = jnp.asarray(src, jnp.int32)
    grid = (n_ues, rows // block_rows, cols // block_cols)

    def _sel(src_ref, u, i, j):
        z = jnp.zeros_like(i)
        keep = src_ref[u] < 0
        return jnp.where(keep, z, i), jnp.where(keep, z, j)

    def compact_index(u, i, j, src_ref):
        k = jnp.maximum(src_ref[u], 0)
        bi, bj = _sel(src_ref, u, i, j)
        return (k, bi, bj)

    def des_index(u, i, j, src_ref):
        del i, j, src_ref
        return (u, 0, 0)

    def out_index(u, i, j, src_ref):
        bi, bj = _sel(src_ref, u, i, j)
        return (u, bi, bj)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_rows, block_cols), compact_index),
            pl.BlockSpec((1, block_rows, block_cols), des_index),
        ],
        out_specs=pl.BlockSpec((1, block_rows, block_cols), out_index),
    )

    return pl.pallas_call(
        _gather_kernel_batched,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_ues, rows, cols), designated.dtype),
        input_output_aliases={2: 0},
        interpret=interpret,
        name="switch_gather_batched",
    )(src, compact, designated)
