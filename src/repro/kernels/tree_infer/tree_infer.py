"""Pallas TPU kernel: vectorized decision-tree inference (paper 5.3, Fig. 8).

The ARCHES switching policy is a depth-2 decision tree whose inference must
stay sub-microsecond (0.41 us on the GH200).  A pointer-chasing tree walk is
hostile to the TPU's vector units, so the kernel re-expresses the complete
binary tree as dense linear algebra that the MXU/VPU execute in one pass:

  proj  = X @ T                    (one-hot feature gather as a matmul)
  D     = proj > thresholds        (all node decisions at once)
  count = D @ (on*dir)^T + (1-D) @ (on*(1-dir))^T
  match = count == n_on            (leaf indicator: every on-path node agrees)
  out   = match * leaf_values      (reduced by the wrapper)

where ``on[l, n]`` marks internal node ``n`` on the root-to-leaf-``l`` path
and ``dir[l, n]`` the branch direction that path takes.  This evaluates every
slot's KPM vector against the whole tree with two small matmuls — the TPU
analogue of the paper's "sub-microsecond decision inference".

Layout contract: all dims padded to lane/sublane multiples by ops.py; padded
leaves carry ``n_on = -1`` so they can never match.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BLOCK_B = 256


def _tree_kernel(x_ref, t_ref, thr_ref, a_ref, b_ref, non_ref, leaf_ref, out_ref):
    x = x_ref[...]
    # the one-hot gather must reproduce x bit for bit, so it asks for full
    # f32 contraction; the 0/1 count matmuls below are exact at any precision
    proj = jnp.dot(
        x, t_ref[...], preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )
    d = (proj > thr_ref[...]).astype(jnp.float32)
    count = jnp.dot(d, a_ref[...], preferred_element_type=jnp.float32) + jnp.dot(
        1.0 - d, b_ref[...], preferred_element_type=jnp.float32
    )
    match = (count == non_ref[...]).astype(jnp.float32)
    out_ref[...] = match * leaf_ref[...]


def tree_infer_2d(
    x: jax.Array,
    t: jax.Array,
    thr: jax.Array,
    a: jax.Array,
    b: jax.Array,
    n_on: jax.Array,
    leaf_vals: jax.Array,
    *,
    block_b: int = DEFAULT_BLOCK_B,
    interpret: bool = False,
) -> jax.Array:
    """Returns per-leaf scores ``(B, Nl)``; row-sum gives the prediction."""
    bsz, f = x.shape
    nn = t.shape[1]
    nl = a.shape[1]
    block_b = min(block_b, bsz)
    if bsz % block_b:
        raise ValueError(f"batch {bsz} not divisible by block {block_b}")

    grid = (bsz // block_b,)
    return pl.pallas_call(
        _tree_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_b, f), lambda i: (i, 0)),
            pl.BlockSpec((f, nn), lambda i: (0, 0)),
            pl.BlockSpec((1, nn), lambda i: (0, 0)),
            pl.BlockSpec((nn, nl), lambda i: (0, 0)),
            pl.BlockSpec((nn, nl), lambda i: (0, 0)),
            pl.BlockSpec((1, nl), lambda i: (0, 0)),
            pl.BlockSpec((1, nl), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_b, nl), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((bsz, nl), jnp.float32),
        interpret=interpret,
        name="tree_infer",
    )(x, t, thr, a, b, n_on, leaf_vals)
