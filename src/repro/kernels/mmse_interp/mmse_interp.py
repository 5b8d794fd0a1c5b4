"""Pallas TPU kernel: MMSE/Wiener frequency-domain interpolation (paper 5.1).

The MMSE channel estimator interpolates DMRS-position estimates across the
full band with a Wiener filter: ``H_full = W @ H_pilot`` where
``W = R_fp (R_pp + sigma^2 I)^{-1}`` is precomputed from the power-delay
profile approximation (Hung & Lin [16]).  On the GPU this is cuBB's
filtering kernel (~5.04 us, paper Fig. 8); on TPU the natural mapping is an
MXU matmul over the pilot dimension.

Complex arithmetic is expanded over real planes.  With ``use_gauss=True`` the
kernel uses the 3-multiplication Gauss trick::

    p1 = Hr @ Wr;  p2 = Hi @ Wi;  p3 = (Hr + Hi) @ (Wr + Wi)
    out_r = p1 - p2;  out_i = p3 - p1 - p2

trading one MXU pass for a few VPU adds (25% less MXU work than the naive
4-matmul expansion).

Layout contract: ``H`` is ``(B, Np)`` (batch of antenna x DMRS-symbol pilot
vectors), ``W`` is ``(Np, Nsc)``; ``B % block_b == 0``, ``Nsc % block_n == 0``
and ``Np`` is kept whole in VMEM (padded to a lane multiple by ops.py).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BLOCK_B = 8
DEFAULT_BLOCK_N = 512


def _mmse_interp_kernel(hr_ref, hi_ref, wr_ref, wi_ref, or_ref, oi_ref, *, use_gauss):
    hr = hr_ref[...]
    hi = hi_ref[...]
    wr = wr_ref[...]
    wi = wi_ref[...]
    if use_gauss:
        p1 = jnp.dot(hr, wr, preferred_element_type=jnp.float32)
        p2 = jnp.dot(hi, wi, preferred_element_type=jnp.float32)
        p3 = jnp.dot(hr + hi, wr + wi, preferred_element_type=jnp.float32)
        or_ref[...] = p1 - p2
        oi_ref[...] = p3 - p1 - p2
    else:
        or_ref[...] = jnp.dot(hr, wr, preferred_element_type=jnp.float32) - jnp.dot(
            hi, wi, preferred_element_type=jnp.float32
        )
        oi_ref[...] = jnp.dot(hr, wi, preferred_element_type=jnp.float32) + jnp.dot(
            hi, wr, preferred_element_type=jnp.float32
        )


def mmse_interp_2d(
    h_real: jax.Array,
    h_imag: jax.Array,
    w_real: jax.Array,
    w_imag: jax.Array,
    *,
    block_b: int = DEFAULT_BLOCK_B,
    block_n: int = DEFAULT_BLOCK_N,
    use_gauss: bool = True,
    interpret: bool = False,
):
    """Batched Wiener interpolation. Returns ``(out_real, out_imag)``."""
    b, np_ = h_real.shape
    np2, nsc = w_real.shape
    if np_ != np2:
        raise ValueError(f"pilot dims disagree: {np_} vs {np2}")
    block_b = min(block_b, b)
    block_n = min(block_n, nsc)
    if b % block_b or nsc % block_n:
        raise ValueError(f"({b},{nsc}) not divisible by ({block_b},{block_n})")

    grid = (b // block_b, nsc // block_n)
    h_spec = pl.BlockSpec((block_b, np_), lambda i, j: (i, 0))
    w_spec = pl.BlockSpec((np_, block_n), lambda i, j: (0, j))
    o_spec = pl.BlockSpec((block_b, block_n), lambda i, j: (i, j))

    import functools

    kernel = functools.partial(_mmse_interp_kernel, use_gauss=use_gauss)
    out_shape = jax.ShapeDtypeStruct((b, nsc), jnp.float32)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[h_spec, h_spec, w_spec, w_spec],
        out_specs=[o_spec, o_spec],
        out_shape=[out_shape, out_shape],
        interpret=interpret,
        name="mmse_interp",
    )(h_real, h_imag, w_real, w_imag)
