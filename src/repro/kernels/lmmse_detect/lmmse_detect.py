"""Pallas TPU kernel: per-RE LMMSE detection of two uplink layers.

With two layers the receiver separates them at every resource element:
with the channel ``H`` (``n_ant x 2``, each column a layer's precoded
channel, linearly interpolated across symbols from its estimate at the
DMRS symbols) and the received vector ``y``,

    G = H^H H + s2 I,    x_hat = G^-1 H^H y

(unit-power symbols per layer, noise variance ``s2``).  ``G`` is a 2x2
Hermitian matrix, inverted in closed form.  Everything is f32 elementwise
work on the VPU; there is no matmul, so no bf16 pass touches the result.

Layout contract (planes, subcarriers on lanes):

* ``h_real``/``h_imag`` ``(U, n_ant, 2, n_dmrs_sym, n_sc)``;
* ``y_real``/``y_imag`` ``(U, n_ant, n_sym, n_sc)``;
* ``w`` ``(n_dmrs_sym, n_sym, 128)``: the time-interpolation weight of
  each DMRS symbol for each symbol, broadcast along the lanes;
* ``noise_var`` ``(U,)``, held in SMEM;
* outputs ``x_real``/``x_imag`` ``(U, 2, n_sym, n_sc)``;

with ``n_sc`` a multiple of 128 (``ops.py`` pads).  The grid runs over UEs
and blocks of subcarriers; inside a block the kernel walks 128-lane
chunks so that the per-RE accumulators stay in registers.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANE = 128
#: largest subcarrier block a grid step holds, in 128-lane chunks
MAX_CHUNKS = 16
N_LAYERS = 2


def _lmmse_kernel(nv_ref, w_ref, hr_ref, hi_ref, yr_ref, yi_ref,
                  xr_ref, xi_ref, *, n_ant, n_dmrs, chunks):
    nv = nv_ref[pl.program_id(0)]
    for c in range(chunks):
        sl = pl.ds(c * LANE, LANE)

        def interp(ref, a, l):
            """Channel of (antenna a, layer l) over the slot's symbols."""
            acc = None
            for d in range(n_dmrs):
                t = w_ref[d] * ref[0, a, l, pl.ds(d, 1), sl]
                acc = t if acc is None else acc + t
            return acc

        g = [None, None]  # diagonal of H^H H
        zr, zi = [None, None], [None, None]  # H^H y
        g01r = g01i = None  # off-diagonal h_0^H h_1
        add = lambda acc, v: v if acc is None else acc + v  # noqa: E731
        for a in range(n_ant):
            yr = yr_ref[0, a, :, sl]
            yi = yi_ref[0, a, :, sl]
            hr = [interp(hr_ref, a, l) for l in range(N_LAYERS)]
            hi = [interp(hi_ref, a, l) for l in range(N_LAYERS)]
            for l in range(N_LAYERS):
                g[l] = add(g[l], hr[l] * hr[l] + hi[l] * hi[l])
                zr[l] = add(zr[l], hr[l] * yr + hi[l] * yi)
                zi[l] = add(zi[l], hr[l] * yi - hi[l] * yr)
            g01r = add(g01r, hr[0] * hr[1] + hi[0] * hi[1])
            g01i = add(g01i, hr[0] * hi[1] - hi[0] * hr[1])

        a00 = g[0] + nv
        a11 = g[1] + nv
        inv = 1.0 / (a00 * a11 - (g01r * g01r + g01i * g01i))
        # x0 = (a11 z0 - g01 z1) / det;  x1 = (a00 z1 - conj(g01) z0) / det
        xr_ref[0, 0, :, sl] = (a11 * zr[0] - (g01r * zr[1] - g01i * zi[1])) * inv
        xi_ref[0, 0, :, sl] = (a11 * zi[0] - (g01r * zi[1] + g01i * zr[1])) * inv
        xr_ref[0, 1, :, sl] = (a00 * zr[1] - (g01r * zr[0] + g01i * zi[0])) * inv
        xi_ref[0, 1, :, sl] = (a00 * zi[1] - (g01r * zi[0] - g01i * zr[0])) * inv


def lmmse_detect_planes(
    h_real: jax.Array,
    h_imag: jax.Array,
    y_real: jax.Array,
    y_imag: jax.Array,
    w: jax.Array,
    noise_var: jax.Array,
    *,
    interpret: bool = False,
):
    """Per-RE LMMSE detection on f32 planes (see the module docstring).
    Returns ``(x_real, x_imag)``."""
    n_ues, n_ant, n_l, n_dmrs, n_sc = h_real.shape
    n_sym = y_real.shape[2]
    if n_l != N_LAYERS:
        raise ValueError(f"n_l={n_l}: the closed form covers {N_LAYERS} layers")
    if n_sc % LANE:
        raise ValueError(f"n_sc={n_sc} is not a multiple of {LANE}")
    if y_real.shape != (n_ues, n_ant, n_sym, n_sc):
        raise ValueError(f"y {y_real.shape} vs h {h_real.shape}")
    if w.shape != (n_dmrs, n_sym, LANE):
        raise ValueError(f"w {w.shape} vs ({n_dmrs}, {n_sym}, {LANE})")
    n_chunks = n_sc // LANE
    chunks = max(d for d in range(1, MAX_CHUNKS + 1) if n_chunks % d == 0)
    block = chunks * LANE
    grid = (n_ues, n_sc // block)

    h_spec = pl.BlockSpec((1, n_ant, n_l, n_dmrs, block),
                          lambda u, j: (u, 0, 0, 0, j))
    y_spec = pl.BlockSpec((1, n_ant, n_sym, block), lambda u, j: (u, 0, 0, j))
    w_spec = pl.BlockSpec((n_dmrs, n_sym, LANE), lambda u, j: (0, 0, 0))
    o_spec = pl.BlockSpec((1, n_l, n_sym, block), lambda u, j: (u, 0, 0, j))
    out = jax.ShapeDtypeStruct((n_ues, n_l, n_sym, n_sc), jnp.float32)
    kernel = functools.partial(_lmmse_kernel, n_ant=n_ant, n_dmrs=n_dmrs,
                               chunks=chunks)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), w_spec,
                  h_spec, h_spec, y_spec, y_spec],
        out_specs=[o_spec, o_spec],
        out_shape=[out, out],
        interpret=interpret,
        name="lmmse_detect",
    )(noise_var, w, h_real, h_imag, y_real, y_imag)
