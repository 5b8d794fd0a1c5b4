"""Jit'd public wrapper for the per-RE LMMSE detection kernel.

Takes the complex estimate at the DMRS symbols and the received grid in
the slot's own layout, moves subcarriers onto lanes (padded to a lane
multiple; a zero channel and zero samples detect to zero), and dispatches
to the Pallas kernel (interpret mode off-TPU).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.lmmse_detect import lmmse_detect as _k
from repro.kernels.switch_select.ops import _use_interpret


@functools.partial(jax.jit, static_argnames=("interpret",))
def lmmse_detect(
    h_dmrs: jax.Array,
    rx_grid: jax.Array,
    noise_var: jax.Array,
    time_weights: jax.Array,
    *,
    interpret: bool | None = None,
) -> jax.Array:
    """Per-RE LMMSE detection of both layers of every UE.

    Args:
      h_dmrs: complex ``(U, n_ant, 2, n_sc, n_dmrs_sym)`` selected
        estimate at the DMRS symbols.
      rx_grid: complex ``(U, n_ant, n_sc, n_sym)`` received grid.
      noise_var: ``(U,)`` noise variance.
      time_weights: ``(n_sym, n_dmrs_sym)`` linear interpolation weights
        across symbols (``repro.phy.equalizer.time_weights``).

    Returns:
      ``x_hat``: complex64 ``(U, 2, n_sc, n_sym)``.
    """
    if interpret is None:
        interpret = _use_interpret()
    n_sc = h_dmrs.shape[3]
    pad = (-n_sc) % _k.LANE

    def lanes(x, perm):
        x = jnp.transpose(x, perm)
        return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])

    h = lanes(h_dmrs, (0, 1, 2, 4, 3))
    y = lanes(rx_grid, (0, 1, 3, 2))
    w = jnp.broadcast_to(
        jnp.asarray(time_weights, jnp.float32).T[:, :, None],
        time_weights.shape[::-1] + (_k.LANE,),
    )
    xr, xi = _k.lmmse_detect_planes(
        jnp.real(h).astype(jnp.float32), jnp.imag(h).astype(jnp.float32),
        jnp.real(y).astype(jnp.float32), jnp.imag(y).astype(jnp.float32),
        w, jnp.asarray(noise_var, jnp.float32).reshape(-1),
        interpret=interpret,
    )
    back = lambda x: jnp.transpose(x[..., :n_sc], (0, 1, 3, 2))  # noqa: E731
    return (back(xr) + 1j * back(xi)).astype(jnp.complex64)
