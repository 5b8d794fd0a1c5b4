"""Pure-jnp oracle for the per-RE LMMSE detection kernel: the textbook
form with a general matrix inverse."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def lmmse_detect_ref(
    h_dmrs: jax.Array,
    rx_grid: jax.Array,
    noise_var: jax.Array,
    time_weights: jax.Array,
) -> jax.Array:
    """Same arguments and result as ``ops.lmmse_detect``."""
    w = jnp.asarray(time_weights, jnp.float32).astype(h_dmrs.dtype)
    h = jnp.einsum("ualsd,md->usmal", h_dmrs, w)  # (U, sc, sym, ant, l)
    y = jnp.transpose(rx_grid, (0, 2, 3, 1))  # (U, sc, sym, ant)
    nv = jnp.asarray(noise_var, jnp.float32).reshape(-1, 1, 1, 1, 1)
    n_l = h.shape[-1]
    g = jnp.einsum("...al,...ak->...lk", jnp.conj(h), h) + nv * jnp.eye(n_l)
    x = jnp.einsum("...lk,...ak,...a->...l", jnp.linalg.inv(g), jnp.conj(h), y)
    return jnp.transpose(x, (0, 3, 1, 2)).astype(jnp.complex64)
