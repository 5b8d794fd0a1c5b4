"""Gray-coded QAM modulation and max-log LLR demapping (TS 38.211 5.1)."""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


def _gray_pam_levels(bits_per_axis: int) -> np.ndarray:
    """Gray-mapped PAM levels indexed by the per-axis bit group."""
    m = 1 << bits_per_axis
    # natural-order levels: -(m-1), ..., (m-1) step 2
    levels = np.arange(-(m - 1), m, 2, dtype=np.float64)
    out = np.zeros(m)
    for code in range(m):
        gray = code ^ (code >> 1)
        out[code] = levels[gray]
    return out


_NORM = {2: np.sqrt(2.0), 4: np.sqrt(10.0), 6: np.sqrt(42.0), 8: np.sqrt(170.0)}


def _table(qm: int) -> np.ndarray:
    """``constellation(qm)`` as a host array."""
    half = qm // 2
    pam = _gray_pam_levels(half)
    pts = np.zeros(1 << qm, np.complex128)
    for label in range(1 << qm):
        i_bits = label >> half
        q_bits = label & ((1 << half) - 1)
        pts[label] = pam[i_bits] + 1j * pam[q_bits]
    return (pts / _NORM[qm]).astype(np.complex64)


def constellation(qm: int) -> jax.Array:
    """All 2**qm points in bit-label order (MSB first, I bits then Q bits)."""
    return jnp.asarray(_table(qm))


@partial(jax.jit, static_argnames=("qm",))
def modulate(bits: jax.Array, qm: int) -> jax.Array:
    """(..., n*qm) bits in {0,1} -> (..., n) unit-energy QAM symbols."""
    shape = bits.shape[:-1]
    groups = bits.reshape(shape + (-1, qm))
    weights = jnp.asarray([1 << (qm - 1 - i) for i in range(qm)], jnp.int32)
    labels = jnp.sum(groups.astype(jnp.int32) * weights, axis=-1)
    half = qm // 2
    code_i, code_q = labels >> half, labels & ((1 << half) - 1)
    lev = _axis_levels(qm)
    return jax.lax.complex(
        _pick(lev, code_i ^ (code_i >> 1)), _pick(lev, code_q ^ (code_q >> 1))
    )


@partial(jax.jit, static_argnames=("qm",))
def demap_llr(y: jax.Array, noise_var: jax.Array, qm: int) -> jax.Array:
    """Max-log LLRs. ``y`` (..., n) equalized symbols -> (..., n*qm) LLRs.

    Positive LLR => bit 0 more likely (LLR = log P(b=0)/P(b=1)).
    """
    pts = constellation(qm)  # (M,)
    d2 = jnp.abs(y[..., None] - pts) ** 2  # (..., n, M)
    nv = jnp.maximum(jnp.asarray(noise_var), 1e-9)
    if nv.ndim:  # per-RE noise variance -> broadcast over constellation
        nv = nv[..., None]
    metric = -d2 / nv
    labels = np.arange(1 << qm)
    llrs = []
    for b in range(qm):
        bit = (labels >> (qm - 1 - b)) & 1
        m0 = jnp.max(jnp.where(jnp.asarray(bit == 0), metric, -jnp.inf), axis=-1)
        m1 = jnp.max(jnp.where(jnp.asarray(bit == 1), metric, -jnp.inf), axis=-1)
        llrs.append(m0 - m1)
    out = jnp.stack(llrs, axis=-1)  # (..., n, qm)
    return out.reshape(y.shape[:-1] + (-1,))


def hard_bits(llr: jax.Array) -> jax.Array:
    """LLR -> hard decisions (bit = 1 when LLR < 0)."""
    return (llr < 0).astype(jnp.uint8)


def _axis_levels(qm: int) -> np.ndarray:
    """The per-axis values of ``constellation(qm)`` in natural (ascending) order.

    Taken from the table itself, so they are bitwise its f32 components.
    """
    return np.unique(_table(qm).real)


def _pick(levels: np.ndarray, idx: jax.Array) -> jax.Array:
    """``levels[idx]`` for a static table of at most 16 values, as a select chain.

    XLA lowers a ``take`` from a table to a per-element gather, which on the
    TPU costs far more than these elementwise selects.
    """
    out = jnp.full(idx.shape, levels[0], jnp.float32)
    for k in range(1, len(levels)):
        out = jnp.where(idx >= k, levels[k], out)
    return out


@partial(jax.jit, static_argnames=("qm",))
def nearest_point(y: jax.Array, qm: int) -> jax.Array:
    """Nearest constellation point to each symbol in ``y``.

    Square Gray-mapped QAM factorizes: the closest point is the closest PAM
    level per I/Q axis, so this is O(1) per symbol instead of the O(2^qm)
    distance argmin — same point (up to measure-zero midpoint ties), built
    from the exact per-axis values of the ``constellation`` table without a
    gather.  Used by the batched engine's decision-directed EVM, which
    evaluates every supported modulation order each slot.
    """
    m = 1 << (qm // 2)
    lev = _axis_levels(qm)
    scaled = y * _NORM[qm]

    def level_idx(x):
        return jnp.clip(jnp.round((x + (m - 1)) / 2.0), 0, m - 1).astype(
            jnp.int32
        )

    return jax.lax.complex(
        _pick(lev, level_idx(jnp.real(scaled))),
        _pick(lev, level_idx(jnp.imag(scaled))),
    )
