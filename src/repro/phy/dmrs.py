"""DMRS generation and resource-grid mapping (paper 5.1, Fig. 6).

Type-1 DMRS with interleaved frequency-domain placement (comb-2) on OFDM
symbols {0, 5, 10}.  Sequences are QPSK symbols from a Gold-sequence
pseudo-random generator (TS 38.211 7.4.1.1 style, simplified init).

Two layers take DMRS ports 0 and 1 of CDM group 0 (TS 38.211 6.4.1.1.3):
both on the same comb, told apart by the frequency OCC ``w_f(k')``,
``(+1, +1)`` for port 0 and ``(+1, -1)`` for port 1, over each pilot pair
``k = 4n + 2k'``.  The other comb keeps carrying data (one CDM group
without data).  Both layers are precoded with TPMI 0, ``W = I / sqrt(2)``
(TS 38.211 6.3.1.5), data and DMRS alike, so the two layers together
transmit the power of one.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.phy.nr import SlotConfig


def _gold_sequence(c_init: int, length: int) -> np.ndarray:
    """TS 38.211 5.2.1 length-31 Gold sequence (host-side, cached)."""
    nc = 1600
    x1 = np.zeros(nc + length + 31, np.int8)
    x2 = np.zeros(nc + length + 31, np.int8)
    x1[0] = 1
    for i in range(31):
        x2[i] = (c_init >> i) & 1
    for n in range(len(x1) - 31):
        x1[n + 31] = (x1[n + 3] + x1[n]) % 2
        x2[n + 31] = (x2[n + 3] + x2[n + 2] + x2[n + 1] + x2[n]) % 2
    return ((x1[nc : nc + length] + x2[nc : nc + length]) % 2).astype(np.int8)


def dmrs_sequence(cfg: SlotConfig, *, slot: int = 0, cell_id: int = 42) -> jax.Array:
    """QPSK DMRS symbols, (n_dmrs_sym, n_pilot_sc) complex64."""
    seqs = []
    for sym in cfg.dmrs_symbols:
        c_init = ((14 * slot + sym + 1) * (2 * cell_id + 1) * 2**17 + 2 * cell_id) % (
            2**31
        )
        bits = _gold_sequence(int(c_init), 2 * cfg.n_pilot_sc).astype(np.float32)
        re = (1.0 - 2.0 * bits[0::2]) / np.sqrt(2.0)
        im = (1.0 - 2.0 * bits[1::2]) / np.sqrt(2.0)
        seqs.append(re + 1j * im)
    return jnp.asarray(np.stack(seqs), jnp.complex64)


def port_occ(cfg: SlotConfig) -> np.ndarray:
    """Frequency OCC ``w_f(k')`` of DMRS ports 0..n_layers-1 at every pilot
    of the comb, (n_layers, n_pilot_sc) in {+1, -1}: pilot ``m`` is ``k' =
    m mod 2`` of its pair, and port ``p`` weighs it by ``(-1)^(p k')``."""
    k_prime = np.arange(cfg.n_pilot_sc) % 2
    return np.stack([(-1.0) ** (p * k_prime) for p in range(cfg.n_layers)])


@partial(jax.jit, static_argnames=("cfg",))
def map_slot_grid(
    cfg: SlotConfig, data_symbols: jax.Array, pilots: jax.Array
) -> jax.Array:
    """Assemble the TX resource grid (n_layers, n_sc, n_sym).

    ``data_symbols`` is a flat (n_data_re,) complex vector in grid scan
    order, (n_layers, n_data_re) with more than one layer; ``pilots`` is
    the (n_dmrs_sym, n_pilot_sc) sequence.  Layer ``l`` carries port
    ``l``'s DMRS (the sequence times its OCC, ``port_occ``), and with more
    than one layer every layer is scaled by ``1 / sqrt(n_layers)`` (TPMI 0).
    """
    grid = jnp.zeros((cfg.n_layers, cfg.n_sc, cfg.n_sym), jnp.complex64)
    # data placement mask (True where PUSCH data lives)
    mask = np.ones((cfg.n_sc, cfg.n_sym), bool)
    for i, sym in enumerate(cfg.dmrs_symbols):
        mask[cfg.pilot_sc_indices, sym] = False
    mask_j = jnp.asarray(mask)
    flat_idx = jnp.cumsum(mask_j.reshape(-1).astype(jnp.int32)) - 1
    if cfg.n_layers == 1:
        layers = [(data_symbols, pilots)]
    else:
        occ = port_occ(cfg).astype(np.float32)
        layers = [(data_symbols[l], pilots * occ[l]) for l in range(cfg.n_layers)]
    for l, (data, port_pilots) in enumerate(layers):
        data_grid = jnp.where(
            mask_j.reshape(-1),
            jnp.take(data, jnp.clip(flat_idx, 0, data.shape[0] - 1)),
            0.0,
        ).reshape(cfg.n_sc, cfg.n_sym)
        grid = grid.at[l].set(data_grid)
        for i, sym in enumerate(cfg.dmrs_symbols):
            grid = grid.at[l, jnp.asarray(cfg.pilot_sc_indices), sym].set(
                port_pilots[i]
            )
    if cfg.n_layers > 1:
        grid = grid * np.float32(1.0 / np.sqrt(cfg.n_layers))
    return grid


def extract_data_re(cfg: SlotConfig, grid: jax.Array) -> jax.Array:
    """Inverse of the data mapping: (..., n_sc, n_sym) -> (..., n_data_re)."""
    mask = np.ones((cfg.n_sc, cfg.n_sym), bool)
    for sym in cfg.dmrs_symbols:
        mask[cfg.pilot_sc_indices, sym] = False
    flat = grid.reshape(grid.shape[:-2] + (-1,))
    idx = jnp.asarray(np.nonzero(mask.reshape(-1))[0])
    return jnp.take(flat, idx, axis=-1)


def extract_pilot_re(cfg: SlotConfig, grid: jax.Array) -> jax.Array:
    """RX samples at DMRS REs: (..., n_sc, n_sym) -> (..., n_dmrs_sym, n_pilot_sc)."""
    cols = []
    pilot_idx = jnp.asarray(cfg.pilot_sc_indices)
    for sym in cfg.dmrs_symbols:
        cols.append(jnp.take(grid[..., sym], pilot_idx, axis=-1))
    return jnp.stack(cols, axis=-2)
