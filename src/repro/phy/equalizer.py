"""MMSE equalizer with time-domain interpolation (paper 5.1).

The estimator experts produce full-band estimates at the N_sym^DMRS pilot
symbols only; the equalizer (i) interpolates across all 14 OFDM symbols in
time — the division of labour the paper describes for Aerial — then
(ii) performs per-RE MRC/MMSE combining across receive antennas and
(iii) reports post-equalization SINR, which feeds the SNR KPM.  With two
layers, ``lmmse_equalize`` separates the layers per RE (LMMSE, the
``lmmse_detect`` kernel); one layer keeps ``mmse_equalize``.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.lmmse_detect import lmmse_detect
from repro.phy.nr import SlotConfig


def time_weights(cfg: SlotConfig) -> np.ndarray:
    """Piecewise-linear interpolation weights across OFDM symbols,
    (n_sym, n_dmrs_sym) float64: symbol ``m`` takes ``w[m] @ h_dmrs`` of
    its two neighbouring DMRS symbols (edge symbols clamped)."""
    sym = np.arange(cfg.n_sym, dtype=np.float64)
    anchors = np.asarray(cfg.dmrs_symbols, np.float64)
    w = np.zeros((cfg.n_sym, cfg.n_dmrs_sym))
    for i, s in enumerate(sym):
        j = int(np.clip(np.searchsorted(anchors, s) - 1, 0, len(anchors) - 2))
        t0, t1 = anchors[j], anchors[j + 1]
        a = np.clip((s - t0) / (t1 - t0), 0.0, 1.0)
        w[i, j] = 1.0 - a
        w[i, j + 1] = a
    return w


def time_interpolate(cfg: SlotConfig, h_dmrs: jax.Array) -> jax.Array:
    """Linear interpolation across OFDM symbols.

    ``h_dmrs`` (..., n_sc, n_dmrs_sym) at symbols ``cfg.dmrs_symbols``
    -> (..., n_sc, n_sym) over the whole slot (edge symbols clamped).
    """
    wj = jnp.asarray(time_weights(cfg), jnp.float32)
    return jnp.einsum("...sd,md->...sm", h_dmrs, wj.astype(h_dmrs.dtype))


@partial(jax.jit, static_argnames=("cfg",))
def mmse_equalize(
    cfg: SlotConfig,
    rx_grid: jax.Array,
    h_est_dmrs: jax.Array,
    noise_var: jax.Array,
) -> tuple[jax.Array, jax.Array]:
    """Equalize one slot.

    Args:
      rx_grid: (n_ant, n_sc, n_sym) received grid.
      h_est_dmrs: (n_ant, n_layers, n_sc, n_dmrs_sym) expert output.
      noise_var: scalar noise variance.

    Returns:
      ``(x_hat, sinr)`` — (n_sc, n_sym) equalized symbols for layer 0 and
      (n_sc, n_sym) per-RE post-equalization SINR (linear).
    """
    h = time_interpolate(cfg, h_est_dmrs)[:, 0]  # (ant, sc, sym)
    num = jnp.sum(jnp.conj(h) * rx_grid, axis=0)  # MRC combine
    den = jnp.sum(jnp.abs(h) ** 2, axis=0)  # (sc, sym)
    x_hat = num / (den + noise_var)
    # nominal post-MRC SINR assuming a perfect estimate; the pipeline layers
    # an EVM-based *measured* SINR on top (see pipeline._rx_slot), which is
    # what degrades when the estimate is bad
    sinr = den / jnp.maximum(noise_var, 1e-12)
    return x_hat, sinr


def lmmse_equalize(
    cfg: SlotConfig,
    rx_grid: jax.Array,
    h_est_dmrs: jax.Array,
    noise_var: jax.Array,
) -> jax.Array:
    """Per-RE LMMSE detection of two layers, for a batch of UEs (the
    ``lmmse_detect`` kernel, with this slot's interpolation across symbols).

    Args:
      rx_grid: (U, n_ant, n_sc, n_sym) received grids.
      h_est_dmrs: (U, n_ant, 2, n_sc, n_dmrs_sym) expert outputs.
      noise_var: (U,) noise variances.

    Returns:
      (U, 2, n_sc, n_sym) detected symbols.  With the second layer's
      channel zero, layer 0 is ``mmse_equalize``'s, up to f32 rounding.
    """
    return lmmse_detect(
        h_est_dmrs, rx_grid, noise_var,
        jnp.asarray(time_weights(cfg), jnp.float32),
    )


def effective_noise_var(sinr: jax.Array) -> jax.Array:
    """Per-RE effective noise variance for the LLR demapper (unit signal)."""
    return 1.0 / jnp.maximum(sinr, 1e-9)


@partial(jax.jit, static_argnames=("cfg", "prb_per_subband"))
def mmse_irc_equalize(
    cfg: SlotConfig,
    rx_grid: jax.Array,
    h_est_dmrs: jax.Array,
    pilots: jax.Array,
    noise_var: jax.Array,
    *,
    prb_per_subband: int = 2,
) -> tuple[jax.Array, jax.Array]:
    """MMSE-IRC: interference-rejection combining (Aerial's UL combiner).

    The interference-plus-noise covariance ``R`` is estimated per frequency
    subband from DMRS residuals ``e = rx_pilot - h_est * pilot`` — i.e. from
    whatever the *selected expert's* channel estimate leaves unexplained at
    the pilots.  The combiner ``w = R^{-1} h / (h^H R^{-1} h + 1)`` then
    spatially nulls in-band interference.  This is the stage where channel-
    estimate quality pays off under interference: a worse estimate leaks
    desired signal into ``e``, biasing ``R`` and mis-steering the null —
    exactly the coupling that makes the paper's AI expert win in *poor*
    conditions (paper 6.2).

    Args:
      rx_grid: (n_ant, n_sc, n_sym).
      h_est_dmrs: (n_ant, n_layers, n_sc, n_dmrs_sym) expert output.
      pilots: (n_dmrs_sym, n_pilot_sc) transmitted DMRS.
      noise_var: scalar thermal-noise variance (diagonal loading).
      prb_per_subband: covariance-averaging granularity (frequency-selective
        interference needs narrow subbands; estimation stability wants wide).

    Returns:
      ``(x_hat, sinr)`` — (n_sc, n_sym) layer-0 symbol estimates and per-RE
      post-IRC SINR ``h^H R^{-1} h`` (linear).
    """
    n_ant, n_sc = cfg.n_ant, cfg.n_sc
    h_full = time_interpolate(cfg, h_est_dmrs)[:, 0]  # (ant, sc, sym)

    # -- residuals at pilot REs --------------------------------------------------
    pilot_sc = jnp.asarray(cfg.pilot_sc_indices)
    dmrs_sym = jnp.asarray(cfg.dmrs_symbols)
    rx_p = rx_grid[:, pilot_sc][:, :, dmrs_sym]  # (ant, n_pilot, n_dmrs)
    h_p = h_full[:, pilot_sc][:, :, dmrs_sym]  # (ant, n_pilot, n_dmrs)
    e = rx_p - h_p * jnp.swapaxes(pilots, 0, 1)[None]  # (ant, n_pilot, n_dmrs)

    # -- per-subband covariance ---------------------------------------------------
    sb_pilots = prb_per_subband * 6  # comb-2: 6 pilots per PRB
    n_sb = cfg.n_pilot_sc // sb_pilots
    e_sb = e[:, : n_sb * sb_pilots].reshape(n_ant, n_sb, sb_pilots, -1)
    # R_sb: (n_sb, ant, ant), averaged over pilots x dmrs symbols
    r = jnp.einsum("aspd,bspd->sab", e_sb, jnp.conj(e_sb)) / (
        sb_pilots * cfg.n_dmrs_sym
    )
    r = r + (noise_var * 0.1 + 1e-6) * jnp.eye(n_ant, dtype=r.dtype)[None]

    # map every subcarrier to its subband
    sc_to_sb = jnp.clip(jnp.arange(n_sc) // (12 * prb_per_subband), 0, n_sb - 1)

    # -- IRC combine per RE ----------------------------------------------------------
    h_t = jnp.moveaxis(h_full, 0, -1)  # (sc, sym, ant)
    r_sc = r[sc_to_sb]  # (sc, ant, ant)
    rinv_h = jnp.linalg.solve(r_sc[:, None], h_t[..., None])[..., 0]  # (sc,sym,ant)
    hrh = jnp.real(jnp.sum(jnp.conj(h_t) * rinv_h, axis=-1))  # (sc, sym)
    rx_t = jnp.moveaxis(rx_grid, 0, -1)  # (sc, sym, ant)
    num = jnp.sum(jnp.conj(rinv_h) * rx_t, axis=-1)  # (R^-1 h)^H y
    x_hat = num / jnp.maximum(hrh, 1e-9)  # unbiased MMSE-IRC estimate
    sinr = hrh
    return x_hat, sinr
