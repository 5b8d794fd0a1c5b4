"""Channel estimators: LS and MMSE/Wiener with PDP approximation (paper 5.1-5.2).

Expert A (conventional, fail-safe default) is the MMSE estimator native to
the Aerial PUSCH pipeline: DMRS-based LS at pilot positions followed by
frequency-domain Wiener interpolation built from a power-delay-profile
approximation (paper ref [16]).  Time-domain interpolation across OFDM
symbols is deliberately NOT performed here — the paper notes Aerial leaves
it to the equalizer (5.1), and so do we.

The Wiener matmul is the estimator's compute hot-spot and runs through the
Pallas ``mmse_interp`` kernel (MXU path); the pure-jnp reference is used by
the tests.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.mmse_interp import mmse_interp, mmse_interp_ref
from repro.phy import dmrs as dmrs_mod
from repro.phy.nr import SlotConfig


def ls_estimate(
    cfg: SlotConfig, rx_grid: jax.Array, pilots: jax.Array
) -> jax.Array:
    """Least-squares estimates at DMRS REs.

    ``rx_grid`` (n_ant, n_sc, n_sym), ``pilots`` (n_dmrs_sym, n_pilot_sc)
    -> (n_ant, n_dmrs_sym, n_pilot_sc).

    With more than one layer, -> (n_ant, n_layers, n_dmrs_sym, n_pilot_sc):
    each pilot pair ``(2m, 2m+1)`` of the comb is de-spread with each
    port's OCC (``dmrs.port_occ``), and the pair's estimate is given to
    both of its pilots, so each port keeps the comb's shape.  A port's
    estimate is of its precoded channel (TPMI 0's ``1 / sqrt(n_layers)``
    included), the channel its layer's unit-power symbols see.
    """
    rx_pilots = dmrs_mod.extract_pilot_re(cfg, rx_grid)
    h = rx_pilots * jnp.conj(pilots) / (jnp.abs(pilots) ** 2 + 1e-12)
    if cfg.n_layers == 1:
        return h
    occ = dmrs_mod.port_occ(cfg).astype(np.float32)  # (n_l, n_pilot)
    pairs = (h[..., None, :, :] * occ[:, None, :]).reshape(
        h.shape[:-2] + (cfg.n_layers, cfg.n_dmrs_sym, -1, 2)
    )
    return jnp.repeat(jnp.mean(pairs, axis=-1), 2, axis=-1)


def exponential_pdp_correlation(
    cfg: SlotConfig, rms_delay_spread_s: float
) -> np.ndarray:
    """Frequency-correlation r(dk) for an exponential PDP approximation.

    r(delta_f) = 1 / (1 + j 2 pi tau_rms delta_f)  (paper ref [16]).
    Returns the (n_sc, n_sc) correlation matrix (host-side, cached per cfg).
    """
    df = cfg.scs_khz * 1e3
    k = np.arange(cfg.n_sc)
    dk = (k[:, None] - k[None, :]) * df
    return 1.0 / (1.0 + 2j * np.pi * rms_delay_spread_s * dk)


@dataclasses.dataclass(frozen=True)
class WienerInterpolator:
    """Precomputed W = R_fp (R_pp + sigma^2 I)^-1, pilot -> full band."""

    w: jax.Array  # (n_pilot_sc, n_sc) complex64 — matches kernel layout

    @classmethod
    def build(
        cls,
        cfg: SlotConfig,
        *,
        rms_delay_spread_s: float = 100e-9,
        noise_var: float = 1e-2,
    ) -> "WienerInterpolator":
        r = exponential_pdp_correlation(cfg, rms_delay_spread_s)
        p = cfg.pilot_sc_indices
        r_fp = r[:, p]  # (n_sc, n_pilot)
        r_pp = r[np.ix_(p, p)]  # (n_pilot, n_pilot)
        w = r_fp @ np.linalg.inv(r_pp + noise_var * np.eye(len(p)))
        return cls(w=jnp.asarray(w.T, jnp.complex64))  # (n_pilot, n_sc)


def mmse_estimate(
    cfg: SlotConfig,
    rx_grid: jax.Array,
    pilots: jax.Array,
    interpolator: WienerInterpolator,
    *,
    use_kernel: bool = True,
) -> jax.Array:
    """Expert A: LS at pilots + Wiener frequency interpolation.

    Returns hat{H}_MMSE (n_ant, n_layers, n_sc, n_dmrs_sym) — estimates at
    the N_sym^DMRS pilot symbols, full band (paper 4.1).
    """
    h_ls = ls_estimate(cfg, rx_grid, pilots)  # (ant, dmrs_sym, pilot_sc)
    interp = mmse_interp if use_kernel else mmse_interp_ref
    h_full = interp(h_ls, interpolator.w)  # (ant, dmrs_sym, n_sc)
    return jnp.moveaxis(h_full, -2, -1)[:, None]  # (ant, 1, n_sc, dmrs_sym)


def estimator_flops(cfg: SlotConfig) -> float:
    """Complex-matmul FLOPs for the Wiener interpolation (cost model): one
    interpolation per receive antenna and layer."""
    b = cfg.n_ant * cfg.n_layers * cfg.n_dmrs_sym
    return 8.0 * b * cfg.n_pilot_sc * cfg.n_sc
