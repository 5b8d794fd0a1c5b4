"""Plain reference of one two-layer uplink slot of one UE, independent of
the program.

The two-layer copy of ``reference.py``, in NumPy float64; it imports
nothing of ``repro`` and keeps the interface written in ``reference.py``'s
docstring (``SlotReference(config, traffic, seed, weights, precision)``,
``slot``, ``olla``, ``mcs_thresholds``).  What it shares with the one-layer
reference (the pilot sequence, the Wiener interpolator, the interpolation
across symbols, the MCS table and thresholds, the conditions, the draws'
keys, the quantized matrix products of a control, the outer loop) it
takes from there; what two layers change it computes here, from the
standards the configuration cites:

* the payload: ``bernoulli(k_tx, 0.5, (2, n_re * 8))``, layer ``l``'s row
  cut to ``n_re * q`` bits at modulation order ``q``, Gray-mapped;
* the DMRS of ports 0 and 1 of CDM group 0 (TS 38.211 6.4.1.1.3): the
  sequence times the frequency OCC ``w_f(k') = (+1, +1)`` and ``(+1, -1)``
  over pilot pairs ``k = 4n + 2k'``, on the comb of ``dmrs_comb_offset``,
  the other comb carrying data (one CDM group without data);
* TPMI 0 (TS 38.211 6.3.1.5): ``W = I / sqrt(2)`` on data and DMRS alike;
* the channel: the tapped delay line drawn for ``(n_ant, 2, taps)`` from
  one key and normalized to unit mean power over antennas, layers,
  subcarriers and symbols; the interferer's response drawn for the same
  shape, its layer 0 kept and normalized;
* LS per port: each pilot pair de-spread with the port's OCC, the pair's
  estimate given to both pilots;
* either expert on each (antenna, layer) port, the same expert for all
  of the UE's ports;
* detection: per RE, ``G = H^H H + s2 I`` and ``x = solve(G, H^H y)``, a
  general solve;
* one codeword over both layers: the TBS at ``v = 2`` layers (TS 38.214
  6.1.4.2), the decision-directed SNR over the data REs of both layers,
  the MIESM outcome over PRB-sized groups of each layer, and the RSRP as
  the mean ``|h|^2`` of the estimate over antennas, layers, subcarriers
  and DMRS symbols.

``fault`` plants a receiver fault in the reference, for the tests that
show the checks catch it: ``"mrc_layer0"`` (the one-layer receiver, MRC of
layer 0 alone, in place of the LMMSE), ``"swap_layers"`` (each layer
detected with the other's estimate) and ``"no_occ"`` (LS without the
OCC's de-spread).

This module computes two layers, and refuses a configuration with any
other number.
"""

from __future__ import annotations

import numpy as np

from bench.harness import reference as base
from bench.harness.reference import MCS_TABLE, N_SYM

N_LAYERS = 2
FAULTS = (None, "mrc_layer0", "swap_layers", "no_occ")


class SlotReference(base.SlotReference):
    def __init__(self, config: dict, traffic: dict, seed: int, weights: dict,
                 precision: str = "f64", *, fault: str | None = None):
        if config["n_layers"] != N_LAYERS:
            raise ValueError(
                f"n_layers={config['n_layers']}: this reference computes "
                f"{N_LAYERS} layers")
        if fault not in FAULTS:
            raise ValueError(f"fault {fault!r}; one of {FAULTS}")
        # the base's fields do not depend on the layers; its own check
        # refuses every configuration of more than one
        super().__init__(dict(config, n_layers=1), traffic, seed, weights,
                         precision)
        self.c = config
        self.fault = fault
        #: OCC of ports 0 and 1 at every pilot: pilot m is k' = m mod 2
        self.occ = np.stack([(-1.0) ** (p * (np.arange(self.n_p) % 2))
                             for p in range(N_LAYERS)])

    def tbs(self, mcs: int) -> int:
        q, r = MCS_TABLE[mcs]
        n_info = self.n_re * q * (r / 1024) * N_LAYERS
        return int(max(24, np.floor(n_info / 8.0) * 8 - 24))

    def _response(self, key) -> np.ndarray:
        """(n_ant, 2, n_sc, 14) tapped-delay-line response from one key,
        not normalized."""
        import jax

        prof = self.c["tdl_profile"]
        p = 10.0 ** (np.asarray(prof["powers_db"]) / 10.0)
        amps = np.sqrt(p / p.sum())
        k_init, k_evo = jax.random.split(key)
        shape0 = (self.n_ant, N_LAYERS, len(amps))
        g = self._cnormal(k_init, shape0)
        innov = self._cnormal(k_evo, (N_SYM,) + shape0)
        x = 2 * np.pi * prof["doppler_hz"] * self.slot_s / N_SYM
        rho = float(np.clip(1.0 - x * x / 4.0, 0.0, 1.0))
        g_t = []
        for m in range(N_SYM):
            g = rho * g + np.sqrt(1 - rho * rho) * innov[m]
            g_t.append(g)
        g_t = np.stack(g_t, -1) * amps[None, None, :, None]  # (A, L, T, 14)
        f = np.arange(self.n_sc) * self.c["scs_khz"] * 1e3
        steer = np.exp(-2j * np.pi * f[:, None]
                       * np.asarray(prof["delays_s"])[None, :])
        return np.einsum("st,altm->alsm", steer, g_t)

    @staticmethod
    def _unit(h: np.ndarray) -> np.ndarray:
        return h / np.sqrt(np.mean(np.abs(h) ** 2) + 1e-12)

    def transmit(self, k_tx, mcs: int):
        """Both layers' symbols (2, n_re) and the precoded grid
        (2, n_sc, 14)."""
        import jax

        q = MCS_TABLE[mcs][0]
        bits = np.asarray(jax.random.bernoulli(
            k_tx, 0.5, (N_LAYERS, self.n_re * 8)), np.int64)
        half, m = q // 2, 1 << (q // 2)
        w = 1 << np.arange(half - 1, -1, -1)
        gray = lambda c: c ^ (c >> 1)  # noqa: E731
        lev = lambda c: 2.0 * gray(c) - (m - 1)  # noqa: E731
        syms, grid = [], np.zeros((N_LAYERS, self.n_sc, N_SYM), np.complex128)
        for layer in range(N_LAYERS):
            b = bits[layer, : self.n_re * q].reshape(-1, q)
            i_code, q_code = b[:, :half] @ w, b[:, half:] @ w
            s = (lev(i_code) + 1j * lev(q_code)) / np.sqrt(
                2 * ((1 << q) - 1) / 3)
            syms.append(s)
            grid[layer].reshape(-1)[
                np.flatnonzero(self.data_mask.reshape(-1))] = s
            for i, sym in enumerate(self.dmrs):
                grid[layer, self.pilot_sc, sym] = (self.pilots[i]
                                                   * self.occ[layer])
        return np.stack(syms), grid / np.sqrt(N_LAYERS)

    def receive(self, k_ch, k_n, grid, cond):
        import jax

        nv = 10.0 ** (-cond["snr_db"] / 10.0)
        k_h, k_i, k_hi = jax.random.split(k_ch, 3)
        h = self._unit(self._response(k_h))
        y = np.einsum("alsm,lsm->asm", h, grid)
        if cond["interference"]:
            start = int(round(cond["interference_prb_start"] * self.c["n_prb"]))
            n_hit = int(round(cond["interference_prb_frac"] * self.c["n_prb"]))
            sc = np.zeros(self.n_sc)
            sc[start * 12: min((start + n_hit) * 12, self.n_sc)] = 1.0
            duty = cond["interference_symbol_duty"]
            u = np.asarray(jax.random.uniform(jax.random.fold_in(k_i, 7),
                                              (N_SYM,)), np.float64)
            if duty >= 1.0:
                sym_mask = np.ones(N_SYM)
            elif cond["dmrs_collision"]:
                dm = np.zeros(N_SYM)
                dm[list(self.dmrs)] = 1.0
                p_rest = max(duty * N_SYM - len(self.dmrs), 0.0) / (
                    N_SYM - len(self.dmrs))
                sym_mask = np.maximum(dm, (u < np.float32(p_rest)) * 1.0)
            else:
                sym_mask = (u < np.float32(duty)) * 1.0
            hi = self._unit(self._response(k_hi)[:, 0])
            sym = self._cnormal(k_i, (self.n_sc, N_SYM))
            amp = np.sqrt(nv * 10.0 ** (cond["inr_db"] / 10.0))
            y = y + amp * hi * (sc[:, None] * sym_mask[None, :] * sym)[None]
        y = y + np.sqrt(nv) * self._cnormal(k_n, y.shape)
        return y, nv

    def ls(self, y):
        """(A, 2, S, Np): each port's pair-wise de-spread LS estimate."""
        raw = super().ls(y)  # (A, S, Np)
        if self.fault == "no_occ":
            return np.stack([raw] * N_LAYERS, axis=1)
        pairs = (raw[:, None] * self.occ[None, :, None, :]).reshape(
            self.n_ant, N_LAYERS, len(self.dmrs), -1, 2)
        return np.repeat(pairs.mean(-1), 2, axis=-1)

    def mmse(self, h_ls):
        """(P, S, Np) -> (P, Nsc, S), one Wiener interpolation a port."""
        n = h_ls.shape[0]
        out = self._cmatmul(h_ls.reshape(-1, self.n_p), self.wiener.T)
        return np.moveaxis(out.reshape(n, len(self.dmrs), -1), -1, -2)

    def ai(self, h_ls):
        """(P, S, Np) -> (P, Nsc, S): the residual CNN on each port (the
        one-layer reference's, ``n_ant`` ports at a time)."""
        return np.concatenate([super(SlotReference, self).ai(
            h_ls[i:i + self.n_ant]) for i in range(0, len(h_ls), self.n_ant)])

    def detect(self, h_t, y, nv):
        """Per RE ``solve(H^H H + s2 I, H^H y)``: h_t (A, 2, Nsc, 14),
        y (A, Nsc, 14) -> (2, Nsc, 14)."""
        if self.fault == "mrc_layer0":
            h0 = h_t[:, 0]
            return (np.sum(np.conj(h0) * y, 0)
                    / (np.sum(np.abs(h0) ** 2, 0) + nv))[None]
        if self.fault == "swap_layers":
            h_t = h_t[:, ::-1]
        hm = np.moveaxis(h_t, (0, 1), (-2, -1))  # (Nsc, 14, A, 2)
        g = np.einsum("...al,...ak->...lk", np.conj(hm), hm) + nv * np.eye(
            N_LAYERS)
        z = np.einsum("...al,...a->...l", np.conj(hm), np.moveaxis(y, 0, -1))
        return np.moveaxis(np.linalg.solve(g, z[..., None])[..., 0], -1, 0)

    def slot(self, slot: int, ue: int, reported_snr_db: float,
             olla_db: float, served_by_ai: bool) -> dict:
        """Everything the step reports for one UE in one slot."""
        import jax

        L = self.link
        k_tx, k_ch, k_n, k_crc = self._keys(slot, ue)
        snr_in = reported_snr_db + olla_db
        mcs = self.mcs(snr_in)
        q, r = MCS_TABLE[mcs]
        syms, grid = self.transmit(k_tx, mcs)
        y, nv = self.receive(k_ch, k_n, grid, self.condition(slot))
        h_ls = self.ls(y).reshape(-1, len(self.dmrs), self.n_p)
        h = self.ai(h_ls) if served_by_ai else self.mmse(h_ls)  # (P,Nsc,S)
        h = h.reshape(self.n_ant, N_LAYERS, self.n_sc, -1)
        x_hat = self.detect(h @ self.time_weights.T, y, nv)  # (l, Nsc, 14)
        idx = np.flatnonzero(self.data_mask.reshape(-1))
        d = x_hat.reshape(len(x_hat), -1)[:, idx]  # (l, n_re)
        m = 1 << (q // 2)
        norm = np.sqrt(2 * ((1 << q) - 1) / 3)
        lvl = lambda v: np.clip(np.round((v * norm + (m - 1)) / 2), 0,  # noqa
                                m - 1)
        near = ((2 * lvl(d.real) - (m - 1)) + 1j * (2 * lvl(d.imag) - (m - 1))
                ) / norm
        sinr = np.mean(np.abs(near) ** 2) / max(
            np.mean(np.abs(d - near) ** 2), 1e-9)
        err = np.abs(d - syms[: len(d)]) ** 2
        g = L["genie_group_re"]
        n = err.shape[1] - err.shape[1] % g
        genie = 1.0 / np.maximum(
            err[:, :n].reshape(len(d), -1, g).mean(-1).reshape(-1), 1e-9)
        cap = np.log2(1 + genie / L["mi_gap"])
        beta = L["mi_softmin_beta"]
        mi = -np.logaddexp(-beta * cap, -beta * q) / beta
        margin = np.mean(mi) / q - (r / 1024 + L["tb_margin"])
        p_ok = 1.0 / (1.0 + np.exp(-L["tb_slope"] * margin))
        u = float(np.asarray(jax.random.uniform(k_crc, ())))
        return {
            "mcs": mcs, "mcs_input_db": snr_in, "tbs": self.tbs(mcs),
            "snr": 10 * np.log10(sinr + 1e-9),
            "rsrp": float(np.mean(np.abs(h) ** 2)),
            "tb_ok": bool(u < p_ok), "tb_p": float(p_ok), "tb_u": u,
        }
