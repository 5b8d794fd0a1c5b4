"""Plain reference of one uplink slot of one UE, independent of the program.

Written from the configuration and traffic files and the standards they
cite, in NumPy float64; it imports nothing of ``repro``.  Only the random
draws use ``jax.random``, with the key schedule the benchmark hands the
program: UE ``u`` in slot ``s`` draws from ``fold_in(fold_in(seed_key,
u), s)`` split four ways into payload bits, channel, noise and the
transport block's decoding draw.  The draws are made on JAX's default
device, the chip in a benchmark run: a normal sample's inverse-error-
function transform rounds differently on another backend.

One call reproduces, for one slot of one UE: link adaptation (MCS from the
reported SNR and the outer-loop offset, TS 38.214 Table 5.1.3.1-2), the
Gray-mapped QAM payload and type-1 comb-2 DMRS (TS 38.211 5.1, 5.2.1,
7.4.1.1), the tapped-delay-line channel with AR(1) time evolution, the
neighbour-cell interference and AWGN, least squares at the pilots, the
selected expert (Wiener interpolation ``W = R_fp (R_pp + s2 I)^-1`` from an
exponential power-delay profile, or the residual CNN with a comb-2
baseline and a sub-pixel upsample), linear interpolation across symbols,
MRC/MMSE combining, the decision-directed SINR and RSRP the policy reads,
and the transport block's MIESM outcome.

``precision="int8"`` is the control (``"fp8"`` a second reading): every
operand of the estimators' matrix products (LS input and Wiener matrix;
activations and weights of every convolution) is rounded to symmetric int8,
or to float8 e4m3, with one scale per tensor, and the products accumulate
exactly.

**The interface of a reference.**  A configuration names its reference by
the optional key ``"reference"``: a module of ``bench.harness``, this one
(``"reference"``) where the key is absent.  ``reference_for`` resolves it;
the checks (``correct.py``) and the control (``bench/control.py``) take the
class from there alone.  The module exposes ``SlotReference``, which keeps
to this interface:

* ``SlotReference(config, traffic, seed, weights, precision="f64")``:
  the configuration and traffic as loaded from their files, the run's
  seed, a host copy of the AI expert's parameter tree, and ``"f64"`` or a
  control's precision (``"int8"``, ``"fp8"``).  It raises where the
  configuration states what it does not compute;
* ``slot(slot, ue, reported_snr_db, olla_db, served_by_ai)``: one slot
  of one UE, as a dict with ``mcs``, ``mcs_input_db``, ``tbs``, ``snr``
  (dB), ``rsrp``, ``tb_ok``, ``tb_p`` and ``tb_u`` (the transport block's
  outcome, its probability and its draw);
* ``olla(tb_ok_history)``: the outer-loop offset (dB) after those outcomes;
* ``mcs_thresholds()``: the SNR (dB) each MCS needs, in MCS order.

This module computes one layer, and refuses a configuration with more.
"""

from __future__ import annotations

import functools
import importlib

import numpy as np

#: TS 38.214 Table 5.1.3.1-2 (256QAM MCS table), entries 0..27:
#: (modulation order, target code rate x 1024)
MCS_TABLE = (
    (2, 120), (2, 193), (2, 308), (2, 449), (2, 602), (4, 378), (4, 434),
    (4, 490), (4, 553), (4, 616), (4, 658), (6, 466), (6, 517), (6, 567),
    (6, 616), (6, 666), (6, 719), (6, 772), (6, 822), (6, 873), (8, 682.5),
    (8, 711), (8, 754), (8, 797), (8, 841), (8, 885), (8, 916.5), (8, 948),
)
N_SYM = 14


def _quantize(x: np.ndarray, precision: str) -> np.ndarray:
    """Round to float8 e4m3 (max -> 448) or symmetric int8 (max -> 127),
    one scale per tensor; ``f64`` leaves ``x`` as it is."""
    if precision == "f64":
        return x
    import ml_dtypes

    x = np.asarray(x, np.float64)
    amax = float(np.max(np.abs(x)))
    if amax == 0.0:
        return x
    if precision == "int8":
        scale = amax / 127.0
        return np.clip(np.round(x / scale), -127, 127) * scale
    scale = amax / 448.0
    q = (x / scale).astype(ml_dtypes.float8_e4m3fn).astype(np.float64)
    return q * scale


def _gold(c_init: int, length: int) -> np.ndarray:
    nc = 1600
    n = nc + length
    x1 = np.zeros(n + 31, np.int64)
    x2 = np.zeros(n + 31, np.int64)
    x1[0] = 1
    x2[:31] = [(c_init >> i) & 1 for i in range(31)]
    for i in range(n):
        x1[i + 31] = (x1[i + 3] + x1[i]) % 2
        x2[i + 31] = (x2[i + 3] + x2[i + 2] + x2[i + 1] + x2[i]) % 2
    return (x1[nc:n] + x2[nc:n]) % 2


def reference_for(config: dict) -> type:
    """The ``SlotReference`` class the configuration names (see above).

    Raises ``ValueError``, naming the key, where ``"reference"`` names no
    module of ``bench.harness`` or one without ``SlotReference``."""
    name = config.get("reference", "reference")
    if not (isinstance(name, str) and name.isidentifier()):
        raise ValueError(f'configuration key "reference": {name!r} is not '
                         "a module name of bench.harness")
    module = f"bench.harness.{name}"
    try:
        mod = importlib.import_module(module)
    except ModuleNotFoundError as e:
        if e.name != module:
            raise
        raise ValueError(f'configuration key "reference": no module '
                         f"{module}") from None
    if not isinstance(getattr(mod, "SlotReference", None), type):
        raise ValueError(f'configuration key "reference": {module} has no '
                         "class SlotReference")
    return mod.SlotReference


class SlotReference:
    def __init__(self, config: dict, traffic: dict, seed: int, weights: dict,
                 precision: str = "f64"):
        if precision not in ("f64", "fp8", "int8"):
            raise ValueError(f"precision {precision!r}")
        if config["n_layers"] != 1:
            raise ValueError(
                f"n_layers={config['n_layers']}: this reference computes one "
                'layer; the configuration has to name, under "reference", '
                "a reference that computes its layers")
        self.c = config
        self.t = traffic
        self.seed = seed
        self.w = weights  # host copy of the AI expert's parameter tree
        self.precision = precision
        self.link = config["link"]
        n_prb = config["n_prb"]
        self.n_sc = 12 * n_prb
        self.n_ant = config["n_ant"]
        self.dmrs = tuple(config["dmrs_symbols"])
        off = config["dmrs_comb_offset"]
        self.pilot_sc = np.arange(off, self.n_sc, 2)
        self.n_p = len(self.pilot_sc)
        mask = np.ones((self.n_sc, N_SYM), bool)
        for s in self.dmrs:
            mask[self.pilot_sc, s] = False
        self.data_mask = mask
        self.n_re = int(mask.sum())
        self.slot_s = 1e-3 / (config["scs_khz"] // 15)

    # -- tables from the configuration and the standards ----------------------

    @functools.cached_property
    def pilots(self) -> np.ndarray:
        """QPSK DMRS, (n_dmrs_sym, n_pilot) complex (TS 38.211 7.4.1.1),
        the sequence of slot 0 in every slot."""
        n_id = self.link["dmrs_n_id"]
        rows = []
        for sym in self.dmrs:
            c_init = (((sym + 1) * (2 * n_id + 1) * 2**17 + 2 * n_id)
                      % 2**31)
            c = _gold(c_init, 2 * self.n_p).astype(np.float64)
            rows.append(((1 - 2 * c[0::2]) + 1j * (1 - 2 * c[1::2]))
                        / np.sqrt(2.0))
        return np.stack(rows)

    @functools.cached_property
    def wiener(self) -> np.ndarray:
        """(n_sc, n_pilot) Wiener interpolator, float64 complex."""
        df = self.c["scs_khz"] * 1e3
        k = np.arange(self.n_sc)
        r = 1.0 / (1.0 + 2j * np.pi * self.c["wiener_rms_delay_spread_s"]
                   * (k[:, None] - k[None, :]) * df)
        p = self.pilot_sc
        r_pp = r[np.ix_(p, p)] + self.c["wiener_noise_var"] * np.eye(self.n_p)
        return np.linalg.solve(r_pp.T, r[:, p].T).T

    @functools.cached_property
    def time_weights(self) -> np.ndarray:
        """(14, n_dmrs) linear interpolation across symbols, edges held."""
        anchors = np.asarray(self.dmrs, np.float64)
        w = np.zeros((N_SYM, len(anchors)))
        for i in range(N_SYM):
            j = int(np.clip(np.searchsorted(anchors, i) - 1, 0,
                            len(anchors) - 2))
            a = np.clip((i - anchors[j]) / (anchors[j + 1] - anchors[j]),
                        0.0, 1.0)
            w[i, j], w[i, j + 1] = 1.0 - a, a
        return w

    def mcs_thresholds(self) -> np.ndarray:
        """SNR (dB, back-off included) each MCS needs: 1 dB from Shannon
        at its spectral efficiency."""
        return np.asarray([10 * np.log10(2.0 ** (q * r / 1024) - 1) + 1
                           + self.link["mcs_backoff_db"]
                           for q, r in MCS_TABLE])

    def mcs(self, snr_db: float) -> int:
        """The highest MCS whose threshold the SNR meets (0 if none)."""
        return max(int(np.sum(self.mcs_thresholds() <= snr_db)) - 1, 0)

    def tbs(self, mcs: int) -> int:
        q, r = MCS_TABLE[mcs]
        n_info = self.n_re * q * (r / 1024)
        return int(max(24, np.floor(n_info / 8.0) * 8 - 24))

    # -- conditions -------------------------------------------------------------

    def condition(self, slot: int) -> dict:
        s = slot % self.t["cycle_slots"]
        for ph in self.t["phases"]:
            if ph["start"] <= s < ph["end"]:
                return self.t["conditions"][ph["condition"]]
        raise ValueError(f"no phase for slot {s}")

    # -- random draws ------------------------------------------------------------

    def _keys(self, slot: int, ue: int):
        import jax

        from bench.harness.program import seed_key

        k = jax.random.fold_in(jax.random.fold_in(seed_key(self.seed), ue),
                               slot)
        return jax.random.split(k, 4)

    @staticmethod
    def _normal(key, shape):
        import jax

        return np.asarray(jax.random.normal(key, shape), np.float64)

    def _cnormal(self, key, shape):
        return (self._normal(key, shape)
                + 1j * self._normal(key + 1, shape)) / np.sqrt(2.0)

    def _tdl(self, key) -> np.ndarray:
        """(n_ant, n_sc, 14) channel from one key, unit mean power."""
        import jax

        prof = self.c["tdl_profile"]
        p = 10.0 ** (np.asarray(prof["powers_db"]) / 10.0)
        amps = np.sqrt(p / p.sum())
        k_init, k_evo = jax.random.split(key)
        shape0 = (self.n_ant, 1, len(amps))
        g = self._cnormal(k_init, shape0)
        innov = self._cnormal(k_evo, (N_SYM,) + shape0)
        x = 2 * np.pi * prof["doppler_hz"] * self.slot_s / N_SYM
        rho = float(np.clip(1.0 - x * x / 4.0, 0.0, 1.0))
        g_t = []
        for m in range(N_SYM):
            g = rho * g + np.sqrt(1 - rho * rho) * innov[m]
            g_t.append(g)
        g_t = np.stack(g_t, -1)[:, 0] * amps[None, :, None]  # (A, T, 14)
        f = np.arange(self.n_sc) * self.c["scs_khz"] * 1e3
        steer = np.exp(-2j * np.pi * f[:, None]
                       * np.asarray(prof["delays_s"])[None, :])
        h = np.einsum("st,atm->asm", steer, g_t)
        return h / np.sqrt(np.mean(np.abs(h) ** 2) + 1e-12)

    # -- the slot -----------------------------------------------------------------

    def transmit(self, k_tx, mcs: int):
        import jax

        q = MCS_TABLE[mcs][0]
        bits = np.asarray(jax.random.bernoulli(k_tx, 0.5, (self.n_re * 8,)),
                          np.int64)[: self.n_re * q].reshape(-1, q)
        half, m = q // 2, 1 << (q // 2)
        w = 1 << np.arange(half - 1, -1, -1)
        i_code, q_code = bits[:, :half] @ w, bits[:, half:] @ w
        gray = lambda c: c ^ (c >> 1)  # noqa: E731
        lev = lambda c: 2.0 * gray(c) - (m - 1)  # noqa: E731
        syms = (lev(i_code) + 1j * lev(q_code)) / np.sqrt(
            2 * ((1 << q) - 1) / 3)
        grid = np.zeros((self.n_sc, N_SYM), np.complex128)
        grid.reshape(-1)[np.flatnonzero(self.data_mask.reshape(-1))] = syms
        for i, s in enumerate(self.dmrs):
            grid[self.pilot_sc, s] = self.pilots[i]
        return syms, grid

    def receive(self, k_ch, k_n, grid, cond):
        import jax

        nv = 10.0 ** (-cond["snr_db"] / 10.0)
        k_h, k_i, k_hi = jax.random.split(k_ch, 3)
        h = self._tdl(k_h)
        y = h * grid[None]
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
                base = np.zeros(N_SYM)
                base[list(self.dmrs)] = 1.0
                p_rest = max(duty * N_SYM - len(self.dmrs), 0.0) / (
                    N_SYM - len(self.dmrs))
                sym_mask = np.maximum(base, (u < np.float32(p_rest)) * 1.0)
            else:
                sym_mask = (u < np.float32(duty)) * 1.0
            hi = self._tdl(k_hi)
            sym = self._cnormal(k_i, (self.n_sc, N_SYM))
            amp = np.sqrt(nv * 10.0 ** (cond["inr_db"] / 10.0))
            y = y + amp * hi * (sc[:, None] * sym_mask[None, :] * sym)[None]
        y = y + np.sqrt(nv) * self._cnormal(k_n, y.shape)
        return y, nv

    def ls(self, y):
        rx = np.stack([y[:, self.pilot_sc, s] for s in self.dmrs], axis=1)
        p = self.pilots
        return rx * np.conj(p)[None] / (np.abs(p) ** 2 + 1e-12)[None]

    def _cmatmul(self, a, b):
        """Complex ``a @ b``; in a control both operands' real and
        imaginary planes are quantized first."""
        if self.precision == "f64":
            return a @ b
        ar, ai, br, bi = (_quantize(x, self.precision)
                          for x in (a.real, a.imag, b.real, b.imag))
        return (ar @ br - ai @ bi) + 1j * (ar @ bi + ai @ br)

    def mmse(self, h_ls):
        """(A, S, Np) -> (A, Nsc, S)."""
        out = self._cmatmul(h_ls.reshape(-1, self.n_p), self.wiener.T)
        return np.moveaxis(out.reshape(self.n_ant, len(self.dmrs), -1), -1, -2)

    def _conv(self, x, w, b):
        """'Same' 2-D convolution, x (C, H, W), w (O, C, kh, kw)."""
        c, hh, ww = x.shape
        o, _, kh, kw = w.shape
        xp = np.pad(x, ((0, 0), (kh // 2, kh - 1 - kh // 2),
                        (kw // 2, kw - 1 - kw // 2)))
        cols = np.stack([xp[:, i:i + hh, j:j + ww]
                         for i in range(kh) for j in range(kw)], axis=1)
        cols = cols.reshape(c * kh * kw, hh * ww)
        wm = w.reshape(o, c * kh * kw)
        y = _quantize(wm, self.precision) @ _quantize(cols, self.precision)
        return y.reshape(o, hh, ww) + b[:, None, None]

    def ai(self, h_ls):
        """(A, S, Np) -> (A, Nsc, S): the residual CNN per antenna."""
        import jax

        w = jax.tree.map(lambda x: np.asarray(x, np.float64), self.w)
        out = []
        for a in range(self.n_ant):
            x = np.stack([h_ls[a].real.T, h_ls[a].imag.T])  # (2, Np, S)
            nxt = np.concatenate([x[:, 1:], x[:, -1:]], axis=1)
            base = np.stack([x, 0.5 * (x + nxt)], axis=2).reshape(
                2, 2 * self.n_p, -1)
            h = self._conv(x, w["stem_w"], w["stem_b"])
            for blk in w["res"]:
                y = np.maximum(self._conv(h, blk["w1"], blk["b1"]), 0.0)
                h = h + self._conv(y, blk["w2"], blk["b2"])
            u = self._conv(h, w["up_w"], w["up_b"])
            c = u.shape[0] // 2
            u = np.moveaxis(u.reshape(2, c, self.n_p, -1), 0, 2).reshape(
                c, 2 * self.n_p, -1)
            r = base + self._conv(u, w["head_w"], w["head_b"])
            out.append(r[0] + 1j * r[1])
        return np.stack(out)

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
        h_ls = self.ls(y)
        h = self.ai(h_ls) if served_by_ai else self.mmse(h_ls)  # (A,Nsc,S)
        h_t = h @ self.time_weights.T  # (A, Nsc, 14)
        x_hat = (np.sum(np.conj(h_t) * y, 0)
                 / (np.sum(np.abs(h_t) ** 2, 0) + nv))
        d = x_hat.reshape(-1)[np.flatnonzero(self.data_mask.reshape(-1))]
        m = 1 << (q // 2)
        norm = np.sqrt(2 * ((1 << q) - 1) / 3)
        lvl = lambda v: np.clip(np.round((v * norm + (m - 1)) / 2), 0,  # noqa
                                m - 1)
        near = ((2 * lvl(d.real) - (m - 1)) + 1j * (2 * lvl(d.imag) - (m - 1))
                ) / norm
        sinr = np.mean(np.abs(near) ** 2) / max(
            np.mean(np.abs(d - near) ** 2), 1e-9)
        err = np.abs(d - syms) ** 2
        g = L["genie_group_re"]
        n = len(err) - len(err) % g
        genie = 1.0 / np.maximum(err[:n].reshape(-1, g).mean(1), 1e-9)
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

    def olla(self, tb_ok_history: np.ndarray) -> float:
        """Outer-loop offset after the given decoding outcomes."""
        L = self.link
        o = 0.0
        for ok in tb_ok_history:
            o = float(np.clip(o + (L["olla_up_db"] if ok else
                                   -L["olla_down_db"]),
                              -L["olla_clamp_db"], L["olla_clamp_db"]))
        return o
