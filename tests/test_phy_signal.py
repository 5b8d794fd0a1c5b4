"""5G NR PHY substrate: QAM, DMRS grid, estimators, equalizer, link adaptation."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.phy import dmrs as D
from repro.phy import qam as Q
from repro.phy.channel import ChannelConfig, apply_channel, simulate_slot_channel
from repro.phy.equalizer import mmse_equalize, time_interpolate
from repro.phy.estimators import WienerInterpolator, ls_estimate, mmse_estimate
from repro.phy.mcs import mcs_entry, n_code_blocks, select_mcs, transport_block_size
from repro.phy.nr import SlotConfig

CFG = SlotConfig(n_prb=24)


# -- QAM -------------------------------------------------------------------------


@pytest.mark.parametrize("qm", [2, 4, 6, 8])
def test_qam_roundtrip(qm, rng):
    bits = jnp.asarray(rng.integers(0, 2, size=qm * 64), jnp.uint8)
    syms = Q.modulate(bits, qm)
    assert syms.shape == (64,)
    # unit average power constellation
    assert abs(float(jnp.mean(jnp.abs(Q.constellation(qm)) ** 2)) - 1.0) < 1e-5
    # noiseless demap recovers bits
    llr = Q.demap_llr(syms, jnp.asarray(1e-4), qm)
    np.testing.assert_array_equal(np.asarray(Q.hard_bits(llr)), np.asarray(bits))


@pytest.mark.parametrize("qm", [2, 4, 6])
def test_qam_llr_sign_flips_with_noise(qm, rng):
    """LLR magnitudes shrink as noise_var grows (soft information property)."""
    bits = jnp.asarray(rng.integers(0, 2, size=qm * 128), jnp.uint8)
    syms = Q.modulate(bits, qm)
    llr_lo = Q.demap_llr(syms, jnp.asarray(0.01), qm)
    llr_hi = Q.demap_llr(syms, jnp.asarray(1.0), qm)
    assert float(jnp.mean(jnp.abs(llr_lo))) > float(jnp.mean(jnp.abs(llr_hi)))


def _label_bits(labels, qm):
    """Labels -> their qm bits each, MSB first, flattened per row."""
    shifts = np.arange(qm - 1, -1, -1)
    bits = (labels[..., None] >> shifts) & 1
    return bits.reshape(labels.shape[:-1] + (-1,)).astype(np.uint8)


def _bits_of(x):
    return np.asarray(x).view(np.uint64)


@pytest.mark.parametrize("qm", [2, 4, 6, 8])
def test_modulate_equals_table_lookup_on_every_label(qm):
    """Every label of the order maps to its ``constellation`` entry, bit for bit."""
    table = np.asarray(Q.constellation(qm))
    labels = np.arange(1 << qm)
    syms = Q.modulate(jnp.asarray(_label_bits(labels, qm)), qm)
    assert syms.dtype == jnp.complex64 and syms.shape == labels.shape
    np.testing.assert_array_equal(_bits_of(syms), _bits_of(table[labels]))

    n = 15900
    rows = np.stack([np.roll(np.resize(labels, n), r) for r in range(16)])
    batched = jax.vmap(lambda b: Q.modulate(b, qm))(jnp.asarray(_label_bits(rows, qm)))
    assert batched.dtype == jnp.complex64 and batched.shape == (16, n)
    np.testing.assert_array_equal(_bits_of(batched), _bits_of(table[rows]))


def _argmin_point(y, qm):
    table = np.asarray(Q.constellation(qm))
    d2 = np.abs(y.astype(np.complex128)[..., None] - table.astype(np.complex128)) ** 2
    return table[np.argmin(d2, axis=-1)]


@pytest.mark.parametrize("qm", [2, 4, 6, 8])
@pytest.mark.parametrize("kind", ["on_grid", "near_grid", "far_outside"])
def test_nearest_point_equals_bruteforce_argmin(kind, qm):
    """The per-axis pick is the O(2^qm) argmin over the table, bit for bit."""
    table = np.asarray(Q.constellation(qm))
    r = np.random.default_rng(qm)
    spacing = 2.0 / Q._NORM[qm]  # distance between adjacent per-axis levels
    if kind == "on_grid":
        y = table
    elif kind == "near_grid":
        # within 0.45 of a spacing of a point per axis: a decision midpoint
        # lies half a spacing away
        base = table[r.integers(0, table.size, 4096)]
        off = r.uniform(-0.45, 0.45, (2, base.size)) * spacing
        y = (base + off[0] + 1j * off[1]).astype(np.complex64)
    else:
        mag = r.uniform(2.0, 50.0, 4096)
        phase = r.uniform(0.0, 2 * np.pi, 4096)
        y = (mag * np.exp(1j * phase)).astype(np.complex64)
    got = Q.nearest_point(jnp.asarray(y), qm)
    assert got.dtype == jnp.complex64 and got.shape == y.shape
    np.testing.assert_array_equal(_bits_of(got), _bits_of(_argmin_point(y, qm)))


@pytest.mark.parametrize("qm", [2, 4, 6, 8])
@pytest.mark.parametrize("fn", ["modulate", "nearest_point"])
def test_qam_lowers_without_gather(fn, qm):
    """At the 106-PRB slot's (16 UEs, 15900 data REs) neither lookup gathers."""
    n = 15900
    if fn == "modulate":
        f = jax.vmap(lambda b: Q.modulate(b, qm))
        arg = jax.ShapeDtypeStruct((16, n * qm), jnp.uint8)
    else:
        f = jax.vmap(lambda y: Q.nearest_point(y, qm))
        arg = jax.ShapeDtypeStruct((16, n), jnp.complex64)
    text = jax.jit(f).lower(arg).as_text()
    assert "gather" not in text


# -- DMRS grid --------------------------------------------------------------------


def test_grid_mapping_inverse(rng):
    cfg = CFG
    n_data = cfg.n_data_re()
    syms = jnp.asarray(
        rng.normal(size=n_data) + 1j * rng.normal(size=n_data), jnp.complex64
    )
    pilots = D.dmrs_sequence(cfg)
    grid = D.map_slot_grid(cfg, syms, pilots)
    assert grid.shape == (cfg.n_layers, cfg.n_sc, cfg.n_sym)
    got_data = D.extract_data_re(cfg, grid)[0]
    np.testing.assert_allclose(np.asarray(got_data), np.asarray(syms), atol=1e-6)
    got_pilot = D.extract_pilot_re(cfg, grid)[0]
    want = jnp.broadcast_to(pilots, got_pilot.shape)
    np.testing.assert_allclose(np.asarray(got_pilot), np.asarray(want), atol=1e-6)


def test_dmrs_type1_positions():
    """Type-1 DMRS on symbols 0/5/10, comb-2 (paper 5.1, Fig. 6)."""
    assert CFG.dmrs_symbols == (0, 5, 10)
    pilots = D.dmrs_sequence(CFG)
    assert pilots.shape[-1] == CFG.n_sc // 2  # comb-2: every other SC
    # unit-modulus QPSK sequence
    np.testing.assert_allclose(np.abs(np.asarray(pilots)), 1.0, atol=1e-6)


def test_dmrs_sequence_depends_on_cell_and_slot():
    a = D.dmrs_sequence(CFG, slot=0, cell_id=42)
    b = D.dmrs_sequence(CFG, slot=1, cell_id=42)
    c = D.dmrs_sequence(CFG, slot=0, cell_id=7)
    assert not np.allclose(np.asarray(a), np.asarray(b))
    assert not np.allclose(np.asarray(a), np.asarray(c))


# -- estimators ---------------------------------------------------------------------


def _flat_channel_rx(key, cfg, h_scalar=1.0, snr_db=100.0):
    """TX grid through a flat (constant) channel for estimator ground truth."""
    n_data = cfg.n_data_re()
    kd, kn = jax.random.split(key)
    syms = Q.modulate(
        jax.random.bernoulli(kd, 0.5, (n_data * 2,)).astype(jnp.uint8), 2
    )
    pilots = D.dmrs_sequence(cfg)
    grid = D.map_slot_grid(cfg, syms, pilots)[0]  # layer 0 -> (n_sc, n_sym)
    rx = jnp.broadcast_to(grid[None], (cfg.n_ant, *grid.shape)) * h_scalar
    noise_var = 10 ** (-snr_db / 10)
    noise = (
        jax.random.normal(kn, rx.shape) + 1j * jax.random.normal(kn, rx.shape)
    ) * jnp.sqrt(noise_var / 2)
    return rx + noise.astype(rx.dtype), pilots, syms, noise_var


def test_ls_estimate_flat_channel():
    cfg = CFG
    rx, pilots, _, _ = _flat_channel_rx(jax.random.PRNGKey(0), cfg, h_scalar=0.7 + 0.2j)
    h_ls = ls_estimate(cfg, rx, pilots)
    assert h_ls.shape == (cfg.n_ant, len(cfg.dmrs_symbols), cfg.n_sc // 2)
    np.testing.assert_allclose(
        np.asarray(h_ls), np.full(h_ls.shape, 0.7 + 0.2j), atol=1e-3
    )


def test_mmse_beats_ls_at_low_snr():
    """Wiener smoothing must reduce estimation MSE vs raw LS under noise."""
    cfg = CFG
    wi = WienerInterpolator.build(cfg, rms_delay_spread_s=1e-7)
    key = jax.random.PRNGKey(1)
    mse_ls, mse_mmse = [], []
    for t in range(5):
        k = jax.random.fold_in(key, t)
        rx, pilots, _, _ = _flat_channel_rx(k, cfg, h_scalar=1.0, snr_db=0.0)
        h_ls = ls_estimate(cfg, rx, pilots)
        h_mmse = mmse_estimate(cfg, rx, pilots, wi)
        # truth: H == 1 everywhere
        mse_ls.append(float(jnp.mean(jnp.abs(h_ls - 1.0) ** 2)))
        mse_mmse.append(float(jnp.mean(jnp.abs(h_mmse - 1.0) ** 2)))
    assert np.mean(mse_mmse) < np.mean(mse_ls)


def test_mmse_kernel_equals_ref_path():
    cfg = CFG
    wi = WienerInterpolator.build(cfg)
    rx, pilots, _, _ = _flat_channel_rx(jax.random.PRNGKey(2), cfg, snr_db=10.0)
    a = mmse_estimate(cfg, rx, pilots, wi, use_kernel=True)
    b = mmse_estimate(cfg, rx, pilots, wi, use_kernel=False)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=3e-5, atol=3e-5)


# -- equalizer ----------------------------------------------------------------------


def test_equalizer_recovers_flat_channel_symbols():
    cfg = CFG
    h = 0.8 - 0.3j
    rx, pilots, syms, nv = _flat_channel_rx(jax.random.PRNGKey(3), cfg, h_scalar=h)
    h_est = jnp.full(
        (cfg.n_ant, 1, cfg.n_sc, len(cfg.dmrs_symbols)), h, jnp.complex64
    )
    x_hat, _ = mmse_equalize(cfg, rx, h_est, jnp.asarray(nv))
    data = D.extract_data_re(cfg, x_hat[None])[0]
    np.testing.assert_allclose(np.asarray(data), np.asarray(syms), atol=1e-2)


def test_time_interpolate_shape():
    cfg = CFG
    h = jnp.ones((cfg.n_ant, 1, cfg.n_sc, len(cfg.dmrs_symbols)), jnp.complex64)
    full = time_interpolate(cfg, h)
    assert full.shape == (cfg.n_ant, 1, cfg.n_sc, cfg.n_sym)


# -- channel model ---------------------------------------------------------------


def test_channel_sim_fields():
    fields = simulate_slot_channel(jax.random.PRNGKey(0), CFG, ChannelConfig())
    h = fields["h"]
    assert h.shape == (CFG.n_ant, CFG.n_layers, CFG.n_sc, CFG.n_sym)
    assert np.isfinite(np.asarray(h).view(np.float32)).all()
    # normalized average channel power ~ 1
    assert 0.5 < float(jnp.mean(jnp.abs(h) ** 2)) < 2.0


def test_apply_channel_snr():
    """Measured post-channel SNR tracks the configured value."""
    cfg = CFG
    ch = ChannelConfig(snr_db=10.0)
    key = jax.random.PRNGKey(5)
    fields = simulate_slot_channel(key, cfg, ch)
    tx = jnp.ones((cfg.n_layers, cfg.n_sc, cfg.n_sym), jnp.complex64)
    rx = apply_channel(jax.random.PRNGKey(6), tx, fields)
    clean = fields["h"][:, 0] * tx[0]
    sig = rx - clean
    snr_meas = 10 * np.log10(
        float(jnp.mean(jnp.abs(clean) ** 2) / jnp.mean(jnp.abs(sig) ** 2))
    )
    assert abs(snr_meas - 10.0) < 1.5


def test_interference_lowers_sinr():
    cfg = CFG
    clean = ChannelConfig(snr_db=20.0, interference=False)
    dirty = ChannelConfig(snr_db=20.0, interference=True, inr_db=15.0, interference_prb_frac=1.0)
    k = jax.random.PRNGKey(7)
    tx = jnp.ones((cfg.n_layers, cfg.n_sc, cfg.n_sym), jnp.complex64)
    f_c = simulate_slot_channel(k, cfg, clean)
    f_d = simulate_slot_channel(k, cfg, dirty)
    rx_c = apply_channel(jax.random.PRNGKey(8), tx, f_c)
    rx_d = apply_channel(jax.random.PRNGKey(8), tx, f_d)
    err_c = float(jnp.mean(jnp.abs(rx_c - f_c["h"][:, 0] * tx[0]) ** 2))
    err_d = float(jnp.mean(jnp.abs(rx_d - f_d["h"][:, 0] * tx[0]) ** 2))
    assert err_d > 2 * err_c


# -- link adaptation ----------------------------------------------------------------


def test_mcs_table_monotone():
    prev_eff = 0.0
    for i in range(0, 28, 3):
        e = mcs_entry(i)
        eff = e.qm * e.code_rate
        assert eff > prev_eff
        prev_eff = eff


def test_select_mcs_monotone_in_snr():
    idxs = [select_mcs(s).index for s in np.linspace(-5, 35, 15)]
    assert all(b >= a for a, b in zip(idxs, idxs[1:]))
    assert idxs[0] == 0 and idxs[-1] >= 25


def test_tbs_positive_and_scales():
    e = mcs_entry(10)
    small = transport_block_size(1000, e)
    large = transport_block_size(10000, e)
    assert 0 < small < large
    assert n_code_blocks(large) >= 1
