"""Two-layer uplink: DMRS ports 0-1 with OCC, per-port experts, the per-RE
LMMSE detector, and the campaign spec that asks for them.

* ``CampaignSpec`` carries ``n_ant`` and ``n_layers``, validates them at
  construction and at ``from_json``, and a one-layer spec compiles to the
  same closed-loop step whether it states them or not;
* the OCC de-spread gives each port its own channel on a noiseless flat
  channel;
* the ``lmmse_detect`` kernel equals its jnp oracle and a float64 general
  solve, and with one live layer the single-layer MMSE equalizer;
* at a small size the batched closed-loop step and the host
  ``PuschPipeline``, given the same keys, agree slot by slot.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.closed_loop import host_replay_closed_loop
from repro.core.session import (
    ArchesSession,
    CampaignSpec,
    ExpertBankSpec,
    PolicySpec,
    SwitchSpec,
    spec_hash,
)
from repro.kernels.lmmse_detect import lmmse_detect, lmmse_detect_ref
from repro.phy import dmrs
from repro.phy.equalizer import mmse_equalize, time_weights
from repro.phy.estimators import ls_estimate
from repro.phy.nr import SlotConfig
from repro.phy.pipeline import LinkState, PuschPipeline

CFG_2L = SlotConfig(n_prb=4, n_ant=2, n_layers=2)


def _cnormal(key, shape):
    k1, k2 = jax.random.split(key)
    return ((jax.random.normal(k1, shape) + 1j * jax.random.normal(k2, shape))
            / np.sqrt(2.0)).astype(jnp.complex64)


# -- the spec ------------------------------------------------------------------


@pytest.mark.parametrize("kw, key", [
    ({"n_ant": 0}, "n_ant"),
    ({"n_layers": 3}, "n_layers"),
    ({"n_layers": 0}, "n_layers"),
    ({"n_ant": 1, "n_layers": 2}, "n_layers"),
])
def test_spec_refuses_radio_it_cannot_run(kw, key):
    with pytest.raises(ValueError, match=key):
        CampaignSpec(**kw)
    d = CampaignSpec().to_dict()
    d.update(kw)
    with pytest.raises(ValueError, match=key):
        CampaignSpec.from_dict(d)


def test_spec_json_round_trip_with_and_without_radio_keys():
    spec = CampaignSpec(path="closed_loop", n_ant=2, n_layers=2,
                        policies=(PolicySpec(),))
    back = CampaignSpec.from_json(spec.to_json())
    assert back == spec and spec_hash(back) == spec_hash(spec)
    assert (back.n_ant, back.n_layers) == (2, 2)
    # a spec written before the radio keys existed loads with the defaults
    d = CampaignSpec(n_prb=6).to_dict()
    del d["n_ant"], d["n_layers"]
    old = CampaignSpec.from_dict(d)
    assert (old.n_ant, old.n_layers) == (4, 1)
    assert old == CampaignSpec(n_prb=6)
    assert spec_hash(CampaignSpec(n_layers=2)) != spec_hash(CampaignSpec())


def test_session_builds_the_stated_radio():
    s = ArchesSession(CampaignSpec(n_prb=4, n_ant=2, n_layers=2))
    assert (s.cfg.n_prb, s.cfg.n_ant, s.cfg.n_layers) == (4, 2, 2)


def _one_layer_step_text(**radio):
    from repro.core.closed_loop import init_device_switch
    from repro.phy import pipeline

    spec = CampaignSpec(
        path="closed_loop", n_ues=4, n_slots=8, n_prb=8, **radio,
        bank=ExpertBankSpec(execution_mode="gated", gated_capacity=2,
                            channels=4, n_res_blocks=1),
        policies=(PolicySpec(kind="threshold", feature="snr",
                             threshold=5.0),),
        switch=SwitchSpec(window_slots=2),
    )
    s = ArchesSession(spec)
    sw_cfg = spec.switch.to_config(spec.feature_names)
    profile, params = pipeline.resolve_schedule(
        s.cfg, s.schedule, spec.n_slots, spec.n_ues)
    keys = jax.vmap(lambda u: jax.random.fold_in(jax.random.PRNGKey(1), u))(
        jnp.arange(spec.n_ues))
    return pipeline._closed_slot_step.lower(
        s.engine, profile, sw_cfg, pipeline.init_device_link(spec.n_ues),
        init_device_switch(spec.n_ues, len(spec.feature_names), sw_cfg),
        jnp.int32(0), keys, jax.tree.map(lambda v: v[0], params),
        s.device_policy,
    ).as_text()


def test_one_layer_step_lowers_alike_with_radio_keys_absent_or_stated():
    """The one-layer closed-loop step is one program whether the spec
    leaves the radio keys out or states (4, 1)."""
    text = _one_layer_step_text()
    assert "lmmse_detect" not in text
    assert text == _one_layer_step_text(n_ant=4, n_layers=1)


# -- DMRS ports ----------------------------------------------------------------


def test_occ_despread_recovers_each_port_on_a_flat_channel():
    """Noiseless, frequency- and time-flat channel: each port's LS is its
    layer's precoded channel exactly, at every pilot of the comb."""
    cfg = CFG_2L
    h = _cnormal(jax.random.PRNGKey(0), (cfg.n_ant, cfg.n_layers))
    data = _cnormal(jax.random.PRNGKey(1), (cfg.n_layers, cfg.n_data_re()))
    pilots = dmrs.dmrs_sequence(cfg)
    tx = dmrs.map_slot_grid(cfg, data, pilots)
    rx = jnp.einsum("al,lsm->asm", h, tx)
    h_ls = ls_estimate(cfg, rx, pilots)
    assert h_ls.shape == (cfg.n_ant, cfg.n_layers, cfg.n_dmrs_sym,
                          cfg.n_pilot_sc)
    want = np.broadcast_to(np.asarray(h)[:, :, None, None] / np.sqrt(2.0),
                           h_ls.shape)
    np.testing.assert_allclose(np.asarray(h_ls), want, atol=2e-6)
    # the data comb of the DMRS symbols still carries each layer's data
    got = dmrs.extract_data_re(cfg, tx) * np.sqrt(2.0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(data), atol=1e-6)


def test_one_layer_ls_is_unchanged_by_the_port_code():
    cfg = SlotConfig(n_prb=4, n_ant=2)
    rx = _cnormal(jax.random.PRNGKey(2), (cfg.n_ant, cfg.n_sc, cfg.n_sym))
    pilots = dmrs.dmrs_sequence(cfg)
    h_ls = ls_estimate(cfg, rx, pilots)
    rx_p = np.asarray(dmrs.extract_pilot_re(cfg, rx))
    p = np.asarray(pilots)
    np.testing.assert_allclose(np.asarray(h_ls),
                               rx_p * np.conj(p) / (np.abs(p) ** 2 + 1e-12),
                               rtol=1e-6)


# -- the detector ----------------------------------------------------------------


def _detector_case(n_prb=4, n_ant=2, n_ues=3):
    cfg = SlotConfig(n_prb=n_prb, n_ant=n_ant, n_layers=2)
    k1, k2 = jax.random.split(jax.random.PRNGKey(10 + n_ant))
    h = _cnormal(k1, (n_ues, n_ant, 2, cfg.n_sc, cfg.n_dmrs_sym))
    y = _cnormal(k2, (n_ues, n_ant, cfg.n_sc, cfg.n_sym))
    nv = jnp.asarray([0.1, 0.5, 0.02][:n_ues], jnp.float32)
    return cfg, h, y, nv, jnp.asarray(time_weights(cfg), jnp.float32)


@pytest.mark.parametrize("n_ant", [2, 4])
def test_lmmse_kernel_matches_its_reference(n_ant):
    """Interpret mode against the jnp oracle (a general inverse): f32
    rounding of a closed form against an inverse, on random channels."""
    _, h, y, nv, tw = _detector_case(n_ant=n_ant)
    x = lmmse_detect(h, y, nv, tw, interpret=True)
    xr = lmmse_detect_ref(h, y, nv, tw)
    np.testing.assert_allclose(np.asarray(x), np.asarray(xr), atol=1e-4)


def test_lmmse_kernel_matches_a_float64_solve():
    """``x_hat = solve(H^H H + s2 I, H^H y)`` per RE, in float64 NumPy."""
    cfg, h, y, nv, tw = _detector_case()
    x = lmmse_detect(h, y, nv, tw, interpret=True)
    w = time_weights(cfg)
    hn = np.einsum("ualsd,md->usmal", np.asarray(h, np.complex128), w)
    yn = np.transpose(np.asarray(y, np.complex128), (0, 2, 3, 1))
    nvn = np.asarray(nv, np.float64)[:, None, None, None, None]
    g = np.einsum("...al,...ak->...lk", hn.conj(), hn) + nvn * np.eye(2)
    z = np.einsum("...al,...a->...l", hn.conj(), yn)
    want_x = np.linalg.solve(g, z[..., None])[..., 0]
    np.testing.assert_allclose(np.asarray(x),
                               np.transpose(want_x, (0, 3, 1, 2)),
                               rtol=1e-4, atol=1e-4)


def test_lmmse_at_one_layer_is_the_mmse_equalizer():
    """With the second layer's channel zero, ``G`` is diagonal and layer 0
    detects as ``h^H y / (|h|^2 + s2)``: the single-layer MMSE equalizer,
    to f32 rounding; the silent layer detects as zero."""
    cfg, h, y, nv, tw = _detector_case(n_ant=4)
    h = h.at[:, :, 1].set(0)
    x = lmmse_detect(h, y, nv, tw, interpret=True)
    for u in range(h.shape[0]):
        xe, _ = mmse_equalize(cfg, y[u], h[u, :, :1], nv[u])
        np.testing.assert_allclose(np.asarray(x[u, 0]), np.asarray(xe),
                                   rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(x[:, 1]), 0)


# -- the closed loop against the host pipeline -------------------------------


@pytest.fixture(scope="module")
def two_layer_campaign():
    """A two-layer closed-loop campaign at 4 PRB, 2 RX and 2 UEs through
    ``ArchesSession``'s engine, with its fitted tree."""
    n_slots = 18
    spec = CampaignSpec(
        path="closed_loop", scenario="good_poor_good",
        scenario_args=(("poor_start", 6), ("poor_end", 12)),
        n_ues=2, n_slots=n_slots, n_prb=4, n_ant=2, n_layers=2,
        bank=ExpertBankSpec(channels=4, n_res_blocks=1),
        policies=(PolicySpec(kind="tree", depth=2, train_ues=2),),
        switch=SwitchSpec(window_slots=2),
    )
    spec = CampaignSpec.from_json(spec.to_json())
    session = ArchesSession(spec)
    sw_cfg = spec.switch.to_config(spec.feature_names)
    key = jax.random.PRNGKey(5)
    _, _, traj = session.engine.run_closed_loop(
        session.schedule, session.device_policy, sw_cfg,
        n_slots=n_slots, n_ues=spec.n_ues, key=key)
    return session, sw_cfg, key, jax.tree.map(np.asarray, traj)


def test_two_layer_campaign_runs_through_the_session(two_layer_campaign):
    session, _, _, traj = two_layer_campaign
    hist = session.run()
    assert hist.modes.shape == (session.spec.n_slots, 2)
    assert np.all(np.isfinite(hist.kpms["snr"]))
    # the AI expert serves the poor phase: the switch is exercised
    assert set(np.unique(traj["active_mode"])) == {0, 1}


def test_batched_step_matches_host_pipeline_slot_by_slot(two_layer_campaign):
    """Slot by slot, each UE's host ``PuschPipeline`` slot (the batched
    keys, the mode the closed loop committed, the SNR report the batched
    link held) against the batched step: MCS and TB outcomes equal; SNR
    within 1e-4 dB and RSRP within 1e-5 relative (the two programs fuse
    the same f32 arithmetic differently); and the host replay of the
    loop on the host's KPMs takes the same decisions and modes."""
    session, sw_cfg, key, traj = two_layer_campaign
    spec = session.spec
    pipe = PuschPipeline(session.cfg, session.ai_params, net=session.net)
    feats = np.zeros(traj["active_mode"].shape + (len(spec.feature_names),),
                     np.float32)
    for u in range(spec.n_ues):
        link = LinkState()
        for s in range(spec.n_slots):
            if s:
                link = dataclasses.replace(link, reported_snr_db=float(
                    traj["kpms"]["oai"]["snr"][s - 1, u]))
            k = jax.random.fold_in(jax.random.fold_in(key, u), s)
            link, out, kpms = pipe.run_slot_keys(
                (*jax.random.split(k, 4), None),
                int(traj["active_mode"][s, u]), link, session.schedule(s))
            flat = {**kpms["aerial"], **kpms["oai"]}
            feats[s, u] = [flat[n] for n in spec.feature_names]
            assert out["mcs"] == traj["mcs"][s, u], (s, u)
            assert out["tb_ok"] == traj["tb_ok"][s, u], (s, u)
            assert abs(flat["snr"] - traj["kpms"]["oai"]["snr"][s, u]) < 1e-4
            rsrp = traj["kpms"]["aerial"]["rsrp"][s, u]
            assert abs(flat["rsrp"] - rsrp) <= 1e-5 * rsrp, (s, u)
    replay = host_replay_closed_loop(session.host_policies[0], feats, sw_cfg)
    np.testing.assert_array_equal(replay["raw_decision"], traj["raw_decision"])
    np.testing.assert_array_equal(replay["active_mode"], traj["active_mode"])
