"""The GPU-accelerated PUSCH RX pipeline with the ARCHES expert bank
(paper Fig. 2, nodes 2a-2e).

Per slot:
  TX   link adaptation (prev slot's SNR -> MCS/TBS) -> bits -> QAM -> grid+DMRS
  CH   TDL fading + optional interference + AWGN
  RX   LS (2b) -> expert bank {MMSE (2c), AI (2d)} -> switch kernel (2e)
       -> time-interp + MMSE equalizer -> max-log LLRs -> TB CRC (MIESM)
  KPM  Aerial Data Lake (PHY, per-slot) + OAI (L2+) telemetry

With ``SlotConfig.n_layers == 2`` a UE sends two layers of one codeword
(TPMI 0, DMRS ports 0 and 1 told apart by their OCC, ``repro.phy.dmrs``).
LS is per port; the bank sees each (antenna, layer) estimate as one port
of an ``n_ant * n_layers``-port UE (the layer axis is folded into the
antenna axis before the bank and unfolded after it), so both experts and
the switch run unchanged and one expert serves all of a UE's ports.  A
per-RE LMMSE detector (the ``lmmse_detect`` kernel) separates the layers
in place of MRC.  The TBS takes both layers, and the SNR, MIESM and RSRP
KPMs are taken over both.

Mode numbering follows the paper: ``mode=0`` selects AI (designated buffer —
switch is a no-op), ``mode=1`` selects MMSE (copy path).

The pipeline is generic infrastructure: every stage is jitted; the per-slot
host loop only carries link-adaptation state and cumulative counters —
exactly the split the paper's cuBB/L2 boundary imposes.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, Mapping, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import tracing
from repro.core.closed_loop import (
    DevicePolicy,
    SwitchConfig,
    breaker_update,
    init_device_switch,
    switch_boundary,
    switch_update,
)
from repro.core.expert_bank import ExecutionMode, Expert, ExpertBank
from repro.core.methodology import perturb_estimate
from repro.core.telemetry import trajectory_kpm_matrix
from repro.phy import dmrs as dmrs_mod
from repro.phy import qam
from repro.phy.ai_estimator import AiEstimatorConfig, ai_estimate_from_ls
from repro.phy.channel import (
    CellParams,
    ChannelConfig,
    ChannelParams,
    TdlProfile,
    apply_cell_coupling,
    apply_channel,
    channel_params_schedule,
    channel_params_ue_schedule,
    simulate_slot_channel,
    simulate_slot_channel_traced,
)
from repro.phy.equalizer import (
    effective_noise_var,
    lmmse_equalize,
    mmse_equalize,
    mmse_irc_equalize,
)
from repro.phy.estimators import (
    WienerInterpolator,
    estimator_flops,
    ls_estimate,
    mmse_estimate,
)
from repro.phy.link import (
    count_bit_errors,
    effective_mi,
    tb_success,
    tb_success_dynamic,
    throughput_bits,
)
from repro.phy.mcs import (
    MAX_MCS,
    McsEntry,
    QM_BY_MCS,
    QM_INDEX_BY_MCS,
    QM_VALUES,
    RATE_BY_MCS,
    mcs_entry,
    n_code_blocks,
    n_code_blocks_table,
    select_mcs,
    select_mcs_index,
    tbs_table,
    transport_block_size,
)
from repro.phy.nr import SlotConfig
from repro.phy.slot_outputs import SlotOutputs, pack, stack

# MAC overheads (bytes) for the PHY->MAC KPM coupling
_MAC_HEADER_BYTES = 3
_RLC_HEADER_BYTES = 2
_LCID4_FRACTION = 0.95  # share of MAC SDU carrying user-plane LCID 4 traffic


@dataclasses.dataclass
class LinkState:
    """Host-side link-adaptation + cumulative-counter state."""

    reported_snr_db: float = 20.0
    ndi: int = 1
    cum_phy_bits: float = 0.0
    cum_mac_bytes: float = 0.0
    cum_lcid4_bytes: float = 0.0
    slots: int = 0
    # outer-loop link adaptation: HARQ ACK/NACK-driven SINR offset.  The
    # decision-directed SINR measurement is biased at low SINR (wrong hard
    # decisions snap part of the error away, and more so for a worse channel
    # estimate); OLLA closes the loop on *realized* BLER, so estimator
    # quality surfaces in the MCS the scheduler actually grants — exactly
    # how production gNBs (incl. the paper's OAI L2) absorb measurement bias.
    olla_offset_db: float = 0.0


# OLLA steps: steady-state BLER target = up / (up + down) ~= 10 %
_OLLA_UP_DB = 0.15
_OLLA_DOWN_DB = 1.35
_OLLA_CLAMP_DB = 10.0


class PuschPipeline:
    """One UE's UL PUSCH receive chain with a switchable estimator bank."""

    def __init__(
        self,
        cfg: SlotConfig,
        ai_params: Any,
        *,
        net: AiEstimatorConfig = AiEstimatorConfig(),
        execution_mode: ExecutionMode = ExecutionMode.CONCURRENT,
        use_pallas_switch: bool = True,
        rms_delay_spread_s: float = 100e-9,
    ):
        self.cfg = cfg
        self.ai_params = ai_params
        self.interpolator = WienerInterpolator.build(
            cfg, rms_delay_spread_s=rms_delay_spread_s
        )
        # Bank order: designated expert FIRST (mode 0 == AI, paper 5.2).
        self.bank = ExpertBank(
            [
                Expert(
                    name="ai",
                    fn=lambda p, h_ls: ai_estimate_from_ls(p, h_ls),
                    params=ai_params,
                    flops=net.flops(cfg),
                ),
                Expert(
                    name="mmse",
                    fn=lambda p, h_ls: self._mmse_from_ls(h_ls),
                    params=None,
                    flops=estimator_flops(cfg),
                ),
            ],
            default_mode=1,
            execution_mode=execution_mode,
            use_pallas_switch=use_pallas_switch,
        )

    # -- expert wrappers ------------------------------------------------------

    def _mmse_from_ls(self, h_ls: jax.Array) -> jax.Array:
        from repro.kernels.mmse_interp import mmse_interp

        h_full = mmse_interp(h_ls, self.interpolator.w)
        return jnp.moveaxis(h_full, -2, -1)[:, None]

    def _unfold(self, h: jax.Array) -> jax.Array:
        """An expert's estimate on the folded (antenna, layer) ports,
        (n_ant * n_layers, 1, n_sc, n_dmrs_sym), as (n_ant, n_layers, n_sc,
        n_dmrs_sym) (see the module docstring)."""
        return h.reshape((self.cfg.n_ant, self.cfg.n_layers) + h.shape[2:])

    # -- jitted slot stages ----------------------------------------------------

    @partial(jax.jit, static_argnames=("self", "qm", "tbs_bits"))
    def _tx_slot(self, key: jax.Array, qm: int, tbs_bits: int):
        """bits -> QAM symbols -> resource grid (+ pilots).  With more than
        one layer the payload is drawn as the batched engine draws it
        (``BatchedPuschPipeline._ue_pre``), one row a layer."""
        cfg = self.cfg
        n_coded = cfg.n_data_re() * qm
        if cfg.n_layers == 1:
            bits = jax.random.bernoulli(key, 0.5, (n_coded,)).astype(jnp.uint8)
        else:
            bits = jax.random.bernoulli(
                key, 0.5, (cfg.n_layers, cfg.n_data_re() * max(QM_VALUES))
            ).astype(jnp.uint8)[:, :n_coded]
        syms = qam.modulate(bits, qm)
        pilots = dmrs_mod.dmrs_sequence(cfg)
        grid = dmrs_mod.map_slot_grid(cfg, syms, pilots)
        return bits, syms, grid, pilots

    @partial(jax.jit, static_argnames=("self", "qm", "perturb"))
    def _rx_slot(
        self,
        mode: jax.Array,
        rx_grid: jax.Array,
        pilots: jax.Array,
        tx_data_syms: jax.Array,
        noise_var: jax.Array,
        qm: int,
        *,
        perturb: bool = False,
        rho: jax.Array | float = 0.0,
        perturb_key: jax.Array | None = None,
    ):
        """LS -> expert bank -> switch -> equalize -> demap. Returns a dict.

        Two quality signals, deliberately separated:
        * *measured SINR* — decision-directed data-RE EVM, receiver-side
          (what Aerial reports and what drives link adaptation + LLR
          scaling).  Pilot-RE EVM is deliberately NOT used: estimates are
          derived from those same pilots, so their post-equalization EVM is
          self-referentially optimistic for LS-like estimators and blind to
          interpolation error on the data REs, which is exactly the error an
          expert estimator reduces.  Decision-directed EVM (against the
          nearest constellation point) is the standard receiver-side proxy
          and degrades when the channel estimate is bad — which is what
          makes the paper's Fig. 4 KPM trends monotonic in rho.
        * *genie per-RE SINR* — data-RE EVM against the known TX symbols
          (simulator-only), drives the MIESM TB-CRC model.
        """
        cfg = self.cfg
        h_ls = ls_estimate(cfg, rx_grid, pilots)
        ports = h_ls.reshape((-1,) + h_ls.shape[-2:])
        if perturb:
            # Methodology stage 1 (paper Fig. 3): MMSE only, AWGN injected at
            # node 2c — no switching, no AI in the loop.
            h_sel = self._unfold(self._mmse_from_ls(ports))
            h_sel = perturb_estimate(h_sel, rho, perturb_key)
            all_outputs = None
        else:
            out = self.bank(mode, ports)
            h_sel = self._unfold(out.selected)
            all_outputs = None if out.all_outputs is None else tuple(
                self._unfold(h) for h in out.all_outputs)
        if cfg.n_layers == 1:
            x_hat, _ = mmse_equalize(cfg, rx_grid, h_sel, noise_var)
            data_hat = dmrs_mod.extract_data_re(cfg, x_hat[None])[0]
        else:
            x_hat = lmmse_equalize(
                cfg, rx_grid[None], h_sel[None], jnp.reshape(noise_var, (1,))
            )
            # (n_layers, n_data_re)
            data_hat = dmrs_mod.extract_data_re(cfg, x_hat[0])

        # measured SINR: decision-directed EVM on data REs of every layer
        # (receiver-side)
        points = qam.constellation(qm)
        nearest = points[
            jnp.argmin(jnp.abs(data_hat[..., None] - points), axis=-1)
        ]
        dd_err = jnp.mean(jnp.abs(data_hat - nearest) ** 2)
        sig_pow = jnp.mean(jnp.abs(nearest) ** 2)
        sinr_meas = sig_pow / jnp.maximum(dd_err, 1e-9)

        # genie per-RE SINR on data REs (TB-success model only)
        genie_err = jnp.abs(data_hat - tx_data_syms) ** 2
        # smooth over PRB-sized windows of each layer: LDPC averages error
        # bursts
        n = genie_err.shape[-1] - genie_err.shape[-1] % 12
        smoothed = jnp.mean(
            genie_err[..., :n].reshape(genie_err.shape[:-1] + (-1, 12)), axis=-1
        ).reshape(-1)
        genie_sinr = 1.0 / jnp.maximum(smoothed, 1e-9)

        llr = qam.demap_llr(data_hat, 1.0 / sinr_meas, qm)
        rsrp = jnp.mean(jnp.abs(h_sel) ** 2)
        return {
            "h_selected": h_sel,
            "all_outputs": all_outputs,
            "llr": llr,
            "genie_sinr": genie_sinr,
            "rsrp": rsrp,
            "post_snr_lin": sinr_meas,
        }

    # -- full slot -------------------------------------------------------------

    def run_slot(
        self,
        key: jax.Array,
        mode: int | jax.Array,
        link: LinkState,
        channel_cfg: ChannelConfig,
        *,
        perturb_rho: float | None = None,
    ) -> tuple[LinkState, dict[str, Any], dict[str, Mapping[str, float]]]:
        """Execute one slot; returns (new link state, outputs, KPMs-by-source)."""
        return self.run_slot_keys(
            jax.random.split(key, 5), mode, link, channel_cfg,
            perturb_rho=perturb_rho,
        )

    def run_slot_keys(
        self,
        keys,
        mode: int | jax.Array,
        link: LinkState,
        channel_cfg: ChannelConfig,
        *,
        perturb_rho: float | None = None,
    ) -> tuple[LinkState, dict[str, Any], dict[str, Mapping[str, float]]]:
        """``run_slot`` with its draws' keys given: ``(k_tx, k_ch, k_n,
        k_crc, k_p)`` for the payload, channel, noise, TB decoding draw and
        the perturbation (``k_p`` only read with ``perturb_rho``).  With the
        batched engine's four keys of a UE's slot (``BatchedPuschPipeline.
        _ue_pre``) and more than one layer, the host slot draws what the
        batched one draws."""
        cfg = self.cfg
        k_tx, k_ch, k_n, k_crc, k_p = keys

        # link adaptation from last slot's report + OLLA offset (L2 behaviour)
        mcs = select_mcs(link.reported_snr_db + link.olla_offset_db)
        tbs = transport_block_size(cfg.n_data_re(), mcs, cfg.n_layers)
        bits, tx_syms, tx_grid, pilots = self._tx_slot(k_tx, mcs.qm, tbs)

        fields = simulate_slot_channel(k_ch, cfg, channel_cfg)
        rx_grid = apply_channel(k_n, tx_grid, fields)

        rx = self._rx_slot(
            jnp.asarray(mode, jnp.int32),
            rx_grid,
            pilots,
            tx_syms,
            fields["noise_var"],
            mcs.qm,
            perturb=perturb_rho is not None,
            rho=0.0 if perturb_rho is None else perturb_rho,
            perturb_key=k_p,
        )

        ok = tb_success(rx["genie_sinr"], mcs, key=k_crc)
        phy_bits = throughput_bits(tbs, ok, cfg.slot_duration_s)

        # -- host-side KPM assembly (Aerial Data Lake + OAI, paper 4.3/6) --
        ok_f = float(ok)
        tb_bytes = tbs / 8.0
        mac_sdu_bytes = max(tb_bytes - _MAC_HEADER_BYTES, 0.0) * ok_f
        lcid4_bytes = max(mac_sdu_bytes - _RLC_HEADER_BYTES, 0.0) * _LCID4_FRACTION

        olla = link.olla_offset_db + (_OLLA_UP_DB if ok_f else -_OLLA_DOWN_DB)
        olla = float(np.clip(olla, -_OLLA_CLAMP_DB, _OLLA_CLAMP_DB))
        new_link = LinkState(
            reported_snr_db=float(10.0 * np.log10(float(rx["post_snr_lin"]) + 1e-9)),
            ndi=1 if ok_f else 0,  # NDI toggles on new data; retx keeps it
            cum_phy_bits=link.cum_phy_bits + float(phy_bits) * cfg.slot_duration_s,
            cum_mac_bytes=link.cum_mac_bytes + mac_sdu_bytes,
            cum_lcid4_bytes=link.cum_lcid4_bytes + lcid4_bytes,
            slots=link.slots + 1,
            olla_offset_db=olla,
        )
        elapsed = new_link.slots * cfg.slot_duration_s
        kpms = {
            "aerial": {
                "code_rate": mcs.code_rate,
                "sinr": float(10.0 * np.log10(float(rx["post_snr_lin"]) + 1e-9)),
                "qam_order": float(mcs.qm),
                "mcs_index": float(mcs.index),
                "tb_size": float(tbs) * ok_f,
                "n_code_blocks": float(n_code_blocks(tbs)) * ok_f,
                "pdu_length": tb_bytes * ok_f,
                "ndi": float(new_link.ndi),
                "rsrp": float(rx["rsrp"]),
                "phy_throughput": new_link.cum_phy_bits / elapsed,  # cumulative
            },
            "oai": {
                "snr": float(10.0 * np.log10(float(rx["post_snr_lin"]) + 1e-9)),
                "mac_throughput": new_link.cum_mac_bytes * 8.0 / elapsed,
                "lcid4_throughput": new_link.cum_lcid4_bytes * 8.0 / elapsed,
                "mac_rx_bytes": mac_sdu_bytes,
                "lcid4_rx_bytes": lcid4_bytes,
            },
        }
        outputs = {
            "tb_ok": ok_f,
            "tbs": tbs,
            "mcs": mcs.index,
            "phy_bits_per_s": float(phy_bits),
            "bits": bits,
            "llr": rx["llr"],
            "rx": rx,
        }
        return new_link, outputs, kpms

    # -- adapters ----------------------------------------------------------------

    def make_slot_fn(self, channel_schedule):
        """Adapter for ``ArchesRuntime``: carry = LinkState, input = slot idx.

        ``channel_schedule(slot) -> ChannelConfig`` defines the scenario
        (good/poor phases, paper Fig. 9).
        """

        def slot_fn(active_mode, carry, slot_idx):
            link = carry if carry is not None else LinkState()
            key = jax.random.PRNGKey(np.uint32(slot_idx * 2654435761 % (2**31)))
            ch = channel_schedule(int(slot_idx))
            link, outputs, kpms = self.run_slot(key, active_mode, link, ch)
            return link, outputs, kpms

        return slot_fn


# ---------------------------------------------------------------------------
# Batched multi-UE slot engine
# ---------------------------------------------------------------------------


class DeviceLinkState(NamedTuple):
    """Device-resident per-UE link state (the ``lax.scan`` carry).

    The host-loop ``LinkState`` keeps Python floats and pays a host
    round-trip per slot; this pytree keeps OLLA, link adaptation and the
    cumulative KPM counters on device so the whole slot loop compiles.  All
    leaves carry a leading ``(n_ues,)`` axis.
    """

    reported_snr_db: jax.Array  # (U,) float32
    olla_offset_db: jax.Array  # (U,) float32
    ndi: jax.Array  # (U,) int32
    cum_phy_bits: jax.Array  # (U,) float32 — delivered bits
    cum_mac_bytes: jax.Array  # (U,) float32
    cum_lcid4_bytes: jax.Array  # (U,) float32
    slots: jax.Array  # (U,) int32


def init_device_link(n_ues: int) -> DeviceLinkState:
    """Cold-start state matching ``LinkState()`` defaults, per UE."""
    f = lambda v: jnp.full((n_ues,), v, jnp.float32)
    return DeviceLinkState(
        reported_snr_db=f(20.0),
        olla_offset_db=f(0.0),
        ndi=jnp.ones((n_ues,), jnp.int32),
        cum_phy_bits=f(0.0),
        cum_mac_bytes=f(0.0),
        cum_lcid4_bytes=f(0.0),
        slots=jnp.zeros((n_ues,), jnp.int32),
    )


def normalize_modes(modes, n_slots: int, n_ues: int) -> jax.Array:
    """Broadcast any of {scalar, (S,), (U,), (S, U)} to an (S, U) int32 grid.

    A 1-D vector is per-slot when its length matches ``n_slots`` and per-UE
    when it matches ``n_ues``; when ``n_slots == n_ues`` that is ambiguous
    (the two broadcasts route experts differently), so a 1-D vector is
    rejected — pass the explicit ``(S, U)`` grid instead.
    """
    m = jnp.asarray(modes, jnp.int32)
    if m.ndim == 0:
        return jnp.full((n_slots, n_ues), m, jnp.int32)
    if m.ndim == 1:
        if n_slots == n_ues and m.shape[0] == n_slots:
            raise ValueError(
                f"1-D modes of length {m.shape[0]} are ambiguous when "
                f"n_slots == n_ues == {n_slots}: pass modes[:, None] "
                "(per-slot) or modes[None, :] (per-UE) explicitly"
            )
        if m.shape[0] == n_slots:
            return jnp.broadcast_to(m[:, None], (n_slots, n_ues))
        if m.shape[0] == n_ues:
            return jnp.broadcast_to(m[None, :], (n_slots, n_ues))
    elif m.ndim == 2:
        try:  # exact (S, U) or explicit (S, 1) / (1, U) broadcasts
            return jnp.broadcast_to(m, (n_slots, n_ues))
        except ValueError:
            pass
    raise ValueError(f"modes shape {m.shape} vs (n_slots={n_slots}, n_ues={n_ues})")


def resolve_schedule(
    cfg: SlotConfig, schedule, n_slots: int, n_ues: int
) -> tuple[TdlProfile, ChannelParams]:
    """Lower a scenario to traced per-slot channel params.

    ``schedule`` is either one ``schedule(slot) -> ChannelConfig`` callable
    (all UEs share the conditions; params leaves ``(n_slots, ...)``) or a
    per-UE sequence of them (heterogeneous cell; leaves
    ``(n_slots, n_ues, ...)``).
    """
    if callable(schedule):
        return channel_params_schedule(cfg, schedule, n_slots)
    schedules = list(schedule)
    if len(schedules) != n_ues:
        raise ValueError(
            f"per-UE schedule list has {len(schedules)} entries for "
            f"n_ues={n_ues}"
        )
    return channel_params_ue_schedule(cfg, schedules, n_slots)


class BatchedPuschPipeline:
    """Multi-UE PUSCH slot engine: vmapped stages + scan-compiled slot loop.

    The single-UE ``PuschPipeline`` dispatches O(slots x UEs) host-level
    stage calls and bounces link state through Python floats every slot.
    This engine vmaps TX / channel / RX over a leading UE axis, keeps
    ``DeviceLinkState`` on device, and rolls the slot loop into one
    ``jax.lax.scan`` — the whole campaign becomes a single compiled program.

    Link adaptation goes fully traced: MCS index, modulation order, code
    rate, TBS and code-block counts are device table lookups
    (``repro.phy.mcs``), and the modulation-order-dependent TX/EVM paths are
    computed for every supported QAM order and selected per UE (four cheap
    variants instead of a retrace per MCS).

    The expert bank receives a per-UE ``mode`` vector: different UEs run
    different experts in the same slot, selected by the batched Pallas
    switch kernel (``switch_select_batched_2d``).

    With ``execution_mode=ExecutionMode.GATED`` the AI expert runs only on
    the UEs whose committed mode selects it, compacted into a dense
    capacity-``gated_capacity`` sub-batch inside the scan body (MMSE still
    runs densely as the fail-safe baseline; the fused ``switch_scatter``
    pass un-compacts the AI results over it).  Compute then scales with the
    realized AI share instead of the concurrent cost envelope; UEs past
    capacity fall back to MMSE for that slot and surface in the trajectory's
    ``gated_overflow`` leaf.  Every trajectory additionally carries a per-UE
    ``executed_flops`` leaf (the slot's realized compute, from the bank's
    executed-cost accounting) so campaigns report the compute/energy proxy
    as a function of the expert mix.

    Bit-level outputs (LLRs, TX bits) are a per-``qm`` dynamic shape and are
    deliberately not emitted — the engine produces per-slot-per-UE KPMs and
    TB outcomes (what campaigns and policies consume); use ``PuschPipeline``
    for bit-exact single-link inspection.
    """

    def __init__(
        self,
        cfg: SlotConfig,
        ai_params: Any,
        *,
        net: AiEstimatorConfig = AiEstimatorConfig(),
        execution_mode: ExecutionMode = ExecutionMode.CONCURRENT,
        use_pallas_switch: bool = True,
        gated_capacity: int | None = None,
        fused_gated: bool = False,
        expert_dtype: str = "float32",
        audit_nmse_threshold: float | None = None,
        rms_delay_spread_s: float = 100e-9,
    ):
        self.cfg = cfg
        self.ai_params = ai_params
        self.interpolator = WienerInterpolator.build(
            cfg, rms_delay_spread_s=rms_delay_spread_s
        )
        self._pilots = dmrs_mod.dmrs_sequence(cfg)
        self._tbs_table = jnp.asarray(tbs_table(cfg.n_data_re(), cfg.n_layers))
        self._ncb_table = jnp.asarray(
            n_code_blocks_table(cfg.n_data_re(), cfg.n_layers)
        )
        self._qm_by_mcs = jnp.asarray(QM_BY_MCS)
        self._qm_idx_by_mcs = jnp.asarray(QM_INDEX_BY_MCS)
        self._rate_by_mcs = jnp.asarray(RATE_BY_MCS)

        from repro.phy.ai_estimator import ai_estimate_folded, fold_ai_params

        if expert_dtype not in ("float32", "bfloat16"):
            raise ValueError(
                f"expert_dtype {expert_dtype!r}; one of 'float32', 'bfloat16'"
            )
        # None keeps the f32 path bitwise-identical to pre-dtype engines
        compute_dtype = (
            jnp.bfloat16 if expert_dtype == "bfloat16" else None
        )
        folded = fold_ai_params(ai_params, cfg.n_dmrs_sym)

        def ai_fn(_p, h_ls):
            return ai_estimate_folded(
                folded, h_ls, compute_dtype=compute_dtype
            )

        def mmse_fn(_p, h_ls):
            return self._mmse_from_ls_batched(h_ls)

        gated_fused_apply = None
        if fused_gated:
            if execution_mode is not ExecutionMode.GATED:
                raise ValueError("fused_gated requires GATED execution")
            from repro.kernels.gated_expert import gated_expert_apply

            def gated_fused_apply(idx, src, base, h_ls):
                return gated_expert_apply(
                    idx, src, h_ls, base, folded,
                    compute_dtype=compute_dtype,
                    backend="auto" if use_pallas_switch else "ref",
                )

        self.bank = ExpertBank(
            [
                Expert(name="ai", fn=ai_fn, params=ai_params, flops=net.flops(cfg)),
                Expert(name="mmse", fn=mmse_fn, params=None,
                       flops=estimator_flops(cfg)),
            ],
            default_mode=1,
            execution_mode=execution_mode,
            use_pallas_switch=use_pallas_switch,
            gated_capacity=gated_capacity,
            gated_fused_apply=gated_fused_apply,
            audit_threshold=audit_nmse_threshold,
        )

    def _mmse_from_ls_batched(self, h_ls: jax.Array) -> jax.Array:
        """(U, ant, dmrs_sym, pilot_sc) -> (U, ant, 1, n_sc, dmrs_sym)."""
        from repro.kernels.mmse_interp import mmse_interp

        h_full = mmse_interp(h_ls, self.interpolator.w)
        return jnp.moveaxis(h_full, -2, -1)[:, :, None]

    # -- per-UE stages (vmapped inside slot_step) -----------------------------

    def _ue_pre(self, profile: TdlProfile, p: ChannelParams, snr_db, olla_db, key):
        """Link adaptation + TX + channel + LS for one UE (traced MCS).

        The draws, from the UE's slot key ``key`` (``fold_in(fold_in(root,
        u), s)`` for UE ``u`` in slot ``s``), split four ways into ``k_tx,
        k_ch, k_n, k_crc``:

        * ``k_tx``: payload bits, ``bernoulli(k_tx, 0.5, shape)`` with shape
          ``(n_data_re * 8,)``, or ``(n_layers, n_data_re * 8)`` with more
          than one layer; at modulation order ``q`` a layer takes the first
          ``n_data_re * q`` bits of its row;
        * ``k_ch``: the channel (``simulate_slot_channel_traced``): split
          three ways into the UE's TDL response, drawn for shape
          ``(n_ant, n_layers, taps)``, the interference symbols and the
          interferer's response, drawn for the same shape of which layer 0
          is kept;
        * ``k_n``: the receiver noise;
        * ``k_crc``: the transport block's decoding draw.
        """
        cfg = self.cfg
        with tracing.stage(tracing.TX):
            k_tx, k_ch, k_n, k_crc = jax.random.split(key, 4)

            mcs_idx = select_mcs_index(snr_db + olla_db)
            qm_idx = jnp.take(self._qm_idx_by_mcs, mcs_idx)
            qm = jnp.take(self._qm_by_mcs, mcs_idx).astype(jnp.float32)
            code_rate = jnp.take(self._rate_by_mcs, mcs_idx)
            tbs = jnp.take(self._tbs_table, mcs_idx).astype(jnp.float32)

            # TX for every supported modulation order; select per UE.  Bits
            # are drawn once at the widest order and prefix-sliced, so the
            # payload for a given (key, qm) is deterministic.
            n_re = cfg.n_data_re()
            shape = (n_re * max(QM_VALUES),)
            if cfg.n_layers > 1:
                shape = (cfg.n_layers,) + shape
            bits = jax.random.bernoulli(k_tx, 0.5, shape).astype(jnp.uint8)
            syms_all = jnp.stack(
                [qam.modulate(bits[..., : n_re * q], q) for q in QM_VALUES],
                axis=0,
            )
            syms = jnp.take(syms_all, qm_idx, axis=0)
            tx_grid = dmrs_mod.map_slot_grid(cfg, syms, self._pilots)
        with tracing.stage(tracing.CHANNEL):
            fields = simulate_slot_channel_traced(k_ch, cfg, profile, p)
            rx_grid = apply_channel(k_n, tx_grid, fields)
        with tracing.stage(tracing.RX):
            h_ls = ls_estimate(cfg, rx_grid, self._pilots)
        return {
            "mcs_idx": mcs_idx,
            "qm_idx": qm_idx,
            "qm": qm,
            "code_rate": code_rate,
            "tbs": tbs,
            "syms": syms,
            "rx_grid": rx_grid,
            "h_ls": h_ls,
            "noise_var": fields["noise_var"],
            "k_crc": k_crc,
        }

    def _ue_post(self, link: DeviceLinkState, pre: dict, h_sel: jax.Array):
        """Equalize + KPMs + OLLA for one UE (scalar link-state leaves)."""
        cfg = self.cfg
        with tracing.stage(tracing.RX):
            x_hat, _ = mmse_equalize(cfg, pre["rx_grid"], h_sel, pre["noise_var"])
            data_hat = dmrs_mod.extract_data_re(cfg, x_hat[None])[0]
        with tracing.stage(tracing.KPM):
            return self._ue_kpms(link, pre, h_sel, data_hat)

    def _post_layers(self, link: DeviceLinkState, pre: dict, h_sel: jax.Array):
        """``_ue_post`` for every UE at once with more than one layer: the
        ``lmmse_detect`` kernel runs over the whole UE batch."""
        cfg = self.cfg
        with tracing.stage(tracing.RX):
            x_hat = lmmse_equalize(
                cfg, pre["rx_grid"], h_sel, pre["noise_var"]
            )
            data_hat = dmrs_mod.extract_data_re(cfg, x_hat)  # (U, l, n_re)
        with tracing.stage(tracing.KPM):
            return jax.vmap(self._ue_kpms)(link, pre, h_sel, data_hat)

    def _ue_kpms(self, link: DeviceLinkState, pre: dict, h_sel, data_hat):
        """EVM, TB model, OLLA and the KPM report for one UE."""
        cfg = self.cfg
        # decision-directed EVM per modulation order, selected by qm_idx
        # (per-axis PAM nearest — equivalent to the host pipeline's
        # constellation argmin on square Gray QAM, O(1) per symbol, picked
        # from the table's per-axis values by selects, not gathers)
        dd_errs, sig_pows = [], []
        for q in QM_VALUES:
            nearest = qam.nearest_point(data_hat, q)
            dd_errs.append(jnp.mean(jnp.abs(data_hat - nearest) ** 2))
            sig_pows.append(jnp.mean(jnp.abs(nearest) ** 2))
        dd_err = jnp.take(jnp.stack(dd_errs), pre["qm_idx"])
        sig_pow = jnp.take(jnp.stack(sig_pows), pre["qm_idx"])
        sinr_meas = sig_pow / jnp.maximum(dd_err, 1e-9)

        # genie per-RE SINR (MIESM TB model), as in the host pipeline; with
        # more than one layer, PRB windows of each layer, pooled
        genie_err = jnp.abs(data_hat - pre["syms"]) ** 2
        n = genie_err.shape[-1] - genie_err.shape[-1] % 12
        smoothed = jnp.mean(
            genie_err[..., :n].reshape(genie_err.shape[:-1] + (-1, 12)), axis=-1
        ).reshape(-1)
        genie_sinr = 1.0 / jnp.maximum(smoothed, 1e-9)

        ok = tb_success_dynamic(
            genie_sinr, pre["qm"], pre["code_rate"], key=pre["k_crc"]
        )
        ok_f = ok.astype(jnp.float32)
        tbs = pre["tbs"]
        slot_dur = cfg.slot_duration_s
        phy_bits = jnp.where(ok, tbs / slot_dur, 0.0)
        rsrp = jnp.mean(jnp.abs(h_sel) ** 2)

        tb_bytes = tbs / 8.0
        mac_sdu_bytes = jnp.maximum(tb_bytes - _MAC_HEADER_BYTES, 0.0) * ok_f
        lcid4_bytes = (
            jnp.maximum(mac_sdu_bytes - _RLC_HEADER_BYTES, 0.0) * _LCID4_FRACTION
        )

        olla = link.olla_offset_db + jnp.where(ok, _OLLA_UP_DB, -_OLLA_DOWN_DB)
        olla = jnp.clip(olla, -_OLLA_CLAMP_DB, _OLLA_CLAMP_DB)
        snr_db = 10.0 * jnp.log10(sinr_meas + 1e-9)

        new_link = DeviceLinkState(
            reported_snr_db=snr_db,
            olla_offset_db=olla,
            ndi=ok.astype(jnp.int32),
            cum_phy_bits=link.cum_phy_bits + phy_bits * slot_dur,
            cum_mac_bytes=link.cum_mac_bytes + mac_sdu_bytes,
            cum_lcid4_bytes=link.cum_lcid4_bytes + lcid4_bytes,
            slots=link.slots + 1,
        )
        elapsed = new_link.slots.astype(jnp.float32) * slot_dur
        kpms = {
            "aerial": {
                "code_rate": pre["code_rate"],
                "sinr": snr_db,
                "qam_order": pre["qm"],
                "mcs_index": pre["mcs_idx"].astype(jnp.float32),
                "tb_size": tbs * ok_f,
                "n_code_blocks": jnp.take(self._ncb_table, pre["mcs_idx"]).astype(
                    jnp.float32
                )
                * ok_f,
                "pdu_length": tb_bytes * ok_f,
                "ndi": ok_f,
                "rsrp": rsrp,
                "phy_throughput": new_link.cum_phy_bits / elapsed,
            },
            "oai": {
                "snr": snr_db,
                "mac_throughput": new_link.cum_mac_bytes * 8.0 / elapsed,
                "lcid4_throughput": new_link.cum_lcid4_bytes * 8.0 / elapsed,
                "mac_rx_bytes": mac_sdu_bytes,
                "lcid4_rx_bytes": lcid4_bytes,
            },
        }
        outputs = {
            "tb_ok": ok_f,
            "tbs": tbs,
            "mcs": pre["mcs_idx"],
            "phy_bits_per_s": phy_bits,
            "kpms": kpms,
        }
        return new_link, outputs

    def _corrupt_and_screen(self, out, h_sel, modes, corrupt, faults):
        """Fault injection + in-scan health screen on the selected estimate.

        ``corrupt (U,)`` flags this slot's expert-output corruption burst;
        it lands only on UEs actually *served* by the AI expert (mode 0 —
        overflow/audit-reverted UEs already hold the fail-safe output).
        The injected error is NaN, Inf, or a scaled copy per
        ``FaultSpec.corruption_kind``.  The screen then checks every
        AI-served UE's output for finiteness — independently of the
        injection, so a naturally diverged expert trips it too — and
        reverts tripped UEs to the densely-computed fail-safe baseline for
        this slot, returning the per-UE trip flags.  A scaled-error
        corruption stays finite by design: it flows downstream and is the
        breaker's blind spot unless the NMSE audit catches it.

        With an all-False ``corrupt`` mask and finite expert outputs every
        select here is the identity — the zero-fault bitwise contract.
        """
        srv = (
            out.served_by
            if out.served_by is not None
            else jnp.asarray(modes, jnp.int32)
        )
        hit = jnp.logical_and(jnp.asarray(corrupt), srv == 0)

        def inject(x):
            if faults.corruption_kind == "nan":
                bad = jnp.full_like(x, jnp.nan)
            elif faults.corruption_kind == "inf":
                bad = jnp.full_like(x, jnp.inf)
            else:
                bad = x * jnp.asarray(faults.corruption_scale, x.dtype)
            return jnp.where(
                hit.reshape(hit.shape + (1,) * (x.ndim - 1)), bad, x
            )

        h_sel = jax.tree.map(inject, h_sel)
        finite = None
        for leaf in jax.tree.leaves(h_sel):
            f = jnp.all(jnp.isfinite(leaf).reshape(leaf.shape[0], -1), axis=1)
            finite = f if finite is None else jnp.logical_and(finite, f)
        tripped = jnp.logical_and(srv == 0, jnp.logical_not(finite))
        if out.baseline is None:
            raise ValueError(
                "fault injection needs a batched bank output carrying the "
                "fail-safe baseline (BankOutput.baseline)"
            )
        h_sel = jax.tree.map(
            lambda s, b: jnp.where(
                tripped.reshape(tripped.shape + (1,) * (s.ndim - 1)), b, s
            ),
            h_sel,
            out.baseline,
        )
        return h_sel, tripped.astype(jnp.int32)

    # -- one batched slot ------------------------------------------------------

    def _slot_core(
        self,
        profile: TdlProfile,
        link: DeviceLinkState,
        modes: jax.Array,
        keys: jax.Array,
        p: ChannelParams,
        rho: jax.Array | None = None,
        cell_of_ue: jax.Array | None = None,
        cell_params: CellParams | None = None,
        cell_axis: str | None = None,
        active: jax.Array | None = None,
        faults=None,
        corrupt: jax.Array | None = None,
    ):
        if active is not None:
            # streaming bank-slot mask: detached lanes run the fail-safe
            # expert (so they never claim gated compaction capacity), their
            # link state freezes and their outputs/KPMs/executed-FLOPs zero
            # below.  With an all-ones mask every select is the identity, so
            # a fully-attached slot is bitwise-equal to the unmasked path.
            act = jnp.asarray(active)
            with tracing.stage(tracing.EXPERTS):
                modes = jnp.where(
                    act, jnp.asarray(modes, jnp.int32),
                    jnp.int32(self.bank.default_mode),
                )
            if cell_of_ue is not None:
                # empty lanes must not contribute to the per-cell mean load
                with tracing.stage(tracing.CHANNEL):
                    p = p._replace(interf_on=jnp.where(act, p.interf_on, 0.0))
        if cell_of_ue is not None:
            # multi-cell topology: fold per-cell offsets + inter-cell
            # coupling into this slot's per-UE knobs.  Under shard_map,
            # ``cell_axis`` names the UE mesh axis and the per-cell mean is
            # the scan's only cross-device collective.
            if jnp.ndim(p.noise_var) != 1:
                raise ValueError(
                    "cell coupling needs per-UE ChannelParams leaves; "
                    "broadcast_params_to_ues the schedule first"
                )
            with tracing.stage(tracing.CHANNEL):
                p = apply_cell_coupling(
                    p, cell_of_ue, cell_params, axis_name=cell_axis
                )
        if jnp.ndim(p.noise_var) == 1:
            # per-UE heterogeneous conditions: params carry a (U,) axis
            pre = jax.vmap(
                lambda snr, olla, key, pu: self._ue_pre(profile, pu, snr, olla, key)
            )(link.reported_snr_db, link.olla_offset_db, keys, p)
        else:
            pre = jax.vmap(
                lambda snr, olla, key: self._ue_pre(profile, p, snr, olla, key)
            )(link.reported_snr_db, link.olla_offset_db, keys)
        n_ues = keys.shape[0]
        cfg = self.cfg
        with tracing.stage(tracing.EXPERTS):
            # (antenna, layer) ports folded into the antenna axis and back;
            # both reshapes are the identity at one layer
            h_ls = pre["h_ls"].reshape((n_ues, -1) + pre["h_ls"].shape[-2:])
            h_sel, extras = self._bank_core(h_ls, modes, keys, n_ues,
                                            rho, faults, corrupt)
            h_sel = h_sel.reshape(
                (n_ues, cfg.n_ant, cfg.n_layers) + h_sel.shape[3:]
            )
        if cfg.n_layers == 1:
            new_link, outputs = jax.vmap(self._ue_post)(link, pre, h_sel)
        else:
            new_link, outputs = self._post_layers(link, pre, h_sel)
        outputs.update(extras)
        if active is not None:
            # detached lanes: state frozen, every output/KPM leaf zeroed —
            # they carry no throughput, no cost, no overflow, no telemetry
            with tracing.stage(tracing.KPM):
                new_link = jax.tree.map(
                    lambda n, o: jnp.where(act, n, o), new_link, link
                )
                outputs = jax.tree.map(
                    lambda x: jnp.where(
                        act.reshape(act.shape + (1,) * (x.ndim - 1)),
                        x, jnp.zeros_like(x),
                    ),
                    outputs,
                )
        return new_link, outputs

    def _bank_core(self, h_ls, modes, keys, n_ues, rho, faults, corrupt):
        """The expert bank on the slot's LS estimates: the selected estimate
        per UE and the per-UE cost and fallback leaves."""
        if rho is None:
            out = self.bank(jnp.asarray(modes, jnp.int32), h_ls)
            h_sel = out.selected
            exec_flops = self.bank.executed_flops_per_ue(out)
            overflow = (
                out.overflow.astype(jnp.int32)
                if out.overflow is not None
                else jnp.zeros((n_ues,), jnp.int32)
            )
            audit_tripped = (
                out.audit_tripped.astype(jnp.int32)
                if out.audit_tripped is not None
                else jnp.zeros((n_ues,), jnp.int32)
            )
            health_tripped = jnp.zeros((n_ues,), jnp.int32)
            if faults is not None:
                h_sel, health_tripped = self._corrupt_and_screen(
                    out, h_sel, modes, corrupt, faults
                )
        else:
            # methodology stage 1 (paper Fig. 3): MMSE only, AWGN injected
            # at node 2c — no switching, no AI in the loop.  ``rho`` is a
            # per-UE intensity vector, so one batched slot evaluates a whole
            # rho grid at once.
            h_mmse = self._mmse_from_ls_batched(h_ls)
            pkeys = jax.vmap(lambda k: jax.random.fold_in(k, 0x9e7))(keys)
            h_sel = jax.vmap(perturb_estimate)(
                h_mmse, jnp.asarray(rho, jnp.float32), pkeys
            )
            exec_flops = jnp.full(
                (n_ues,), self.bank.experts[self.bank.default_mode].flops,
                jnp.float32,
            )
            overflow = jnp.zeros((n_ues,), jnp.int32)
            audit_tripped = jnp.zeros((n_ues,), jnp.int32)
            health_tripped = jnp.zeros((n_ues,), jnp.int32)
        return h_sel, {
            "executed_flops": exec_flops,
            "gated_overflow": overflow,
            "audit_tripped": audit_tripped,
            "health_tripped": health_tripped,
        }

    @partial(jax.jit, static_argnames=("self", "profile"))
    def slot_step(
        self,
        profile: TdlProfile,
        link: DeviceLinkState,
        modes: jax.Array,
        keys: jax.Array,
        p: ChannelParams,
    ):
        """One compiled multi-UE slot. ``modes``/``keys`` carry the UE axis."""
        return self._slot_core(profile, link, modes, keys, p)

    @partial(jax.jit, static_argnames=("self", "profile", "cell_axis", "faults"))
    def _run_scan(
        self, profile, link0, ue_keys, modes, params,
        cell_of_ue=None, cell_params=None, *, cell_axis=None,
        slot0=None, active=None, faults=None, corrupt=None,
    ):
        # ``slot0`` (traced) starts the carry's slot counter at a global
        # slot index, so an epoch-chunked streaming campaign folds the same
        # per-(UE, slot) PRNG stream a monolithic run folds; ``active`` is
        # the streaming bank-slot mask (see ``_slot_core``).  Both default
        # to the monolithic behaviour.  ``faults`` (static) + ``corrupt``
        # ((S, U), traced, an extra scan operand) enable the open-loop
        # slice of fault injection: expert-output corruption + health
        # screen (decision/telemetry faults only exist in the closed loop).
        start = jnp.int32(0) if slot0 is None else jnp.asarray(slot0, jnp.int32)

        def step(carry, xs):
            link, slot_idx = carry
            if corrupt is None:
                (modes_s, p), cor_s = xs, None
            else:
                modes_s, p, cor_s = xs
            keys = jax.vmap(lambda k: jax.random.fold_in(k, slot_idx))(ue_keys)
            link, out = self._slot_core(
                profile, link, modes_s, keys, p,
                cell_of_ue=cell_of_ue, cell_params=cell_params,
                cell_axis=cell_axis, active=active,
                faults=faults, corrupt=cor_s,
            )
            return (link, slot_idx + 1), out

        xs = (modes, params) if corrupt is None else (modes, params, corrupt)
        (link, _), traj = jax.lax.scan(step, (link0, start), xs)
        return link, traj

    @partial(
        jax.jit,
        static_argnames=("self", "profile", "cell_axis", "faults"),
        donate_argnames=("link0",),
    )
    def _run_scan_streaming(
        self, profile, link0, ue_keys, modes, params,
        cell_of_ue=None, cell_params=None, *, cell_axis=None,
        slot0=None, active=None, faults=None, corrupt=None,
    ):
        # Streaming-only entry: identical program to ``_run_scan`` but the
        # carry buffer is donated — segment k's post-scan link state is dead
        # the moment it has been (copied for checkpointing and) gathered
        # into segment k+1's carry, so the steady-state loop reuses one
        # allocation instead of growing one per segment.  Callers that need
        # the pre-donation value must ``jnp.copy`` it first.
        return self._run_scan(
            profile, link0, ue_keys, modes, params,
            cell_of_ue, cell_params, cell_axis=cell_axis,
            slot0=slot0, active=active, faults=faults, corrupt=corrupt,
        )

    @partial(jax.jit, static_argnames=("self", "profile", "cell_axis"))
    def _run_perturbed_scan(
        self, profile, link0, ue_keys, rho, params,
        cell_of_ue=None, cell_params=None, *, cell_axis=None,
    ):
        def step(carry, p):
            link, slot_idx = carry
            keys = jax.vmap(lambda k: jax.random.fold_in(k, slot_idx))(ue_keys)
            modes = jnp.ones((ue_keys.shape[0],), jnp.int32)  # MMSE-only stage
            link, out = self._slot_core(
                profile, link, modes, keys, p, rho=rho,
                cell_of_ue=cell_of_ue, cell_params=cell_params,
                cell_axis=cell_axis,
            )
            return (link, slot_idx + 1), out

        (link, _), traj = jax.lax.scan(step, (link0, jnp.int32(0)), params)
        return link, traj

    def run_perturbed(
        self,
        schedule: Callable[[int], ChannelConfig],
        rho: jax.Array,
        *,
        n_slots: int,
        key: jax.Array | None = None,
        ue_keys: jax.Array | None = None,
    ) -> tuple[DeviceLinkState, dict[str, Any]]:
        """Methodology stage-1 campaign: per-UE perturbation intensities.

        The host harness loops rho values one slot at a time; here the whole
        rho grid rides the UE axis — UE ``u`` runs the MMSE-only pipeline
        with AWGN injected at intensity ``rho[u]`` every slot, and the whole
        ``n_slots x len(rho)`` sweep is one compiled scan.  PRNG derivation
        matches ``run`` (per-UE fold_in), with an independent stream for the
        injected noise.
        """
        rho = jnp.asarray(rho, jnp.float32)
        n_ues = rho.shape[0]
        if key is None:
            key = jax.random.PRNGKey(0)
        profile, params = resolve_schedule(self.cfg, schedule, n_slots, n_ues)
        if ue_keys is None:
            ue_keys = jax.vmap(lambda u: jax.random.fold_in(key, u))(
                jnp.arange(n_ues)
            )
        elif ue_keys.shape[0] != n_ues:
            raise ValueError(f"ue_keys {ue_keys.shape} vs rho {rho.shape}")
        link = init_device_link(n_ues)
        return self._run_perturbed_scan(profile, link, ue_keys, rho, params)

    # -- closed-loop scan ------------------------------------------------------

    def _closed_step(
        self, profile, sw_cfg, policy, ue_keys, link, sw, slot_idx, p,
        cell_of_ue=None, cell_params=None, cell_axis=None, active=None,
        faults=None, fault_s=None,
    ):
        """One closed-loop slot: boundary-committed modes in, decision out.

        ``sw.active_mode`` (committed at the previous boundary) drives the
        expert bank; this slot's KPMs are pushed into the device window, the
        policy decides, and the register/boundary update prepares slot
        ``slot_idx + 1``.  Shared verbatim by the scan body and the
        python-loop debug path so the two are the same program per slot.

        ``active`` (streaming bank-slot mask) freezes a detached lane's
        whole control-loop state — KPM ring, register, hysteresis streak
        and switch counter — so no telemetry accumulates while detached
        (reattachment cold-starts the row at the segment boundary; the
        streaming driver owns that re-pack).

        ``faults`` (static ``FaultSpec``) + ``fault_s`` (this slot's
        ``(decision_valid, corrupt, telemetry_valid)`` ``(U,)`` masks)
        inject the degradation ladder: quarantined UEs execute the
        fail-safe expert (never claiming gated capacity) while the control
        register keeps deciding, the expert output is corrupted/screened in
        ``_slot_core``, the switch update drops lost decisions and masked
        telemetry, the boundary runs the TTL decay, and the trip flags
        feed the circuit breaker last.  The ``quarantined`` leaf records
        the overlay as of the *start* of the slot.
        """
        with tracing.stage(tracing.TX):
            keys = jax.vmap(lambda k: jax.random.fold_in(k, slot_idx))(ue_keys)
        committed = sw.active_mode
        with tracing.stage(tracing.DECIDE):
            if faults is not None:
                quarantined = (sw.quarantine > 0)
                exec_modes = jnp.where(
                    quarantined, jnp.int32(sw_cfg.default_mode), committed
                )
                dv_s, cor_s, tv_s = fault_s
            else:
                quarantined = jnp.zeros_like(committed, bool)
                exec_modes = committed
                dv_s = cor_s = tv_s = None
        link, out = self._slot_core(
            profile, link, exec_modes, keys, p,
            cell_of_ue=cell_of_ue, cell_params=cell_params,
            cell_axis=cell_axis, active=active,
            faults=faults, corrupt=cor_s,
        )
        with tracing.stage(tracing.DECIDE):
            new_sw, out = self._decide(
                sw_cfg, policy, sw, slot_idx, out, committed, quarantined,
                active, faults, dv_s, tv_s,
            )
        return link, new_sw, out

    def _decide(self, sw_cfg, policy, sw, slot_idx, out, committed,
                quarantined, active, faults, dv_s, tv_s):
        """The slot's KPMs into the window, the policy's decision, and the
        register, boundary and breaker updates for the next slot."""
        vecs = trajectory_kpm_matrix(out["kpms"], sw_cfg.feature_names)
        decide = (
            True
            if sw_cfg.period_slots == 1
            else (slot_idx % jnp.int32(sw_cfg.period_slots)) == 0
        )
        new_sw, raw = switch_update(
            sw, vecs, policy, sw_cfg, decide=decide,
            decision_valid=dv_s, telemetry_valid=tv_s,
        )
        out = dict(
            out,
            active_mode=committed,
            raw_decision=raw,
            pending_mode=new_sw.pending_mode,
            quarantined=quarantined.astype(jnp.int32),
        )
        if faults is not None:
            new_sw = switch_boundary(
                new_sw, ttl_slots=sw_cfg.ttl_slots,
                fail_safe_mode=sw_cfg.default_mode,
            )
            trip = jnp.logical_or(
                out["health_tripped"] > 0, out["audit_tripped"] > 0
            )
            new_sw = breaker_update(new_sw, trip, slot_idx, faults)
        else:
            new_sw = switch_boundary(new_sw)
        if active is not None:
            act = jnp.asarray(active)
            new_sw = jax.tree.map(
                lambda n, o: jnp.where(
                    act.reshape(act.shape + (1,) * (n.ndim - 1)), n, o
                ),
                new_sw, sw,
            )
            out = dict(
                out,
                active_mode=jnp.where(act, committed, 0),
                raw_decision=jnp.where(act, raw, 0),
                pending_mode=jnp.where(act, out["pending_mode"], 0),
                quarantined=jnp.where(act, out["quarantined"], 0),
            )
        return new_sw, out

    @partial(jax.jit, static_argnames=(
        "self", "profile", "sw_cfg", "cell_axis", "faults"
    ))
    def _run_closed_scan(
        self, profile, sw_cfg, link0, sw0, ue_keys, params, policy,
        cell_of_ue=None, cell_params=None, *, cell_axis=None,
        slot0=None, active=None, faults=None, fault_masks=None,
    ):
        # ``faults`` (static) + ``fault_masks`` (the resolved
        # ``(decision_valid, corrupt, telemetry_valid)`` triple of (S, U)
        # arrays, extra scan operands) enable the full degradation ladder.
        start = jnp.int32(0) if slot0 is None else jnp.asarray(slot0, jnp.int32)

        def step(carry, xs):
            link, sw, slot_idx = carry
            if fault_masks is None:
                p, fs = xs, None
            else:
                p, fs = xs
            link, sw, out = self._closed_step(
                profile, sw_cfg, policy, ue_keys, link, sw, slot_idx, p,
                cell_of_ue, cell_params, cell_axis, active,
                faults, fs,
            )
            return (link, sw, slot_idx + 1), out

        xs = params if fault_masks is None else (params, fault_masks)
        (link, sw, _), traj = jax.lax.scan(step, (link0, sw0, start), xs)
        return link, sw, traj

    @partial(
        jax.jit,
        static_argnames=("self", "profile", "sw_cfg", "cell_axis", "faults"),
        donate_argnames=("link0", "sw0"),
    )
    def _run_closed_scan_streaming(
        self, profile, sw_cfg, link0, sw0, ue_keys, params, policy,
        cell_of_ue=None, cell_params=None, *, cell_axis=None,
        slot0=None, active=None, faults=None, fault_masks=None,
    ):
        # Streaming-only entry mirroring ``_run_scan_streaming``: donates
        # both carries (link + switch state).  See that method's note on
        # liveness — copy before donating if the old value is still needed.
        return self._run_closed_scan(
            profile, sw_cfg, link0, sw0, ue_keys, params, policy,
            cell_of_ue, cell_params, cell_axis=cell_axis,
            slot0=slot0, active=active, faults=faults,
            fault_masks=fault_masks,
        )

    def _closed_slot_step(
        self, profile, sw_cfg, link, sw, slot_idx, ue_keys, p, policy,
        fault_s=None, *, faults=None,
    ):
        """One compiled closed-loop slot (python-loop debug/benchmark path),
        called under the ``arches.slot.dispatch`` host span.  Its ``out`` is
        a ``SlotOutputs`` view of two slabs (``repro.phy.slot_outputs``)."""
        with jax.profiler.TraceAnnotation(tracing.SLOT_DISPATCH):
            return _closed_slot_step(
                self, profile, sw_cfg, link, sw, slot_idx, ue_keys, p,
                policy, fault_s, faults=faults,
            )

    def run_closed_loop(
        self,
        schedule: Callable[[int], ChannelConfig],
        policy: DevicePolicy,
        sw_cfg: SwitchConfig,
        *,
        n_slots: int,
        n_ues: int,
        key: jax.Array | None = None,
        ue_keys: jax.Array | None = None,
        use_scan: bool = True,
        faults=None,
    ):
        """Run a campaign with the switching decision inside the scan.

        Instead of an open-loop mode schedule, each slot's ``(n_ues,)`` mode
        vector comes from a ``DeviceSwitchState`` riding the scan carry: the
        previous slot's KPMs (rolling window mean over
        ``sw_cfg.window_slots`` slots) feed the exported ``policy`` tables,
        and the decision is committed to the switch register, taking effect
        at the next slot boundary — the whole loop is one ``lax.scan`` with
        zero host involvement.  ``sw_cfg.period_slots`` sets the dApp-style
        decision periodicity: the policy is consulted every ``period_slots``
        slots and the register holds in between.  PRNG derivation matches ``run`` exactly, so
        a closed-loop campaign whose decided modes happen to equal an
        open-loop grid produces the identical trajectory.

        Returns ``(final_link, final_switch_state, trajectory)``;
        the trajectory adds ``active_mode`` / ``raw_decision`` /
        ``pending_mode`` / ``quarantined`` leaves (all ``(n_slots, n_ues)``
        int32) to the leaves ``run`` emits.

        ``faults`` (a ``FaultSpec``) injects the full degradation ladder:
        decision loss -> TTL decay, expert corruption -> health screen ->
        circuit breaker, telemetry loss -> window masking.  The spec is
        resolved to dense masks here so the host oracle's own resolution
        consumes identical arrays.
        """
        if key is None:
            key = jax.random.PRNGKey(0)
        profile, params = resolve_schedule(self.cfg, schedule, n_slots, n_ues)
        if ue_keys is None:
            ue_keys = jax.vmap(lambda u: jax.random.fold_in(key, u))(
                jnp.arange(n_ues)
            )
        elif ue_keys.shape[0] != n_ues:
            raise ValueError(f"ue_keys {ue_keys.shape} vs n_ues {n_ues}")
        fault_masks = None
        if faults is not None:
            rf = faults.resolve(n_slots, n_ues)
            fault_masks = (
                jnp.asarray(rf.decision_valid),
                jnp.asarray(rf.corrupt),
                jnp.asarray(rf.telemetry_valid),
            )
        link = init_device_link(n_ues)
        sw = init_device_switch(
            n_ues, len(sw_cfg.feature_names), sw_cfg, faults
        )
        if use_scan:
            return self._run_closed_scan(
                profile, sw_cfg, link, sw, ue_keys, params, policy,
                faults=faults, fault_masks=fault_masks,
            )

        outs = []
        for s in range(n_slots):
            p = jax.tree.map(lambda x: x[s], params)
            fs = (
                None
                if fault_masks is None
                else tuple(m[s] for m in fault_masks)
            )
            link, sw, out = self._closed_slot_step(
                profile, sw_cfg, link, sw, jnp.int32(s), ue_keys, p, policy,
                fs, faults=faults,
            )
            outs.append(out)
        return link, sw, stack(outs)

    # -- campaign driver -------------------------------------------------------

    def run(
        self,
        schedule: Callable[[int], ChannelConfig],
        modes,
        *,
        n_slots: int,
        n_ues: int,
        key: jax.Array | None = None,
        ue_keys: jax.Array | None = None,
        use_scan: bool = True,
        faults=None,
    ) -> tuple[DeviceLinkState, dict[str, Any]]:
        """Run an ``n_slots x n_ues`` campaign.

        Args:
          schedule: ``schedule(slot) -> ChannelConfig`` scenario (one TDL
            profile across the run; conditions may change per slot), or a
            per-UE sequence of such schedules (heterogeneous cell — UE
            ``u`` follows ``schedule[u]``; all share one TDL profile).
          modes: expert selection — scalar, per-slot ``(S,)``, per-UE
            ``(U,)`` or full ``(S, U)`` grid.
          key: root PRNG key; UE ``u`` in slot ``s`` consumes
            ``fold_in(fold_in(key, u), s)``, so per-UE streams are
            independent of the batch composition (a UE's trajectory is
            identical whether it runs alone or in a batch).
          ue_keys: explicit ``(n_ues,)`` per-UE base keys, overriding the
            ``fold_in(key, u)`` derivation — lets a batched run be compared
            against independent single-UE runs with the same keys.
          use_scan: compiled ``lax.scan`` loop (default) or a per-slot
            Python loop over the same jitted step (debug/benchmark baseline).
          faults: optional ``FaultSpec`` — the open-loop slice of fault
            injection (expert-output corruption + in-scan health screen;
            decision/telemetry faults only exist in the closed loop).
            Requires ``use_scan=True``.

        Returns:
          ``(final_link, trajectory)`` where every trajectory leaf is
          ``(n_slots, n_ues)``.
        """
        if key is None:
            key = jax.random.PRNGKey(0)
        profile, params = resolve_schedule(self.cfg, schedule, n_slots, n_ues)
        modes = normalize_modes(modes, n_slots, n_ues)
        if ue_keys is None:
            ue_keys = jax.vmap(lambda u: jax.random.fold_in(key, u))(
                jnp.arange(n_ues)
            )
        elif ue_keys.shape[0] != n_ues:
            raise ValueError(f"ue_keys {ue_keys.shape} vs n_ues {n_ues}")
        corrupt = None
        if faults is not None:
            if not use_scan:
                raise ValueError("fault injection needs use_scan=True")
            corrupt = jnp.asarray(faults.resolve(n_slots, n_ues).corrupt)
        link = init_device_link(n_ues)
        if use_scan:
            return self._run_scan(
                profile, link, ue_keys, modes, params,
                faults=faults, corrupt=corrupt,
            )

        outs = []
        for s in range(n_slots):
            keys = jax.vmap(lambda k: jax.random.fold_in(k, s))(ue_keys)
            p = jax.tree.map(lambda x: x[s], params)
            link, out = self.slot_step(profile, link, modes[s], keys, p)
            outs.append(out)
        traj = jax.tree.map(lambda *ls: jnp.stack(ls, 0), *outs)
        return link, traj


@partial(jax.jit, static_argnames=("self", "profile", "sw_cfg", "faults"))
def _closed_slot_step(
    self, profile, sw_cfg, link, sw, slot_idx, ue_keys, p, policy,
    fault_s=None, *, faults=None,
):
    """The compiled body of ``BatchedPuschPipeline._closed_slot_step``; the
    profiler names its program ``jit__closed_slot_step``.  ``out`` leaves
    packed into one slab per dtype: the runtime allocates each output
    array every call, so 2 slabs in place of 27 leaves save 25."""
    link, sw, out = self._closed_step(
        profile, sw_cfg, policy, ue_keys, link, sw, slot_idx, p,
        faults=faults, fault_s=fault_s,
    )
    with tracing.stage(tracing.DECIDE):
        return link, sw, SlotOutputs(*pack(out))
