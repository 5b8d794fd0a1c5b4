#!/usr/bin/env python3
"""Smoke test of the ARCHES main path on a TPU, at the paper's cell width.

    python3 chip_smoke.py                # one chip: phases 1-5 below
    python3 chip_smoke.py --four-chips   # four chips: the sharded campaign only

Runs from the root of a checkout (it puts ``src`` on the path itself).  The
cell is the paper's X5G carrier, the ``SlotConfig`` default: 106 PRB, 4 RX,
one layer, 30 kHz, DMRS on symbols {0, 5, 10}; the AI expert is the
benchmark's 32-channel, 4-block ResNet with random weights from the spec's
``params_seed``.  Phases, in one process:

1. device check — a TPU or nothing: any other platform exits 2 before any
   work, with no CPU fallback;
2. closed loop through ``ArchesSession.run()`` (16 UEs, 48 slots,
   ``good_poor_good`` with the poor window inside the horizon, a depth-2
   tree policy, a gated bank): device modes equal the host replay bitwise,
   both experts serve, every KPM is finite, and the lowered slot program
   holds a ``tpu_custom_call`` for ``mmse_interp``, the switch kernel and
   ``tree_infer`` (so no kernel fell back to interpret mode or jnp);
3. both experts on one slot's LS input against plain references on the
   CPU at ``highest`` matmul precision (for the AI expert, its learned
   correction on top of the baseline interpolation), plus a probe of the
   precision each kind of on-chip matmul runs at;
4. the same campaign with the fused gated kernel (``fused=True``): bitwise
   replay, the kernel present, KPMs within ``KPM_REL_TOL`` of phase 2;
5. the same campaign with a ``ChurnSchedule`` submitted over HTTP to a
   ``CampaignService`` (ephemeral port, temporary state dir), run in 3
   segments and polled to ``completed``, with its segment telemetry in the
   export ring.

``--four-chips`` runs only the multi-cell layout: the phase-2 campaign as 4
cells on 4 shards (``TopologySpec(n_cells=4, n_shards=4)``) against the
same 4-cell campaign unsharded on device 0 (``n_shards=1``), checking 4
real shards, bitwise replay of both runs, exactly one ``all-reduce`` and no
other collective in the sharded program, and KPMs within ``KPM_REL_TOL``.

Compile and warm wall times are printed per phase for information; they are
not benchmark metrics.  Any failed check exits non-zero, and only a run
whose every check passed prints the last line
``{"ok": true, "device": {...}}``.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import re
import sys
import tempfile
import time
import urllib.request

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

#: relative Frobenius error allowed between the on-chip MMSE estimate and
#: its CPU reference at ``highest`` precision.  XLA and Mosaic run f32
#: matmuls on the TPU as one bf16 MXU pass by default (8-bit mantissa, ~2e-3
#: relative per product); the Gauss-trick MMSE interpolation emulated at
#: that precision is 1.7e-3 off at 106 PRB.  The MMSE estimate is all
#: matmul, so a layout or indexing fault is O(1) off.
EXPERT_REL_TOL = 1e-2
#: the AI expert's output is the baseline comb-2 interpolation plus a
#: learned correction that random weights keep to a few percent of it, so
#: its whole output would hide a wrong correction.  The correction (output
#: minus the baseline, which both sides compute exactly) must match the CPU
#: reference's within this many times the error of a one-bf16-pass
#: emulation of the same correction on the CPU: the chip rounds the same
#: operands to bf16, while a fault moves the correction by O(1) of itself.
CORRECTION_TOL_FACTOR = 2.0
#: relative difference allowed between two runs' campaign-mean KPMs (fused
#: vs unfused, sharded vs unsharded).  The two runs sum the same products in
#: different orders; an estimate differing in its last bits can flip a
#: discrete link-adaptation step (MCS, TB outcome) for one UE-slot and the
#: OLLA loop carries it on for a few slots, which moves a campaign mean by
#: well under 1%.  A broken kernel on the AI-served UE-slots moves it by
#: more than 10%.
KPM_REL_TOL = 2e-2

N_PRB, N_UES, N_SLOTS, POOR = 106, 16, 48, (16, 32)
SEGMENT_SLOTS = 16  # phase 5: three segments

#: kernels the lowered slot program must carry as ``tpu_custom_call``
UNFUSED_KERNELS = ("mmse_interp", "switch_gather_batched", "tree_infer")
FUSED_KERNELS = ("mmse_interp", "gated_expert", "tree_infer")


class SmokeFailure(RuntimeError):
    pass


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def require_tpu():
    """The device check: a TPU, or exit 2 before any work."""
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, found platform {dev.platform!r}; "
              "there is no CPU fallback", file=sys.stderr)
        sys.exit(2)
    return dev


def campaign_spec(**over):
    from repro.core.session import CampaignSpec, ExpertBankSpec, PolicySpec

    spec = CampaignSpec(
        path="closed_loop",
        scenario="good_poor_good",
        scenario_args=(("poor_start", POOR[0]), ("poor_end", POOR[1])),
        n_prb=N_PRB,
        n_ues=N_UES,
        n_slots=N_SLOTS,
        seed=0,
        bank=ExpertBankSpec(
            execution_mode="gated", gated_capacity=N_UES // 2,
            channels=32, n_res_blocks=4, params_seed=0,
        ),
        policies=(PolicySpec(kind="tree"),),
    )
    return dataclasses.replace(spec, **over)


class Clock:
    """Per-phase wall times, printed with the device they ran on."""

    def __init__(self, label: str):
        self.label = label

    def timed(self, name: str, fn):
        t0 = time.perf_counter()
        out = fn()
        print(f"[{self.label}] {name}: {time.perf_counter() - t0:.2f} s",
              flush=True)
        return out


def lowered_kernels(session) -> set:
    """Names of the Pallas kernels in the session's lowered closed-loop scan."""
    import jax
    import jax.numpy as jnp

    from repro.core.closed_loop import init_device_switch
    from repro.phy.pipeline import init_device_link, resolve_schedule

    spec, eng = session.spec, session.engine
    sw_cfg = spec.switch.to_config(spec.feature_names)
    profile, params = resolve_schedule(
        eng.cfg, session.schedule, spec.n_slots, spec.n_ues
    )
    key = jax.random.PRNGKey(spec.seed)
    ue_keys = jax.vmap(lambda u: jax.random.fold_in(key, u))(
        jnp.arange(spec.n_ues)
    )
    text = type(eng)._run_closed_scan.lower(
        eng, profile, sw_cfg, init_device_link(spec.n_ues),
        init_device_switch(spec.n_ues, len(spec.feature_names), sw_cfg),
        ue_keys, params, session.device_policy,
    ).as_text()
    return {
        m.group(1)
        for line in text.splitlines() if "tpu_custom_call" in line
        for m in [re.search(r'kernel_name = "([^"]+)"', line)] if m
    }


def check_replay(session, hist, name: str) -> None:
    replay = session.host_replay(hist)
    check(np.array_equal(hist.modes, replay["active_mode"]),
          f"{name}: device modes differ from the host replay")
    print(f"{name}: modes == host replay (bitwise), "
          f"{int(np.sum(hist.modes == 0))} AI / "
          f"{int(np.sum(hist.modes == 1))} MMSE slot-UEs")


def kpm_rel_diff(a, b, names) -> float:
    """Largest relative difference of campaign-mean KPMs between runs."""
    worst = 0.0
    for n in names:
        ma, mb = float(np.mean(a.kpms[n])), float(np.mean(b.kpms[n]))
        worst = max(worst, abs(ma - mb) / max(abs(mb), 1e-12))
    return worst


def rel_err(got, want) -> float:
    got, want = np.asarray(got), np.asarray(want)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


# -- phase 2 -------------------------------------------------------------------


def phase_closed_loop(clock):
    from repro.core.session import ArchesSession

    session = ArchesSession(campaign_spec())
    hist = clock.timed("phase 2 closed loop, first call (compile + policy "
                       "training + run)", session.run)
    clock.timed("phase 2 closed loop, warm run", session.run)
    check_replay(session, hist, "phase 2")
    check({0, 1} <= set(np.unique(hist.modes).tolist()),
          f"phase 2: one expert never served (modes {np.unique(hist.modes)})")
    for n, v in hist.kpms.items():
        check(np.all(np.isfinite(v)), f"phase 2: KPM {n} is not finite")
    found = clock.timed("phase 2 lowering for the kernel audit",
                        lambda: lowered_kernels(session))
    missing = set(UNFUSED_KERNELS) - found
    check(not missing, f"phase 2: no tpu_custom_call for {sorted(missing)} "
                       f"(found {sorted(found)})")
    print(f"phase 2: tpu_custom_call kernels {sorted(found)}")
    return session, hist


# -- phase 3 -------------------------------------------------------------------


def _probe_precision() -> dict:
    """Relative error of an f32 matmul on the chip against float64, for
    XLA's default and for a Pallas kernel's default and HIGHEST dots."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    rng = np.random.default_rng(0)
    a = rng.standard_normal((128, 256)).astype(np.float32)
    b = rng.standard_normal((256, 128)).astype(np.float32)
    want = a.astype(np.float64) @ b.astype(np.float64)

    def mosaic(precision):
        def kernel(a_ref, b_ref, o_ref):
            o_ref[...] = jnp.dot(a_ref[...], b_ref[...], precision=precision,
                                 preferred_element_type=jnp.float32)

        return pl.pallas_call(
            kernel, out_shape=jax.ShapeDtypeStruct((128, 128), jnp.float32)
        )(a, b)

    return {
        "xla_default": rel_err(jax.jit(jnp.dot)(a, b), want),
        "mosaic_default": rel_err(mosaic(None), want),
        "mosaic_highest": rel_err(mosaic(jax.lax.Precision.HIGHEST), want),
    }


def _precision_name(err: float) -> str:
    if err < 1e-6:
        return "f32"
    if err < 1e-4:
        return "bf16x3"
    return "bf16 one pass"


def phase_experts(clock, session):
    import jax
    import jax.numpy as jnp

    from repro.kernels.gated_expert import gated_expert_apply
    from repro.kernels.mmse_interp.ref import mmse_interp_ref
    from repro.phy.ai_estimator import (
        _baseline_interp,
        ai_estimate_folded,
        ai_estimate_from_ls,
        fold_ai_params,
    )
    from repro.phy.pipeline import init_device_link, resolve_schedule

    spec, eng = session.spec, session.engine
    slot = (POOR[0] + POOR[1]) // 2  # a poor-window slot: the AI regime
    profile, params = resolve_schedule(
        eng.cfg, session.schedule, spec.n_slots, spec.n_ues
    )
    p = jax.tree.map(lambda x: x[slot], params)
    key = jax.random.PRNGKey(spec.seed)
    keys = jax.vmap(lambda u: jax.random.fold_in(jax.random.fold_in(key, u),
                                                 slot))(jnp.arange(spec.n_ues))
    link = init_device_link(spec.n_ues)
    h_ls = jax.jit(jax.vmap(
        lambda snr, olla, k: eng._ue_pre(profile, p, snr, olla, k)["h_ls"]
    ))(link.reported_snr_db, link.olla_offset_db, keys)

    ai, mmse = (e.fn for e in eng.bank.experts)
    chip_ai = clock.timed("phase 3 AI expert (compile + run)",
                          lambda: np.asarray(jax.jit(lambda h: ai(None, h))(h_ls)))
    chip_mmse = clock.timed("phase 3 MMSE expert (compile + run)",
                            lambda: np.asarray(jax.jit(lambda h: mmse(None, h))(h_ls)))
    n = spec.n_ues
    folded = fold_ai_params(session.ai_params, eng.cfg.n_dmrs_sym)
    chip_fused = np.asarray(jax.jit(lambda h, d: gated_expert_apply(
        jnp.arange(n, dtype=jnp.int32), jnp.arange(n, dtype=jnp.int32),
        h, d, folded, backend="pallas",
    ))(h_ls, jnp.zeros_like(chip_ai)))

    cpu = jax.devices("cpu")[0]
    on_cpu = lambda x: jax.device_put(np.asarray(x), cpu)  # noqa: E731
    with jax.default_device(cpu), jax.default_matmul_precision("highest"):
        h_cpu = on_cpu(h_ls)
        ref_ai = np.asarray(jax.vmap(ai_estimate_from_ls, in_axes=(None, 0))(
            jax.tree.map(on_cpu, session.ai_params), h_cpu))
        bf16_ai = np.asarray(ai_estimate_folded(
            jax.tree.map(on_cpu, folded), h_cpu, compute_dtype=jnp.bfloat16))
        # the comb-2 baseline in the estimate layout (U, ant, 1, n_sc, S)
        base = np.asarray(
            _baseline_interp(jnp.swapaxes(h_cpu, -1, -2)))[:, :, None]
        ref_mmse = np.moveaxis(np.asarray(
            mmse_interp_ref(h_cpu, on_cpu(eng.interpolator.w))), -2, -1
        )[:, :, None]
    corr = lambda out: np.asarray(out, np.complex128) - base  # noqa: E731
    ref_corr = corr(ref_ai)
    bf16_err = rel_err(corr(bf16_ai), ref_corr)
    corr_tol = CORRECTION_TOL_FACTOR * bf16_err
    share = np.linalg.norm(ref_corr) / np.linalg.norm(ref_ai)
    print(f"phase 3: the AI correction is {share:.3e} of the AI output; its "
          f"one-bf16-pass CPU emulation is {bf16_err:.3e} off")
    checks = {
        "AI expert correction (XLA folded GEMM)":
            (rel_err(corr(chip_ai), ref_corr), corr_tol),
        "AI expert correction (fused Pallas kernel)":
            (rel_err(corr(chip_fused), ref_corr), corr_tol),
        "MMSE expert (mmse_interp kernel)":
            (rel_err(chip_mmse, ref_mmse), EXPERT_REL_TOL),
    }
    print(f"phase 3: AI expert whole output vs reference: rel err "
          f"{rel_err(chip_ai, ref_ai):.3e} (XLA), "
          f"{rel_err(chip_fused, ref_ai):.3e} (fused kernel)")
    for name, (err, tol) in checks.items():
        print(f"phase 3: {name} vs CPU highest-precision reference: "
              f"rel err {err:.3e} (tolerance {tol:.3e})")
    probe = _probe_precision()
    for name, err in probe.items():
        print(f"phase 3: f32 matmul precision probe {name}: rel err "
              f"{err:.3e} -> {_precision_name(err)}")
    for name, (err, tol) in checks.items():
        check(err <= tol, f"phase 3: {name} rel err {err:.3e} above {tol:.3e}")


# -- phase 4 -------------------------------------------------------------------


def phase_fused(clock, session, hist):
    from repro.core.session import ArchesSession

    spec = campaign_spec(
        bank=dataclasses.replace(session.spec.bank, fused=True)
    )
    fused = ArchesSession(spec, ai_params=session.ai_params,
                          host_policies=session.host_policies)
    hist_f = clock.timed("phase 4 fused closed loop, first call (compile + "
                         "run)", fused.run)
    clock.timed("phase 4 fused closed loop, warm run", fused.run)
    check_replay(fused, hist_f, "phase 4")
    found = lowered_kernels(fused)
    missing = set(FUSED_KERNELS) - found
    check(not missing, f"phase 4: no tpu_custom_call for {sorted(missing)} "
                       f"(found {sorted(found)})")
    print(f"phase 4: tpu_custom_call kernels {sorted(found)}")
    diff = kpm_rel_diff(hist_f, hist, spec.feature_names)
    agree = float(np.mean(hist_f.modes == hist.modes))
    print(f"phase 4: fused vs unfused campaign-mean KPMs: max rel diff "
          f"{diff:.3e} (tolerance {KPM_REL_TOL:g}); modes agree on "
          f"{agree:.1%} of slot-UEs")
    check(diff <= KPM_REL_TOL, f"phase 4: fused KPMs off by {diff:.3e}")


# -- phase 5 -------------------------------------------------------------------


def _http(url: str, body: dict | None = None):
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(
        url, data=data, method="GET" if body is None else "POST",
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=30) as resp:
        return json.loads(resp.read().decode())


def phase_service(clock, session):
    from repro.core.streaming import ChurnSchedule
    from repro.service.api import ServiceAPI
    from repro.service.service import CampaignService

    churn = ChurnSchedule(
        n_ue_ids=N_UES + 4,
        segment_slots=SEGMENT_SLOTS,
        initial=tuple(range(N_UES - 2)),
        events=((SEGMENT_SLOTS, N_UES - 2, "attach"),
                (SEGMENT_SLOTS, N_UES - 1, "attach"),
                (SEGMENT_SLOTS, 3, "detach"),
                (2 * SEGMENT_SLOTS, N_UES, "attach"),
                (2 * SEGMENT_SLOTS, 7, "detach")),
    )
    spec = campaign_spec(churn=churn)
    with tempfile.TemporaryDirectory(prefix="arches-service-") as state:
        service = CampaignService(state, ai_params=session.ai_params).start()
        api = ServiceAPI(service, port=0).start()
        try:
            cid = _http(f"{api.url}/campaigns", spec.to_dict())["campaign_id"]

            def poll():
                deadline = time.monotonic() + 900
                while time.monotonic() < deadline:
                    st = _http(f"{api.url}/campaigns/{cid}")
                    if st["state"] in ("completed", "failed", "cancelled",
                                       "interrupted"):
                        return st
                    time.sleep(0.25)
                raise SmokeFailure(f"phase 5: {cid} still running after 900 s")

            st = clock.timed("phase 5 service campaign, submit to completed "
                             "(includes compile)", poll)
            if st["state"] != "completed":
                print(f"phase 5: campaign {cid} ended {st['state']!r}:\n"
                      f"{st['error']}", file=sys.stderr)
            check(st["state"] == "completed",
                  f"phase 5: campaign ended {st['state']!r}")
            check(st["segments_done"] == st["n_segments"] >= 3,
                  f"phase 5: {st['segments_done']}/{st['n_segments']} "
                  "segments")
            samples = [s for s in _http(f"{api.url}/telemetry?n=64")
                       if s["campaign_id"] == cid]
            check(sorted(s["seg_idx"] for s in samples)
                  == list(range(st["n_segments"])),
                  f"phase 5: telemetry ring holds segments "
                  f"{[s['seg_idx'] for s in samples]}")
            hist = service.result(cid)
            check(hist is not None and np.all(np.isfinite(hist.kpms["snr"])),
                  "phase 5: no finite history")
            print(f"phase 5: {cid} completed over HTTP in {st['n_segments']} "
                  f"segments, {len(samples)} telemetry samples in the ring")
        finally:
            api.stop()
            service.drain(timeout=60.0)


# -- four chips ----------------------------------------------------------------


def count_collectives(hlo: str) -> dict:
    counts = {}
    for op in ("all-reduce", "all-gather", "all-to-all", "collective-permute"):
        counts[op] = len(re.findall(rf"\s{op}(?:-start)?\(", hlo))
    return counts


def phase_four_chips(clock):
    import jax

    from repro.core.session import ArchesSession
    from repro.core.topology import TopologySpec, closed_loop_fn, _prepare
    from repro.core.closed_loop import init_device_switch

    n_dev = len(jax.devices())
    check(n_dev == 4, f"--four-chips needs 4 devices, found {n_dev}")
    sharded = ArchesSession(
        campaign_spec(topology=TopologySpec(n_cells=4, n_shards=4))
    )
    topo = sharded.cell_topology
    check(topo.n_shards == 4,
          f"topology resolved to {topo.n_shards} shards, not 4")
    hist_s = clock.timed("4 shards closed loop, first call (compile + policy "
                         "training + run)", sharded.run)
    clock.timed("4 shards closed loop, warm run", sharded.run)
    check_replay(sharded, hist_s, "4 shards")

    # the same 4 cells on one shard: only the sharding (and the gated
    # capacity's split across shards) differs between the two runs
    unsharded = ArchesSession(
        campaign_spec(topology=TopologySpec(n_cells=4, n_shards=1)),
        ai_params=sharded.ai_params, host_policies=sharded.host_policies,
    )
    check(unsharded.cell_topology.n_shards == 1,
          f"unsharded run resolved to {unsharded.cell_topology.n_shards} "
          "shards")
    hist_u = clock.timed("unsharded closed loop on device 0, first call "
                         "(compile + run)", unsharded.run)
    check_replay(unsharded, hist_u, "unsharded")
    print(f"gated overflow slot-UEs: 4 shards {hist_s.overflow_slot_ues}, "
          f"unsharded {hist_u.overflow_slot_ues}")

    spec, eng = sharded.spec, sharded.engine
    sw_cfg = spec.switch.to_config(spec.feature_names)
    profile, params, ue_keys, link0 = _prepare(
        eng, topo, sharded.schedule, spec.n_slots, jax.random.PRNGKey(spec.seed),
        None,
    )
    fn = jax.jit(closed_loop_fn(eng, topo, profile, sw_cfg,
                                sharded.device_policy))
    hlo = fn.lower(
        link0, init_device_switch(spec.n_ues, len(spec.feature_names), sw_cfg),
        ue_keys, params, sharded.device_policy,
        jax.numpy.asarray(topo.cell_of_ue), topo.cell_params,
    ).compile().as_text()
    counts = count_collectives(hlo)
    print(f"4 shards: collectives in the compiled sharded scan {counts}")
    check(counts["all-reduce"] == 1,
          f"expected exactly one all-reduce, found {counts['all-reduce']}")
    for op in ("all-gather", "all-to-all", "collective-permute"):
        check(counts[op] == 0, f"sharded scan holds {counts[op]} {op}")

    diff = kpm_rel_diff(hist_s, hist_u, spec.feature_names)
    agree = float(np.mean(hist_s.modes == hist_u.modes))
    print(f"4 shards vs unsharded campaign-mean KPMs: max rel diff {diff:.3e} "
          f"(tolerance {KPM_REL_TOL:g}); modes agree on {agree:.1%} of "
          "slot-UEs")
    check(diff <= KPM_REL_TOL, f"sharded KPMs off by {diff:.3e}")


# -- driver --------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-cell campaign sharded over 4 chips "
                         "against the same 4 cells on one chip")
    args = ap.parse_args(argv)

    # phase 3's references run on the host's CPU backend: keep it loaded
    # where the environment names only the accelerator
    platforms = os.environ.get("JAX_PLATFORMS")
    if platforms and "cpu" not in platforms.split(","):
        os.environ["JAX_PLATFORMS"] = platforms + ",cpu"
    dev = require_tpu()
    import jax

    from repro.compile_cache import enable_compilation_cache

    cache = enable_compilation_cache()
    n_dev = len(jax.devices())
    print(f"device: {dev.platform} {dev.device_kind}, {n_dev} device(s), "
          f"jax {jax.__version__}; compilation cache {cache}", flush=True)
    clock = Clock(f"{dev.device_kind} x{n_dev}")
    try:
        if args.four_chips:
            phase_four_chips(clock)
        else:
            session, hist = phase_closed_loop(clock)
            phase_experts(clock, session)
            phase_fused(clock, session, hist)
            phase_service(clock, session)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": n_dev,
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
