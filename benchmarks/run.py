"""Benchmark driver: one section per paper table/figure + roofline.

Usage:  PYTHONPATH=src python -m benchmarks.run [--fast | --smoke]
                                                [--json BENCH_<tag>.json]

``--smoke`` is the CI fast path: tiny expert training, nine sections only
(switch-kernel runtimes + batched multi-UE engine + closed-loop device/host
equivalence + gated-execution contract + session-API dispatch/provenance +
sharded-engine parity/scaling + streaming-churn zero-churn equivalence +
fault-injection/crash-resume + campaign-service API/drain-resume),
exits non-zero on any failure.  Finishes in minutes where the full sweep
takes an hour.

``--json PATH`` additionally writes a machine-readable perf snapshot —
slot-UEs/s, in-scan decision latency, executed-FLOPs-per-slot across AI
shares {0, 1/16, 1/2, 1}, and the sharded-engine parity/scaling row — so
the repo's bench trajectory accumulates across PRs.  The snapshot embeds
the serialized ``CampaignSpec`` + its ``spec_hash`` from the session
section, so every perf number carries the exact campaign it was measured
on.
"""

from __future__ import annotations

import argparse
import json
import os
import platform
import sys
import time
import traceback


def _jax_backend() -> str:
    """Default-backend name, without importing jax before env setup."""
    import jax

    return jax.default_backend()


def _json_payload(outs: dict) -> dict:
    """Assemble the perf-trajectory snapshot from section outputs."""
    payload: dict = {"schema": "arches-bench-v5", "time": time.strftime(
        "%Y-%m-%dT%H:%M:%S")}
    # host fingerprint: check_snapshot only compares absolute rates when
    # these match (cross-host wall-clock deltas are meaningless)
    payload["host"] = {
        "platform": platform.platform(),
        "machine": platform.machine(),
        "cpu_count": os.cpu_count(),
        "jax_backend": _jax_backend(),
    }
    batched = outs.get("batched")
    if batched:
        payload["slot_ues_per_s"] = {
            "host_loop": batched["host_rate"],
            "scan_engine": batched["batched_rate"],
            "speedup": batched["speedup"],
        }
    in_scan = outs.get("in_scan")
    if in_scan:
        payload["in_scan_decision_us_per_slot"] = in_scan["decide_us_per_slot"]
        payload["closed_loop_slot_ues_per_s"] = in_scan["closed_rate"]
    gated = outs.get("gated")
    if gated:
        payload["gated"] = {
            share: {
                "executed_flops_per_slot": row["executed_flops_per_slot"],
                "gated_slot_ues_per_s": row["gated_slot_ues_per_s"],
                "concurrent_slot_ues_per_s": row["concurrent_slot_ues_per_s"],
                "speedup_vs_concurrent": row["speedup"],
                "fused_slot_ues_per_s": row["fused_slot_ues_per_s"],
                "fused_speedup_vs_unfused": row["fused_speedup_vs_unfused"],
                # true off-TPU: the ref fallback is the same XLA program,
                # so the fused timing is the unfused one (not re-measured)
                "fused_shares_program_with_unfused":
                    row["fused_shares_program_with_unfused"],
                "bf16_slot_ues_per_s": row["bf16_slot_ues_per_s"],
                "bf16_audit_tripped": row["bf16_audit_tripped"],
            }
            for share, row in gated["by_share"].items()
        }
    session = outs.get("session")
    if session:
        # benchmark provenance: the exact campaign the numbers came from
        payload["campaign_spec"] = session["spec"]
        payload["campaign_spec_hash"] = session["spec_hash"]
        payload["session_slot_ues_per_s"] = session["session_slot_ues_per_s"]
    sharded = outs.get("sharded")
    if sharded:
        forced = sharded["forced"] or {}
        payload["sharded"] = {
            "parity": sharded["parity"],
            "one_device_slot_ues_per_s":
                sharded["one_device_slot_ues_per_s"],
            "forced_shards": forced.get("n_shards"),
            "forced_slot_ues_per_s": forced.get("slot_ues_per_s"),
        }
    streaming = outs.get("streaming")
    if streaming:
        # v2 schema: the epoch-chunked churn-campaign rates; v5 adds the
        # pipelined-executor rates, the per-segment wall-time breakdown,
        # and the O(segment) delta-checkpoint byte measurement
        payload["streaming"] = {
            "zero_churn_equal": streaming["zero_churn_equal"],
            "streaming_slot_ues_per_s":
                streaming["streaming_slot_ues_per_s"],
            "monolithic_slot_ues_per_s":
                streaming["monolithic_slot_ues_per_s"],
            "churn_resident_slot_ues_per_s":
                streaming["churn_resident_slot_ues_per_s"],
            "n_segments": streaming["n_segments"],
            "serial_checkpointed_slot_ues_per_s":
                streaming["serial_checkpointed_slot_ues_per_s"],
            "pipelined_checkpointed_slot_ues_per_s":
                streaming["pipelined_checkpointed_slot_ues_per_s"],
            "pipeline_speedup": streaming["pipeline_speedup"],
            "segment_breakdown_s": streaming["segment_breakdown_s"],
            "delta_ckpt_bytes_per_segment":
                streaming["delta_ckpt_bytes_per_segment"],
            "delta_bytes_length_invariant":
                streaming["delta_bytes_length_invariant"],
        }
    faults = outs.get("faults")
    if faults:
        # v3 schema: fault-injection replay + crash-resume rates
        payload["faults"] = {
            "fault_replay_equal": faults["fault_replay_equal"],
            "resume_equal": faults["resume_equal"],
            "fault_closed_slot_ues_per_s":
                faults["fault_closed_slot_ues_per_s"],
            "checkpointed_slot_ues_per_s":
                faults["checkpointed_slot_ues_per_s"],
            "health_tripped_slot_ues": faults["health_tripped_slot_ues"],
            "quarantined_slot_ues": faults["quarantined_slot_ues"],
        }
    service = outs.get("service")
    if service:
        # v4 schema: the resident campaign service (API-driven campaigns,
        # telemetry export, drain/resume through the service path)
        payload["service"] = {
            "zero_churn_service_equal": service["zero_churn_service_equal"],
            "drain_resume_equal": service["drain_resume_equal"],
            "status_transitions": service["status_transitions"],
            "n_segments": service["n_segments"],
            "telemetry_exported": service["telemetry_exported"],
            "telemetry_dropped": service["telemetry_dropped"],
            "service_campaign_wall_s": service["service_campaign_wall_s"],
            "slot_ues_per_s_cold": service["slot_ues_per_s_cold"],
            "direct_streaming_slot_ues_per_s":
                service["direct_streaming_slot_ues_per_s"],
        }
    return payload


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="smaller sweeps")
    ap.add_argument("--smoke", action="store_true",
                    help="minimal CI smoke check (switch + batched engine)")
    ap.add_argument("--json", default=None, metavar="BENCH_<tag>.json",
                    help="write a machine-readable perf snapshot")
    ap.add_argument("--dryrun-json", default="dryrun_results.json")
    args = ap.parse_args()

    from repro.compile_cache import enable_compilation_cache

    enable_compilation_cache()

    if args.smoke:
        # must precede the benchmarks.common import (module-level env reads)
        os.environ.setdefault("ARCHES_BENCH_TRAIN_STEPS", "40")
        os.environ.setdefault("ARCHES_BENCH_SLOTS", "40")

    from benchmarks import (
        bench_control_loop,
        bench_faults,
        bench_gated,
        bench_kpm_cdfs,
        bench_methodology,
        bench_policy,
        bench_resources,
        bench_session,
        bench_service,
        bench_sharded,
        bench_streaming,
        bench_switch,
        bench_timeseries,
        roofline,
    )

    # (key, title, fn, kwargs): ``key`` names the section's output for the
    # --json payload (None == not part of the snapshot).
    if args.smoke:
        sections = [
            (None, "Fig. 8  switching-mechanism runtimes", bench_switch.run, {}),
            ("batched", "Batched multi-UE engine (smoke)",
             bench_timeseries.run_batched,
             {"n_slots": 24, "n_ues": 4, "host_probe_slots": 6,
              "check_identity": False}),
            # tiny policy, 8 slots: raises unless device-decided modes
            # bitwise-match the host replay (the loop-equivalence contract)
            ("in_scan", "Closed-loop equivalence (smoke)",
             bench_control_loop.run_in_scan,
             {"n_slots": 8, "n_ues": 2, "window_slots": 2}),
            # raises unless gated == concurrent bitwise, fused == unfused
            # bitwise, the bf16 audit stays quiet, and executed FLOPs at AI
            # share 0 equal the MMSE-only cost model.  n_ues=8 keeps the
            # 1/16 share distinct from 1/4 (ceil -> 1 vs 2 AI UEs); the
            # share set matches the acceptance sweep {1/16, 1/4, 1}.
            # n_slots=32 / repeats=9: fused and unfused lower to
            # near-identical XLA:CPU programs, so the speedup columns need
            # long timed runs (scheduler jitter is fixed-size, its relative
            # weight falls with scan length) and min-of-repeats headroom.
            ("gated", "Gated execution (smoke)", bench_gated.run,
             {"n_slots": 32, "n_ues": 8,
              "shares": (0.0, 1.0 / 16.0, 0.25, 1.0), "repeats": 9}),
            # raises unless the declarative session reproduces the legacy
            # closed loop bitwise and a per-UE heterogeneous campaign
            # matches its per-UE host replay (spec JSON round-trip included)
            ("session", "Session API (smoke)", bench_session.run,
             {"n_slots": 12, "n_ues": 2}),
            # raises unless the sharded entry is bitwise-equal to the
            # unsharded engine on 1 device; also runs the same campaign on
            # a forced-8-shard CPU mesh (subprocess) for scaling numbers
            ("sharded", "Sharded multi-cell engine (smoke)",
             bench_sharded.run, {"n_slots": 10, "n_ues": 8}),
            # raises unless a zero-churn streaming run is bitwise-equal to
            # the monolithic session run on every leaf and a churn campaign
            # keeps the detached-sentinel / zero-cost accounting
            ("streaming", "Streaming churn campaigns (smoke)",
             bench_streaming.run,
             {"n_slots": 16, "n_ues": 4, "segment_slots": 8}),
            # raises unless a fault-injected closed loop (outage + NaN
            # corruption + telemetry loss) replays bitwise through the host
            # oracle and a killed-then-resumed streaming run is bitwise-
            # equal to the uninterrupted one on every leaf
            ("faults", "Fault injection + crash resume (smoke)",
             bench_faults.run,
             {"n_slots": 16, "n_ues": 4, "segment_slots": 8}),
            # raises unless a campaign submitted over the live HTTP API is
            # bitwise-equal to the monolithic run, its telemetry export is
            # lossless, and a drained-then-restarted service resumes a
            # churn campaign bitwise from its checkpoint
            ("service", "Campaign service (smoke)", bench_service.run,
             {"n_slots": 16, "n_ues": 4, "segment_slots": 4}),
        ]
    else:
        sections = [
            (None, "Fig. 8  switching-mechanism runtimes", bench_switch.run, {}),
            (None, "6.1     control-loop latency", None, {}),  # uses Fig. 8
            (None, "Fig. 4+5 policy-design methodology", bench_methodology.run,
             {"n_trials": 2 if args.fast else 4,
              "rho_step": 0.5 if args.fast else 0.2}),
            (None, "Table 1 decision-tree performance", bench_policy.run, {}),
            (None, "Fig. 9  throughput time series", bench_timeseries.run,
             {"n_phase": 10 if args.fast else None}),
            ("batched", "Batched multi-UE engine", bench_timeseries.run_batched,
             {"n_slots": 60 if args.fast else 100,
              "n_ues": 8 if args.fast else 16}),
            ("gated", "Gated expert execution", bench_gated.run,
             {"n_slots": 30 if args.fast else 60,
              "n_ues": 8 if args.fast else 16}),
            ("session", "Session API (declarative campaigns)",
             bench_session.run,
             {"n_slots": 24 if args.fast else 48,
              "n_ues": 4 if args.fast else 8}),
            ("sharded", "Sharded multi-cell engine",
             bench_sharded.run,
             {"n_slots": 16 if args.fast else 32,
              "n_ues": 8 if args.fast else 16}),
            ("streaming", "Streaming churn campaigns",
             bench_streaming.run,
             {"n_slots": 24 if args.fast else 48,
              "n_ues": 4 if args.fast else 8,
              "segment_slots": 8}),
            ("faults", "Fault injection + crash resume",
             bench_faults.run,
             {"n_slots": 24 if args.fast else 48,
              "n_ues": 4 if args.fast else 8,
              "segment_slots": 8}),
            ("service", "Campaign service (dispatch + API + drain/resume)",
             bench_service.run,
             {"n_slots": 24 if args.fast else 48,
              "n_ues": 4 if args.fast else 8,
              "segment_slots": 8}),
            (None, "Fig. 10 KPM CDFs", bench_kpm_cdfs.run, {}),
            (None, "Fig. 11 GPU resources proxy", bench_resources.run, {}),
            (None, "Roofline (from dry-run)", roofline.run,
             {"path": args.dryrun_json}),
        ]

    results, failures = {}, []
    json_outs: dict = {}
    switch_stats = None
    for key, title, fn, kw in sections:
        print("\n" + "=" * 78)
        print("##", title)
        print("=" * 78)
        t0 = time.time()
        try:
            if title.startswith("6.1"):
                out = bench_control_loop.run(switch_stats)
                json_outs["in_scan"] = {
                    f.removeprefix("in_scan_"): v
                    for f, v in out.items() if f.startswith("in_scan_")
                }
            else:
                out = fn(**kw)
            if title.startswith("Fig. 8"):
                switch_stats = out
            if key is not None:
                json_outs[key] = out
            results[title] = "ok"
        except Exception:
            traceback.print_exc()
            failures.append(title)
            results[title] = "FAILED"
        print(f"[{title.split()[0]}] {results[title]} in {time.time()-t0:.0f}s")

    print("\n" + "=" * 78)
    print("## Summary")
    for title, status in results.items():
        print(f"  {status:7s} {title}")

    if args.json:
        payload = _json_payload(json_outs)
        payload["failures"] = failures
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=2, sort_keys=True)
        print(f"\nwrote perf snapshot -> {args.json}")

    if args.smoke:
        # schema/regression gate: the committed snapshot must stay readable
        # by current tooling, and a fresh snapshot (when --json was given)
        # must not regress slot-UEs/s >20% on a comparable host
        from benchmarks import check_snapshot

        print("\n" + "=" * 78)
        print("## Snapshot schema/regression gate")
        print("=" * 78)
        rc = check_snapshot.check(
            check_snapshot.DEFAULT_BASELINE,
            candidate=args.json,
        )
        if rc:
            failures.append("Snapshot schema/regression gate")

    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
