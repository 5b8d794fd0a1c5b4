"""Readings that the correctness limits are set from (not part of a run).

    python3 -m bench.control --workload <cell> --seeds 11,12,13 \\
        --seconds 10 [--control 3] [--pin-tree]

With ``--pin-tree``, first the switching tree the cell must run: the
program's profiling campaigns (each expert forced, on the policy's training
schedule) are run once more, the Gini fit of ``bench.harness.tree`` is
made on their KPMs, and the tree is printed as the ``"tree"`` entry of
``bench/limits/<cell>.json``, beside its gap to the program's own fit and
to fits on half the profile (the first half of the slots; one UE).

Then, for each seed, in one process: one run of the cell's traffic on the
chip (a window at the cell's own size and load), every compared number for
the program against the float64 reference, and, for the first
``--control`` seeds, the same numbers with the reference put in the
program's place: the control (its estimator matmuls in int8, the TPU
v5e's low-precision matmul type), float8 e4m3 for comparison, and two
wrong experts for the AI-served slot-UEs: the comb-2 baseline alone (the
CNN skipped) and the MMSE expert.  One JSON line per seed and kind;
``bench/limits/<cell>.json`` is set from them (see PERF.md).  Needs the
same chips as the cell.  The session's policy is fitted once and reused
for the later seeds (it does not depend on the seed).
"""

from __future__ import annotations

import argparse
import importlib
import json
import sys
import time

from bench import run as brun
from bench.harness import correct


#: what the reference put in the program's place runs: (precision, fault)
KINDS = {
    "control_int8": ("int8", None), "control_fp8": ("fp8", None),
    "fault_skip_cnn": ("f64", "skip_cnn"),
    "fault_mmse_for_ai": ("f64", "mmse_for_ai"),
}


def control_gaps(ctx, run: dict, precision: str = "fp8",
                 fault: str | None = None) -> dict:
    """The numbers of the reference at ``precision`` in the program's
    place, with ``fault`` planted in it, on the program's recorded history,
    against the float64 reference."""
    return correct.gaps(ctx, run,
                        answers=_swap_outputs(ctx, run, precision, fault))


def without_cnn(weights: dict) -> dict:
    """AI weights whose head outputs zero: the expert's answer is then the
    comb-2 baseline alone, as if the CNN were skipped."""
    import numpy as np

    return dict(weights, head_w=np.zeros_like(weights["head_w"]),
                head_b=np.zeros_like(weights["head_b"]))


def _swap_outputs(ctx, run: dict, precision: str,
                  fault: str | None = None) -> dict:
    """The recorded outputs, with the sampled slot-UEs' KPMs, MCS and
    decoding outcomes replaced by the reference's at ``precision``;
    ``fault`` ``"skip_cnn"`` or ``"mmse_for_ai"`` serves the AI-flagged
    ones with the baseline alone or with MMSE."""
    import numpy as np

    from bench.harness.reference import reference_for

    out = {k: np.array(v) for k, v in run["outputs"].items()}
    weights = correct.host_weights(run)
    if fault == "skip_cnn":
        weights = without_cnn(weights)
    ref = reference_for(ctx.config)(ctx.config, ctx.traffic, ctx.seed,
                                    weights, precision)
    served_ai = (out["active_mode"] == 0) & (out["gated_overflow"] == 0)
    picks = correct.sample(served_ai, run["window"].first_timed, ctx.seed,
                           ctx.traffic["check_sample_slot_ues"])
    init = ctx.config["link"]["initial_snr_db"]
    for s, u, ai in picks:
        reported = init if s == 0 else float(run["outputs"]["kpms/oai/snr"][
            s - 1, u])
        olla = ref.olla(run["outputs"]["tb_ok"][:s, u] > 0.5)
        r = ref.slot(s, u, reported, olla, ai and fault != "mmse_for_ai")
        out["kpms/oai/snr"][s, u] = r["snr"]
        out["kpms/aerial/rsrp"][s, u] = r["rsrp"]
        out["mcs"][s, u] = r["mcs"]
        out["tb_ok"][s, u] = float(r["tb_ok"])
    return out


def pin_tree(ctx, session) -> dict:
    """The tree the cell must run, and its gaps to other fits (see above)."""
    import numpy as np

    from bench.harness import program, tree

    spec = session.spec
    ps = spec.policies[0]
    n_slots = ps.train_slots or spec.n_slots
    labels = np.array([
        0 if ctx.traffic["conditions"][program.condition_at(ctx.traffic, s)][
            "interference"] else 1 for s in range(n_slots)])
    eng, schedule = session._training_engine(), session._train_schedule(ps)
    x, y = [], []
    for mode in (0, 1):  # AI forced, then MMSE
        _, traj = eng.run(schedule, mode, n_slots=n_slots, n_ues=ps.train_ues)
        kpms = program.flat_kpms(traj)
        x.append(np.stack([np.asarray(kpms[n], np.float32)
                           for n in spec.feature_names], -1))
        y.append(np.broadcast_to(labels[:, None], x[-1].shape[:2]))
    x, y = np.stack(x), np.stack(y)  # (mode, slots, UEs, F), (mode, slots, UEs)
    rows = lambda a: a.reshape(-1, a.shape[-1])  # noqa: E731
    pinned = tree.fit(rows(x), y.reshape(-1), ps.depth)
    half = n_slots // 2
    other = {
        "program": session.host_policies[0].tree,
        "first_half_slots": tree.fit(rows(x[:, :half]),
                                     y[:, :half].reshape(-1), ps.depth),
        "one_ue": tree.fit(rows(x[:, :, :1]), y[:, :, :1].reshape(-1),
                           ps.depth),
    }
    return {"tree": dict(pinned, features=list(spec.feature_names),
                         profile_slots=n_slots, profile_ues=ps.train_ues),
            "gaps": {k: tree.compare(t, pinned) for k, t in other.items()}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--pin-tree", action="store_true")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    ctx0 = brun.make_context(
        brun.parse(["--workload", args.workload, "--seed", str(seeds[0]),
                    "--seconds", str(args.seconds)]), brun.ROOT)
    import jax

    from repro.compile_cache import enable_compilation_cache

    from bench.harness import program

    enable_compilation_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    driver = importlib.import_module(
        f"bench.harness.{ctx0.traffic['driver']}")
    policies = None
    if args.pin_tree:
        session = program.build_session(
            ctx0.config, ctx0.traffic, program.make_ai_weights(ctx0.config))
        pinned = pin_tree(ctx0, session)
        print(json.dumps({"kind": "pin_tree", **pinned}), flush=True)
        ctx0.limits = dict(ctx0.limits, tree=pinned["tree"])
        policies = session.host_policies
        del session
    for i, seed in enumerate(seeds):
        ctx = brun.Context(**dict(vars(ctx0), seed=seed,
                                  t_start=time.perf_counter()))
        run = driver.run(ctx, host_policies=policies)
        policies = run["session"].host_policies
        t = time.perf_counter()
        sound = correct.gaps(ctx, run)
        ref_s = time.perf_counter() - t
        print(json.dumps({"seed": seed, "kind": "program", "gaps": sound,
                          "slots": run["window"].n_timed,
                          "setup_s": run["setup_s"], "reference_s": ref_s}),
              flush=True)
        if i < args.control:
            for kind, (precision, fault) in KINDS.items():
                print(json.dumps({"seed": seed, "kind": kind,
                                  "gaps": control_gaps(ctx, run, precision,
                                                       fault)}),
                      flush=True)
        del run
    return 0


if __name__ == "__main__":
    sys.exit(main())
