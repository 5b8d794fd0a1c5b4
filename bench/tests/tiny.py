"""A tiny copy of the benchmark for CPU tests: 8 PRB (or the paper's 106
for the control), a few UEs, a CPU-sized expert and a 30-slot cycle, with
every Pallas kernel in interpret mode.  ``make_root`` lays it out in a temporary checkout that
reuses this repository's ``src`` and ``bench`` files, with the tiny cell's
switching tree pinned by ``bench.control.pin_tree`` on the CPU."""

from __future__ import annotations

import json
import os
import shutil

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
CELL = "tiny.slot.gpg"


def cpu_device(n: int) -> dict:
    return {"platform": "cpu", "kind": "cpu", "count": n}


def make_root(tmp: str, *, limits: dict | None = None, n_prb: int = 8,
              n_ues: int = 4, expert: tuple = (4, 1)) -> str:
    """``expert``: the AI expert's channels and residual blocks (the x5g
    configuration's are (32, 4))."""
    root = os.path.join(tmp, "checkout")
    bench = os.path.join(root, "bench")
    for sub in ("metrics", "work", "traffic", "limits", "configs"):
        shutil.copytree(os.path.join(REPO, "bench", sub),
                        os.path.join(bench, sub))
    os.symlink(os.path.join(REPO, "src"), os.path.join(root, "src"))
    peaks = json.load(open(os.path.join(REPO, "bench", "peaks.json")))
    peaks["devices"]["cpu"] = dict(peaks["devices"]["TPU v5 lite"])
    json.dump(peaks, open(os.path.join(bench, "peaks.json"), "w"))
    cfg = json.load(open(os.path.join(bench, "configs", "x5g_106prb.json")))
    cfg.update(name="tiny", n_prb=n_prb, n_ues=n_ues,
               gated_capacity=n_ues // 2, ai_channels=expert[0],
               ai_res_blocks=expert[1])
    json.dump(cfg, open(os.path.join(bench, "configs", "tiny.json"), "w"))
    tr = json.load(open(os.path.join(bench, "traffic", "slot_gpg.json")))
    tr.update(name="tiny_gpg", cycle_slots=30, warmup_slots=4,
              check_sample_slot_ues=8,
              scenario_args={"poor_start": 10, "poor_end": 20},
              phases=[{"start": 0, "end": 10, "condition": "good"},
                      {"start": 10, "end": 20, "condition": "poor"},
                      {"start": 20, "end": 30, "condition": "good"}])
    json.dump(tr, open(os.path.join(bench, "traffic", "tiny_gpg.json"), "w"))
    lim = json.load(open(os.path.join(bench, "limits", "x5g.slot.gpg.json")))
    if limits is not None:
        lim["limits"] = limits
    json.dump(lim, open(os.path.join(bench, "limits", f"{CELL}.json"), "w"))
    spec = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    spec["configs"].append({"name": "tiny", "source": "test",
                            "file": "bench/configs/tiny.json", "reduced": [],
                            "why": "test"})
    spec["workloads"].append({"name": CELL, "config": "tiny",
                              "traffic": "tiny_gpg", "chips": 1,
                              "why": "test"})
    for m in spec["per_layer"] + spec["end_to_end"]:
        if "workloads" in m:
            m["workloads"].append(CELL)
    json.dump(spec, open(os.path.join(root, "BENCHMARK.json"), "w"))
    lim["tree"] = pin_tree(root)
    json.dump(lim, open(os.path.join(bench, "limits", f"{CELL}.json"), "w"))
    return root


def pin_tree(root: str) -> dict:
    from bench import control
    from bench import run as brun
    from bench.harness import program

    args = brun.parse(["--workload", CELL, "--seed", "0", "--seconds", "1"])
    ctx = brun.make_context(args, root, cpu_device)
    session = program.build_session(ctx.config, ctx.traffic,
                                    program.make_ai_weights(ctx.config))
    pinned = control.pin_tree(ctx, session)
    assert pinned["gaps"]["program"] == (0, pinned["gaps"]["program"][1])
    return pinned["tree"]
