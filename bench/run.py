"""Run one cell of the ARCHES chip benchmark once.

    python3 -m bench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout.  The cell is an entry of ``workloads`` in
``BENCHMARK.json``; everything else is found by name: the configuration
file it names, ``bench/traffic/<traffic>.json`` (whose ``driver`` is a
module of ``bench.harness``), ``bench/limits/<cell>.json`` (the limits of
the correctness check) and one reader ``bench/metrics/<metric>.py`` per
metric.  ``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1``
its per-layer metrics from a profiler trace of the window.

The run needs TPUs: on any other platform, or with fewer chips than the
cell asks for, it exits 3 and prints no result.  Detail goes to earlier
lines; the last line of standard output is one JSON object with
``correct``, ``attempted``, ``failed``, ``metrics``, ``device``, with
``--trace 1`` ``breakdown``, and last ``checks``: every number the
correctness check compared, beside its limit.  The same numbers end
standard error.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
EXIT_NO_CHIP = 3


class NoChip(RuntimeError):
    pass


@dataclasses.dataclass
class Context:
    """What a traffic driver, the checks and the readers get."""

    root: str
    cell: dict
    config: dict
    traffic: dict
    limits: dict
    seed: int
    seconds: float
    trace: bool
    t_start: float
    chips: int
    peaks: dict
    device: dict

    def out_dir(self, name: str) -> str:
        """A scratch directory under ``bench/out`` (git-ignored)."""
        path = os.path.join(self.root, "bench", "out",
                            f"{self.cell['name']}.{self.seed}.{name}")
        os.makedirs(path, exist_ok=True)
        return path

    def memory_peak_bytes(self) -> int:
        import jax

        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                 for d in jax.devices()[: self.chips]]
        return int(max(peaks))


def load_json(root: str, *parts: str) -> dict:
    with open(os.path.join(root, *parts)) as f:
        return json.load(f)


def load_reader(root: str, name: str):
    """``bench/metrics/<name>.py`` (metric names may hold dots)."""
    path = os.path.join(root, "bench", "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def require_chips(n: int) -> dict:
    """The device the cell runs on: TPUs, at least ``n``, or ``NoChip``."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"needs a TPU, found platform {devs[0].platform!r}; "
                     "there is no CPU fallback")
    if len(devs) < n:
        raise NoChip(f"the cell needs {n} chips, found {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": n}


def peaks_for(root: str, kind: str) -> dict:
    table = load_json(root, "bench", "peaks.json")["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in "
                       f"bench/peaks.json (known: {sorted(table)})")
    return table[kind]


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def make_context(args, root: str, device_fn=require_chips) -> Context:
    bench = load_json(root, "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in cells:
        raise KeyError(f"no workload {args.workload!r} (have {sorted(cells)})")
    cell = cells[args.workload]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    sys.path.insert(0, os.path.join(root, "src"))
    device = device_fn(cell["chips"])
    return Context(
        root=root, cell=cell,
        config=load_json(root, conf["file"]),
        traffic=load_json(root, "bench", "traffic", f"{cell['traffic']}.json"),
        limits=load_json(root, "bench", "limits", f"{cell['name']}.json"),
        seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
        t_start=T_START, chips=cell["chips"],
        peaks=peaks_for(root, device["kind"]), device=device,
    )


def cell_metrics(root: str, cell_name: str, trace: bool) -> list:
    """The metrics this cell reports in this kind of run."""
    bench = load_json(root, "BENCHMARK.json")
    kind = "per_layer" if trace else "end_to_end"
    return [m for m in bench[kind]
            if cell_name in m.get("workloads", [cell_name])]


def main(argv=None, *, root: str = ROOT, device_fn=require_chips) -> int:
    args = parse(argv)
    try:
        ctx = make_context(args, root, device_fn)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return EXIT_NO_CHIP
    import jax

    from repro.compile_cache import enable_compilation_cache

    from bench.harness import correct, result

    cache = enable_compilation_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    print(f"bench: {ctx.cell['name']} seed {ctx.seed} on {ctx.device}, "
          f"jax {jax.__version__}, compilation cache {cache}", flush=True)
    driver = importlib.import_module(f"bench.harness.{ctx.traffic['driver']}")
    run = driver.run(ctx)
    checks = correct.check(ctx, run)
    view = result.RunView(ctx, run, root)
    metrics = {}
    for m in cell_metrics(root, ctx.cell["name"], ctx.trace):
        value = load_reader(root, m["name"]).read(view)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    line = result.assemble(ctx, run, view, metrics, checks)
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r} "
              f"({'ok' if c['ok'] else 'FAILED'})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
