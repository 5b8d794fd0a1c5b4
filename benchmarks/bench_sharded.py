"""Sharded multi-cell engine: 1-device parity + forced-multi-shard scaling.

Two legs, both doubling as CI smoke checks:

* **Parity (in-process)** — the sharded entry on the local (1-device CI)
  mesh must be bitwise-equal on physical trajectory leaves to the plain
  unsharded engine under a trivial topology; raises otherwise.  Warm
  wall-time of the sharded scan is reported next to the unsharded engine's
  so the shard_map wrapper's overhead is visible.
* **Scaling** — runs the same campaign across several shards and reports
  slot-UEs/s plus the per-shard UE count.  On the CPU backend that is a
  subprocess with ``JAX_PLATFORMS=cpu`` and
  ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` (default 8): the
  forced shards oversubscribe the same cores, so the number demonstrates
  the path works and what it costs there, not accelerator scaling.  On an
  accelerator the leg runs in this process on its own devices (a child
  could not open a chip this process holds), and is skipped with a note
  when the process has only one device.

Invoked as a module (``python -m benchmarks.bench_sharded --child ...``)
it runs the scaling leg and prints one JSON line (the parent parses it).
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np


def _build(n_ues: int, topo_spec=None):
    from repro.core.topology import CellTopology, TopologySpec
    from repro.phy.ai_estimator import AiEstimatorConfig, init_params
    from repro.phy.nr import SlotConfig
    from repro.phy.pipeline import BatchedPuschPipeline

    cfg = SlotConfig(n_prb=24)
    net = AiEstimatorConfig(channels=8, n_res_blocks=1)
    params = init_params(jax.random.PRNGKey(0), cfg, net)
    engine = BatchedPuschPipeline(cfg, params, net=net)
    topo = CellTopology.build(
        topo_spec or TopologySpec(n_cells=2), n_ues
    )
    return cfg, engine, topo


def _sharded_callable(cfg, engine, topo, n_slots: int, n_ues: int):
    """One cached jitted callable + its args (timing needs a stable fn)."""
    from repro.core.topology import open_loop_fn
    from repro.phy.channel import broadcast_params_to_ues
    from repro.phy.pipeline import init_device_link, resolve_schedule
    from repro.phy.scenario import good_poor_good_schedule

    sched = good_poor_good_schedule(
        poor_start=n_slots // 3, poor_end=2 * n_slots // 3
    )
    profile, params = resolve_schedule(cfg, sched, n_slots, n_ues)
    params = broadcast_params_to_ues(params, n_ues)
    key = jax.random.PRNGKey(3)
    ue_keys = jax.vmap(lambda u: jax.random.fold_in(key, u))(
        jnp.arange(n_ues)
    )
    modes = jnp.ones((n_slots, n_ues), jnp.int32).at[:, 0].set(0)
    args = (
        init_device_link(n_ues), ue_keys, modes, params,
        jnp.asarray(topo.cell_of_ue), topo.cell_params,
    )
    return jax.jit(open_loop_fn(engine, topo, profile)), args, sched, modes


def _time_warm(fn, args, repeats: int = 3) -> float:
    fn(*args)  # compile
    t0 = time.perf_counter()
    for _ in range(repeats):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / repeats


def _child(n_slots: int, n_ues: int) -> dict:
    """Scaling leg: runs on whatever device count XLA was forced to."""
    cfg, engine, topo = _build(n_ues)
    fn, args, _, _ = _sharded_callable(cfg, engine, topo, n_slots, n_ues)
    warm_s = _time_warm(fn, args)
    return {
        "devices": len(jax.devices()),
        "n_shards": topo.n_shards,
        "ues_per_shard": topo.ues_per_shard,
        "slot_ues_per_s": n_slots * n_ues / warm_s,
    }


def _forced_host_child(n_slots: int, n_ues: int, forced_shards: int) -> dict:
    """The scaling leg on ``forced_shards`` CPU devices, in a child process
    (``XLA_FLAGS`` must precede jax initialization).  The child is pinned
    to the CPU backend, so it never reaches for an accelerator."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={forced_shards} "
        + env.get("XLA_FLAGS", "")
    ).strip()
    proc = subprocess.run(
        [sys.executable, "-m", "benchmarks.bench_sharded", "--child",
         "--n-slots", str(n_slots), "--n-ues", str(n_ues)],
        env=env, capture_output=True, text=True, timeout=540,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    if proc.returncode != 0:
        raise RuntimeError(
            f"forced-{forced_shards}-shard child failed:\n{proc.stderr[-3000:]}"
        )
    return json.loads(proc.stdout.strip().splitlines()[-1])


def run(n_slots: int = 16, n_ues: int = 8, forced_shards: int = 8) -> dict:
    cfg, engine, topo = _build(n_ues)
    fn, args, sched, modes = _sharded_callable(
        cfg, engine, topo, n_slots, n_ues
    )

    # -- parity: sharded entry == plain engine, bitwise ---------------------
    _, traj_s = fn(*args)
    _, traj_u = engine.run(
        sched, modes, n_slots=n_slots, n_ues=n_ues, key=jax.random.PRNGKey(3)
    )
    for leaf in ("tb_ok", "mcs", "phy_bits_per_s", "executed_flops"):
        assert np.array_equal(
            np.asarray(traj_s[leaf]), np.asarray(traj_u[leaf])
        ), f"sharded != unsharded on {leaf}"
    assert np.array_equal(
        np.asarray(traj_s["kpms"]["aerial"]["sinr"]),
        np.asarray(traj_u["kpms"]["aerial"]["sinr"]),
    ), "sharded != unsharded on sinr"
    sharded_warm = _time_warm(fn, args)
    t0 = time.perf_counter()
    out = engine.run(
        sched, modes, n_slots=n_slots, n_ues=n_ues, key=jax.random.PRNGKey(3)
    )
    jax.block_until_ready(out)
    unsharded_warm = time.perf_counter() - t0
    rate_1dev = n_slots * n_ues / sharded_warm
    print(f"1-device parity:   bitwise on all physical leaves "
          f"({n_slots}x{n_ues}, {topo.n_shards} shard(s))")
    print(f"1-device sharded:  {rate_1dev:8.1f} slot-UEs/s warm "
          f"(unsharded engine {n_slots * n_ues / unsharded_warm:8.1f})")

    # -- scaling: several shards ---------------------------------------------
    if jax.default_backend() == "cpu":
        forced = _forced_host_child(n_slots, n_ues, forced_shards)
        print(f"forced {forced['n_shards']} shards: "
              f"{forced['slot_ues_per_s']:8.1f} slot-UEs/s warm "
              f"({forced['ues_per_shard']} UEs/shard; CPU cores shared)")
    elif len(jax.devices()) > 1:
        forced = _child(n_slots, n_ues)
        print(f"{forced['n_shards']} {jax.devices()[0].device_kind} shards: "
              f"{forced['slot_ues_per_s']:8.1f} slot-UEs/s warm "
              f"({forced['ues_per_shard']} UEs/shard)")
    else:
        forced = None
        print("scaling leg skipped: it needs more than one device")
    return {
        "parity": "bitwise",
        "one_device_slot_ues_per_s": rate_1dev,
        "forced": forced,
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--child", action="store_true")
    ap.add_argument("--n-slots", type=int, default=16)
    ap.add_argument("--n-ues", type=int, default=8)
    ap.add_argument("--forced-shards", type=int, default=8)
    args = ap.parse_args()
    if args.child:
        print(json.dumps(_child(args.n_slots, args.n_ues)))
    else:
        run(args.n_slots, args.n_ues, args.forced_shards)


if __name__ == "__main__":
    main()
