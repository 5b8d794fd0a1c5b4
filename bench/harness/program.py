"""Everything the benchmark takes from the system under test.

The benchmark drives ARCHES through ``ArchesSession`` and the engine's
compiled one-slot closed-loop step, and reads back the step's outputs.  It
makes the AI expert's weights itself (on the device, from the
configuration's seed), so the reference can use the same numbers without
taking them from the program.
Before anything runs, the program's own slot numerology, TDL profile,
switch configuration and per-slot channel conditions are checked against
what the configuration and traffic files state: the reference is built from
those files, so the two must describe the same deployment.
"""

from __future__ import annotations

import dataclasses

import numpy as np

#: the step outputs the checks and the per-layer readers use, by path
OUTPUT_LEAVES = (
    ("active_mode",), ("raw_decision",), ("gated_overflow",), ("tb_ok",),
    ("mcs",), ("tbs",), ("kpms", "oai", "snr"), ("kpms", "aerial", "rsrp"),
)


def seed_key(seed: int):
    """A PRNG key for any whole-number seed (``PRNGKey`` keeps 32 bits)."""
    import jax

    return jax.random.fold_in(
        jax.random.PRNGKey(seed % 2**32), (seed // 2**32) % 2**31
    )


def make_ai_weights(config: dict):
    """The AI expert's weights, drawn in one jitted call on the device from
    the configuration's ``ai_weights_seed``.

    The layout is the program's parameter tree (``stem``, ``res`` blocks,
    ``up``, ``head``), He-initialised as ``repro.phy.ai_estimator.
    init_params`` does: a near-zero head, so the untrained net's output is
    the comb-2 baseline plus a small learned correction.  The seed is the
    configuration's and not the run's: the engine folds the weights into
    its compiled programs as constants, so weights drawn per run would make
    every run compile afresh.
    """
    import jax
    import jax.numpy as jnp

    c = config["ai_channels"]
    kh, kw = config["ai_kernel_hw"]
    n_res = config["ai_res_blocks"]

    def he(k, o, i, scale=2.0):
        s = jnp.sqrt(scale / (i * kh * kw))
        return jax.random.normal(k, (o, i, kh, kw), jnp.float32) * s

    def init(key):
        keys = jax.random.split(key, 3 + 2 * n_res)
        return {
            "stem_w": he(keys[0], c, 2), "stem_b": jnp.zeros(c),
            "up_w": he(keys[1], 2 * c, c), "up_b": jnp.zeros(2 * c),
            "head_w": he(keys[2], 2, c, scale=1e-4), "head_b": jnp.zeros(2),
            "res": [
                {"w1": he(keys[3 + 2 * r], c, c), "b1": jnp.zeros(c),
                 "w2": he(keys[4 + 2 * r], c, c, scale=0.2),
                 "b2": jnp.zeros(c)}
                for r in range(n_res)
            ],
        }

    key = seed_key(config["ai_weights_seed"])
    return jax.block_until_ready(jax.jit(init)(key))


def build_session(config: dict, traffic: dict, weights, host_policies=None):
    """The closed-loop session the cell runs, as a user would declare it
    (``host_policies``: an already fitted policy to reuse)."""
    from repro.core.session import (
        ArchesSession, CampaignSpec, ExpertBankSpec, PolicySpec, SwitchSpec,
    )

    spec = CampaignSpec(
        path="closed_loop",
        scenario=traffic["scenario"],
        scenario_args=tuple(sorted(traffic["scenario_args"].items())),
        n_prb=config["n_prb"],
        n_ues=config["n_ues"],
        n_slots=traffic["cycle_slots"],
        seed=0,
        bank=ExpertBankSpec(
            execution_mode="gated", gated_capacity=config["gated_capacity"],
            channels=config["ai_channels"],
            n_res_blocks=config["ai_res_blocks"],
        ),
        policies=(PolicySpec(kind="tree", depth=config["tree_depth"],
                             train_ues=config["policy_train_ues"]),),
        switch=SwitchSpec(window_slots=config["window_slots"],
                          hysteresis_slots=config["hysteresis_slots"],
                          default_mode=config["default_mode"]),
    )
    session = ArchesSession(spec, ai_params=weights,
                            host_policies=host_policies)
    check_matches(session, config, traffic)
    return session


def check_matches(session, config: dict, traffic: dict) -> None:
    """Refuse to run where the program and the files describe different
    deployments (the reference follows the files)."""
    cfg = session.cfg
    for key in ("n_prb", "scs_khz", "n_ant", "n_layers", "dmrs_comb_offset"):
        if getattr(cfg, key) != config[key]:
            raise ValueError(f"program's {key}={getattr(cfg, key)} but the "
                             f"configuration states {config[key]}")
    if tuple(session.spec.feature_names) != tuple(config["policy_features"]):
        raise ValueError(f"program's policy features "
                         f"{session.spec.feature_names}")
    if tuple(cfg.dmrs_symbols) != tuple(config["dmrs_symbols"]):
        raise ValueError(f"program's DMRS symbols {cfg.dmrs_symbols}")
    net = session.net
    if tuple(net.kernel_hw) != tuple(config["ai_kernel_hw"]):
        raise ValueError(f"program's AI kernel {net.kernel_hw}")
    stated = {name: cond for name, cond in traffic["conditions"].items()}
    prof = config["tdl_profile"]
    for s in range(traffic["cycle_slots"]):
        ch = session.schedule(s)
        want = stated[condition_at(traffic, s)]
        got = {k: getattr(ch, k) for k in want}
        if any(not np.isclose(float(got[k]), float(want[k]), rtol=1e-12)
               for k in want):
            raise ValueError(f"slot {s}: program's channel {got} but the "
                             f"traffic states {want}")
        if (tuple(ch.profile.delays_s) != tuple(prof["delays_s"])
                or tuple(ch.profile.powers_db) != tuple(prof["powers_db"])
                or ch.profile.doppler_hz != prof["doppler_hz"]):
            raise ValueError(f"program's TDL profile {ch.profile}")


def condition_at(traffic: dict, slot: int) -> str:
    s = slot % traffic["cycle_slots"]
    for ph in traffic["phases"]:
        if ph["start"] <= s < ph["end"]:
            return ph["condition"]
    raise ValueError(f"traffic {traffic['name']} has no phase for slot {s}")


@dataclasses.dataclass
class SlotProgram:
    """The compiled one-slot closed-loop step and what it is fed."""

    step: object  # (link, sw, slot_idx, params) -> (link, sw, out)
    link0: object
    sw0: object
    params: list  # one ChannelParams pytree per slot of the traffic cycle
    tree: object  # the fitted tree: feature, threshold, leaf_values, depth
    capacity: int


def slot_program(session, seed: int) -> SlotProgram:
    """Resolve the session into the per-slot step the window drives.

    The step is ``BatchedPuschPipeline._closed_slot_step``: the same
    ``_closed_step`` body as the closed-loop scan and as the per-slot path
    of ``run_closed_loop(use_scan=False)``.
    """
    import jax
    import jax.numpy as jnp

    from repro.core.closed_loop import init_device_switch
    from repro.phy.pipeline import init_device_link, resolve_schedule

    spec, eng = session.spec, session.engine
    sw_cfg = spec.switch.to_config(spec.feature_names)
    policy = session.device_policy
    profile, stacked = resolve_schedule(eng.cfg, session.schedule,
                                        spec.n_slots, spec.n_ues)
    params = [jax.tree.map(lambda x, s=s: x[s], stacked)
              for s in range(spec.n_slots)]
    root = seed_key(seed)
    ue_keys = jax.vmap(lambda u: jax.random.fold_in(root, u))(
        jnp.arange(spec.n_ues))
    fn = eng._closed_slot_step

    def step(link, sw, slot_idx, p):
        return fn(profile, sw_cfg, link, sw, slot_idx, ue_keys, p, policy)

    return SlotProgram(
        step=step,
        link0=init_device_link(spec.n_ues),
        sw0=init_device_switch(spec.n_ues, len(spec.feature_names), sw_cfg),
        params=params,
        tree=session.host_policies[0].tree,
        capacity=eng.bank.gated_capacity,
    )


def pick(out: dict, path: tuple):
    for k in path:
        out = out[k]
    return out


def flat_kpms(out: dict) -> dict:
    flat = {}
    for src in out["kpms"].values():
        flat.update(src)
    return flat
