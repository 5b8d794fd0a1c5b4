"""The correctness check: the timed path's outputs against the reference.

Three layers, each against the plain reference (``reference.py``) or a
replay written here, never against the program's own code:

* the expert bank and everything after it: for a sample of the window's
  slot-UEs, drawn from the seed and split between the two experts, the
  reference recomputes the slot from the seed with the expert the program
  served and the link state the program reported before that slot (its
  SNR report, and the outer-loop offset rebuilt from its decoding
  outcomes).  Read: the SNR KPM (dB) and the RSRP KPM (relative), by
  their widest gap per expert; the MCS; the transport block outcome,
  except where the decoding draw lies within ``TB_BAND`` of its
  probability;
* the switching policy: the program's fitted tree against the tree pinned
  in the limits file (``tree.py``: a Gini fit written here, run once on the
  program's profile KPMs), node by node and threshold by threshold;
* the closed-loop decisions, over every slot: the rolling window mean of
  the reported KPMs through the pinned tree (a walk written here), the
  hysteresis register and the commit at the next boundary; UE-slots whose
  window mean lies within ``TIE_REL`` of a threshold on their path are not
  counted;
* the gated bank's capacity: which UEs overflow to the fail-safe expert,
  over every slot.

The numbers ``bench/limits/<cell>.json`` names are compared, each with its
limit; one of them that a run cannot compute fails.
"""

from __future__ import annotations

import numpy as np

from bench.harness import tree as tree_mod
from bench.harness.reference import SlotReference

TB_BAND = 0.05
TIE_REL = 1e-5
MCS_BAND_DB = 1e-3
SAMPLE_SALT = 0x5A11


def replay_decisions(feats, tree, window: int):
    """The tree's decision in every slot from the recorded KPMs.

    ``feats`` (slots, UEs, F), ``tree`` as ``tree.as_arrays`` gives it.
    Returns ``(raw, tied)``: the decision on
    the rolling ``window``-slot mean, and where that mean lies within
    ``TIE_REL`` of a threshold on the decision path.
    """
    n_s, n_u, _ = feats.shape
    csum = np.cumsum(feats.astype(np.float64), axis=0)
    prev = np.concatenate([np.zeros((window, n_u, feats.shape[2])),
                           csum[:-window]]) if n_s > window else None
    lo = np.zeros_like(csum) if prev is None else prev[:n_s]
    count = np.minimum(np.arange(1, n_s + 1), window)[:, None, None]
    mean = (csum - lo) / count
    depth = tree["depth"]
    node = np.zeros((n_s, n_u), np.int64)
    tied = np.zeros((n_s, n_u), bool)
    for _ in range(depth):
        f = tree["feature"][node]
        t = tree["threshold"][node]
        x = np.take_along_axis(mean, f[..., None], axis=2)[..., 0]
        finite = np.isfinite(t)
        tied |= finite & (np.abs(x - t) <= TIE_REL * np.maximum(np.abs(t),
                                                                 1e-6))
        right = finite & (x > t)
        node = np.where(right, 2 * node + 2, 2 * node + 1)
    raw = tree["leaf_values"][node - (2 ** depth - 1)]
    return raw, tied


def commit(raw, hysteresis: int, default_mode: int):
    """Mode active in each slot from the raw decisions (register commits
    after ``hysteresis`` disagreeing decisions, applied at the boundary)."""
    n_s, n_u = raw.shape
    pending = np.full(n_u, default_mode)
    streak = np.zeros(n_u, int)
    active = np.empty_like(raw)
    for s in range(n_s):
        active[s] = pending
        agree = raw[s] == pending
        streak = np.where(agree, 0, streak + 1)
        do = streak >= hysteresis
        pending = np.where(do, raw[s], pending)
        streak = np.where(do, 0, streak)
    return active


def overflow_of(active, capacity: int):
    gated = active == 0
    pos = np.cumsum(gated, axis=1) - 1
    return gated & (pos >= capacity)


def sample(served_ai, first: int, seed: int, n: int):
    """Slot-UEs of the window to recompute, half from each expert."""
    rng = np.random.default_rng([seed % 2**63, SAMPLE_SALT])
    picks = []
    for flag in (True, False):
        s, u = np.nonzero(served_ai[first:] == flag)
        k = min(len(s), n // 2)
        idx = rng.choice(len(s), size=k, replace=False) if k else []
        picks += [(int(s[i]) + first, int(u[i]), flag) for i in idx]
    return sorted(picks)


def gaps(ctx, run: dict, answers: dict | None = None) -> dict:
    """Every compared number of one run (no limits applied).

    The reference is fed the run's recorded history; the sampled answers
    compared are the run's own, or ``answers`` where given (the control
    puts its own there)."""
    cfg, out = ctx.config, run["outputs"]
    ans = out if answers is None else answers
    prog = run["program"]
    feats = np.stack([out["feat/" + n] for n in cfg["policy_features"]], -1)
    active = out["active_mode"].astype(int)
    raw_dev = out["raw_decision"].astype(int)
    pinned = ctx.limits["tree"]
    if tuple(pinned["features"]) != tuple(cfg["policy_features"]):
        raise ValueError(f"the pinned tree reads {pinned['features']}")
    raw_ref, tied = replay_decisions(feats, tree_mod.as_arrays(pinned),
                                     cfg["window_slots"])
    # the register follows the program's own raw decisions, so one
    # mismatch is counted once and does not shift the rest
    active_ref = commit(raw_dev, cfg["hysteresis_slots"], cfg["default_mode"])
    overflow = out["gated_overflow"].astype(bool)
    tree_mismatch, tree_gap = tree_mod.compare(prog.tree, pinned)
    g = {
        "tree_mismatch": tree_mismatch,
        "tree_threshold_gap": tree_gap,
        "decision_mismatch": int(np.sum((raw_ref != raw_dev) & ~tied)),
        "commit_mismatch": int(np.sum(active_ref != active)),
        "overflow_mismatch": int(np.sum(overflow_of(active, prog.capacity)
                                        != overflow)),
    }
    served_ai = (active == 0) & ~overflow
    first = run["window"].first_timed
    picks = sample(served_ai, first, ctx.seed,
                   ctx.traffic["check_sample_slot_ues"])
    ref = SlotReference(cfg, ctx.traffic, ctx.seed, host_weights(run))
    snr_gap = {True: [], False: []}
    rsrp_gap = {True: [], False: []}
    mcs_bad = tb_bad = 0
    init = cfg["link"]["initial_snr_db"]
    th = ref.mcs_thresholds()
    for s, u, ai in picks:
        reported = init if s == 0 else float(out["kpms/oai/snr"][s - 1, u])
        olla = ref.olla(out["tb_ok"][:s, u] > 0.5)
        r = ref.slot(s, u, reported, olla, ai)
        if np.min(np.abs(th - r["mcs_input_db"])) > MCS_BAND_DB:
            mcs_bad += int(r["mcs"] != int(ans["mcs"][s, u]))
        snr_gap[ai].append(abs(float(ans["kpms/oai/snr"][s, u]) - r["snr"]))
        rsrp_gap[ai].append(abs(float(ans["kpms/aerial/rsrp"][s, u])
                                - r["rsrp"]) / r["rsrp"])
        if abs(r["tb_u"] - r["tb_p"]) > TB_BAND:
            tb_bad += int(r["tb_ok"] != (ans["tb_ok"][s, u] > 0.5))
    g["mcs_mismatch"] = mcs_bad
    g["tb_mismatch"] = tb_bad
    for ai, name in ((True, "ai"), (False, "mmse")):
        if snr_gap[ai]:
            g[f"snr_gap_db.{name}"] = float(np.max(snr_gap[ai]))
            g[f"rsrp_rel_gap.{name}"] = float(np.max(rsrp_gap[ai]))
    g["sampled_slot_ues"] = len(picks)
    return g


def host_weights(run: dict) -> dict:
    """The run's AI weights as NumPy arrays (the reference's copy)."""
    import jax

    return jax.tree.map(np.asarray, jax.device_get(run["weights"]))


def check(ctx, run: dict) -> dict:
    """``{name: {"value", "limit", "ok"}}`` for every limited number."""
    values = gaps(ctx, run)
    checks = {}
    for name, limit in ctx.limits["limits"].items():
        v = values.get(name)
        checks[name] = {"value": v, "limit": limit,
                        "ok": v is not None and v <= limit}
    return checks
