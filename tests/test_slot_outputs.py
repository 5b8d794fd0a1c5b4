"""The per-slot closed-loop step's packed outputs (``repro.phy.slot_outputs``).

* The compiled step returns its state leaves and two slabs, one int32 and
  one float32, in place of one array per output leaf;
* every path of its ``SlotOutputs`` view equals the closed-loop scan's
  trajectory at that slot, bit for bit, and the per-slot loop of
  ``run_closed_loop`` returns the scan's plain dict trajectory;
* the view keeps the interface the chip benchmark reads the step through
  and its fault runs write through: nested reads, ``.values()``, item
  assignment at both levels, ``dict(out, ...)``, a UE-axis
  ``jax.tree.map``, ``block_until_ready`` and ``device_get``;
* ``pack`` / ``unpack`` keep leaves with trailing axes (the two-layer
  step's) and a leading slot axis, bit for bit.
"""

from collections.abc import Mapping

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from test_tracing import N_UES, _slot, step_inputs

from repro.core.session import (
    ArchesSession,
    CampaignSpec,
    ExpertBankSpec,
    PolicySpec,
)
from repro.phy import pipeline
from repro.phy.slot_outputs import SlotOutputs, pack, stack, unpack


@pytest.fixture(scope="module")
def step():
    return step_inputs()


@pytest.fixture(scope="module")
def three_slots(step):
    """Three per-slot calls, chained through the state, and the scan's
    trajectory over the same slots."""
    x = step
    link, sw, outs = x["link"], x["sw"], []
    for s in range(3):
        args = list(_slot(x, x["params"], s))
        args[2], args[3] = link, sw
        link, sw, out = x["eng"]._closed_slot_step(*args)
        outs.append(out)
    first3 = jax.tree.map(lambda v: v[:3], x["params"])
    _, _, traj = x["eng"]._run_closed_scan(
        x["profile"], x["sw_cfg"], x["link"], x["sw"], x["ue_keys"], first3,
        x["policy"],
    )
    return outs, traj


def _assert_same(got: dict, want: dict, what=""):
    got_l = jax.tree_util.tree_leaves_with_path(got)
    want_l = jax.tree_util.tree_leaves_with_path(want)
    assert [p for p, _ in got_l] == [p for p, _ in want_l]
    for (path, a), (_, b) in zip(got_l, want_l):
        np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b),
            err_msg=f"{what} {jax.tree_util.keystr(path)}")


def test_step_returns_its_state_and_two_slabs(step):
    x = step
    link, sw, out = x["eng"]._closed_slot_step(*_slot(x, x["params"], 0))
    n_state = len(jax.tree.leaves((link, sw)))
    assert n_state == 17
    assert len(jax.tree.leaves((link, sw, out))) == n_state + 2 <= 19
    assert isinstance(out, SlotOutputs)
    slabs = jax.tree.leaves(out)
    assert sorted(s.dtype.name for s in slabs) == ["float32", "int32"]
    assert all(s.ndim == 2 and s.shape[0] == N_UES for s in slabs)
    assert len(out.layout.fields) == 27
    assert sum(s.shape[1] for s in slabs) == 27


def test_every_path_of_the_view_equals_the_scan(three_slots):
    outs, traj = three_slots
    for s, out in enumerate(outs):
        _assert_same(out.to_dict(), jax.tree.map(lambda v: v[s], traj),
                     f"slot {s}")


def test_stacked_views_are_the_scans_dict_trajectory(three_slots):
    outs, traj = three_slots
    stacked = stack(outs)
    assert type(stacked) is dict
    _assert_same(stacked, traj)


def test_python_loop_returns_the_scans_trajectory(step):
    x = step
    from repro.phy.scenario import good_poor_good_schedule

    kw = dict(n_slots=4, n_ues=N_UES, ue_keys=x["ue_keys"])
    sched = good_poor_good_schedule(poor_start=2, poor_end=4)
    link_a, sw_a, ta = x["eng"].run_closed_loop(
        sched, x["policy"], x["sw_cfg"], use_scan=True, **kw)
    link_b, sw_b, tb = x["eng"].run_closed_loop(
        sched, x["policy"], x["sw_cfg"], use_scan=False, **kw)
    assert type(tb) is dict
    _assert_same(tb, ta)
    _assert_same((link_b, sw_b), (link_a, sw_a))


# -- the view's interface --------------------------------------------------------


@pytest.fixture
def out(three_slots):
    """A fresh view of slot 0's slabs (its reads and writes are its own)."""
    v = three_slots[0][0]
    return SlotOutputs(v.slabs, v.layout)


def test_nested_reads_are_per_ue_arrays(out, three_slots):
    traj = three_slots[1]
    for a, b in [(out["mcs"], traj["mcs"][0]),
                 (out["kpms"]["oai"]["snr"], traj["kpms"]["oai"]["snr"][0])]:
        assert isinstance(a, jax.Array) and a.shape == (N_UES,)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert set(out) == set(traj) and len(out) == len(traj)
    srcs = list(out["kpms"].values())
    assert len(srcs) == 2 and all(isinstance(m, Mapping) for m in srcs)


def test_item_assignment_at_both_levels_reaches_readers_and_leaves(out):
    snr = out["kpms"]["oai"]["snr"]
    out["kpms"]["oai"]["snr"] = snr + 0.1
    out["raw_decision"] = out["raw_decision"].at[0].set(7)
    assert float(out["kpms"]["oai"]["snr"][0]) == float(snr[0] + 0.1)
    assert int(out["raw_decision"][0]) == 7
    # the pytree leaves carry the writes too
    again = jax.tree.map(lambda v: v, out)
    assert isinstance(again, SlotOutputs)
    np.testing.assert_array_equal(np.asarray(again["kpms"]["oai"]["snr"]),
                                  np.asarray(snr + 0.1))
    assert int(jax.device_get(out)["raw_decision"][0]) == 7


def test_dict_of_the_view_with_replacements(out, three_slots):
    other = three_slots[0][1]
    d = dict(out, active_mode=other["active_mode"],
             gated_overflow=other["gated_overflow"])
    assert type(d) is dict and set(d) == set(out)
    assert d["active_mode"] is other["active_mode"]
    np.testing.assert_array_equal(np.asarray(d["mcs"]),
                                  np.asarray(out["mcs"]))
    jax.block_until_ready(d)


def test_ue_axis_tree_map_folds_every_field(out):
    half = out["tb_ok"].shape[0] // 2
    folded = jax.tree.map(
        lambda v: jnp.concatenate([v[:half], v[:half]])[: v.shape[0]], out)
    want = jax.tree.map(
        lambda v: jnp.concatenate([v[:half], v[:half]]), out.to_dict())
    _assert_same(folded.to_dict(), want)


def test_block_until_ready_and_device_get(out):
    assert jax.block_until_ready(out) is out
    assert jax.tree.leaves(out) == list(out.slabs)
    host = jax.device_get(out)
    assert isinstance(host, SlotOutputs)
    assert all(isinstance(s, np.ndarray) for s in host.slabs)
    _assert_same(host.to_dict(), out.to_dict())


# -- pack / unpack ---------------------------------------------------------------


def test_pack_keeps_trailing_axes_and_a_leading_slot_axis():
    rng = np.random.default_rng(0)
    tree = {
        "a": jnp.asarray(rng.standard_normal((3, 2)), jnp.float32),
        "b": {"c": jnp.arange(3, dtype=jnp.int32),
              "d": jnp.asarray(rng.standard_normal(3), jnp.float32)},
        "e": jnp.asarray(rng.integers(0, 9, (3, 2, 2)), jnp.int32),
        "f": jnp.asarray([True, False, True]),
    }
    slabs, layout = pack(tree)
    assert [s.shape for s in slabs] == [(3, 3), (3, 5), (3, 1)]
    assert layout.dtypes == ("float32", "int32", "bool")
    view = SlotOutputs(slabs, layout)
    _assert_same(view.to_dict(), tree)
    stacked = jax.tree.map(lambda *s: jnp.stack(s), *[view, view])
    _assert_same(stack([view, view]),
                 jax.tree.map(lambda v: jnp.stack([v, v]), tree))
    assert unpack(stacked.slabs, layout)[0].shape == (2, 3, 2)
    with pytest.raises(ValueError, match="UE axis"):
        pack({"a": jnp.zeros(3), "b": jnp.zeros(4)})


def test_two_layer_step_packs_by_the_same_code():
    """The two-layer step's outputs fit the same two slabs."""
    from repro.core.closed_loop import init_device_switch

    spec = CampaignSpec(
        path="closed_loop", n_ues=2, n_slots=2, n_prb=4, n_ant=2,
        n_layers=2,
        bank=ExpertBankSpec(execution_mode="gated", gated_capacity=1,
                            channels=4, n_res_blocks=1),
        policies=(PolicySpec(kind="threshold", feature="snr",
                             threshold=5.0),),
    )
    sess = ArchesSession(spec)
    eng, sw_cfg = sess.engine, spec.switch.to_config(spec.feature_names)
    profile, params = pipeline.resolve_schedule(
        sess.cfg, sess.schedule, spec.n_slots, spec.n_ues)
    state = (pipeline.init_device_link(spec.n_ues),
             init_device_switch(spec.n_ues, len(spec.feature_names), sw_cfg))
    keys = jax.random.split(jax.random.PRNGKey(1), spec.n_ues)
    p0 = jax.tree.map(lambda v: v[0], params)

    _, _, out = jax.eval_shape(
        lambda st, k, p, pol: pipeline._closed_slot_step(
            eng, profile, sw_cfg, *st, jnp.int32(0), k, p, pol),
        state, keys, p0, sess.device_policy)
    want = jax.eval_shape(
        lambda st, k, p, pol: eng._closed_step(
            profile, sw_cfg, pol, k, *st, jnp.int32(0), p)[2],
        state, keys, p0, sess.device_policy)
    assert sorted(out.layout.dtypes) == ["float32", "int32"]
    assert [f.path for f in out.layout.fields] == [
        tuple(k.key for k in path)
        for path, _ in jax.tree_util.tree_leaves_with_path(want)]
    assert all(sl.shape[0] == spec.n_ues for sl in out.slabs)
