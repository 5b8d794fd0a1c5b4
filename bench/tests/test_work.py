"""The benchmark's own work counts agree with the repository's cost model."""

import json
import os

import pytest

from bench.tests.tiny import REPO


def _config(name):
    with open(os.path.join(REPO, "bench", "configs", f"{name}.json")) as f:
        return json.load(f)


def _work(name):
    import importlib.util

    path = os.path.join(REPO, "bench", "work", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"w_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("name", ["x5g_106prb", "nr100_273prb"])
def test_work_counts_match_program_cost_model(name):
    from repro.phy.ai_estimator import AiEstimatorConfig
    from repro.phy.estimators import estimator_flops
    from repro.phy.nr import SlotConfig

    cfg = _config(name)
    slot = SlotConfig(n_prb=cfg["n_prb"])
    net = AiEstimatorConfig(channels=cfg["ai_channels"],
                            n_res_blocks=cfg["ai_res_blocks"],
                            kernel_hw=tuple(cfg["ai_kernel_hw"]))
    ai, mmse = _work("ai_expert"), _work("mmse_interp")
    assert mmse.expert_flops_per_ue(cfg) == estimator_flops(slot)
    # the program counts the head from 2C channels; it has C
    assert ai.flops_per_ue(cfg) + ai.head_overcount(cfg) == net.flops(slot)
    b, n_p, n_sc = mmse.shapes(cfg)
    assert (n_p, n_sc) == (slot.n_pilot_sc, slot.n_sc)
    assert b == cfg["n_ues"] * slot.n_ant * slot.n_dmrs_sym
    # Gauss: three real products of the four a complex product needs
    assert mmse.kernel_flops(b, n_p, n_sc) * 8 / 6 == (
        cfg["n_ues"] * estimator_flops(slot))


def test_mmse_interp_roofline_bound_at_106_prb():
    mmse = _work("mmse_interp")
    b, n_p, n_sc = mmse.shapes(_config("x5g_106prb"))
    assert (b, n_p, n_sc) == (192, 636, 1272)
    assert mmse.kernel_flops(b, n_p, n_sc) == 6 * 192 * 636 * 1272
    assert mmse.kernel_bytes(b, n_p, n_sc) == 8 * (192 * 636 + 636 * 1272
                                                   + 192 * 1272)


#: the counts at one layer, as the files gave them before they counted
#: layers: AI flops per UE, the AI head's overcount, MMSE flops per UE,
#: and the kernel's shapes, flops and bytes
ONE_LAYER = {
    "x5g_106prb": (1433106432.0, 17584128.0, 77663232.0, (192, 636, 1272),
                   931958784.0, 9402624.0),
    "nr100_273prb": (3690925056.0, 45287424.0, 515144448.0,
                     (192, 1638, 3276), 6181733376.0, 50476608.0),
}


def _counts(cfg):
    ai, mmse = _work("ai_expert"), _work("mmse_interp")
    shape = mmse.shapes(cfg)
    return (ai.flops_per_ue(cfg), ai.head_overcount(cfg),
            mmse.expert_flops_per_ue(cfg), shape, mmse.kernel_flops(*shape),
            mmse.kernel_bytes(*shape))


@pytest.mark.parametrize("name", sorted(ONE_LAYER))
def test_work_counts_at_one_layer_are_unchanged(name):
    cfg = _config(name)
    assert cfg["n_layers"] == 1
    assert _counts(cfg) == ONE_LAYER[name]
    assert _counts({k: v for k, v in cfg.items() if k != "n_layers"}) == (
        ONE_LAYER[name])


@pytest.mark.parametrize("name", sorted(ONE_LAYER))
def test_work_counts_count_one_estimate_per_antenna_and_layer(name):
    cfg = _config(name)
    ai_1, head_1, mmse_1, (b_1, n_p, n_sc), _, _ = _counts(cfg)
    ai_2, head_2, mmse_2, shape_2, _, _ = _counts(dict(cfg, n_layers=2))
    assert (ai_2, head_2, mmse_2) == (2 * ai_1, 2 * head_1, 2 * mmse_1)
    assert shape_2 == (2 * b_1, n_p, n_sc)
    assert _counts(dict(cfg, n_ant=8)) == _counts(dict(cfg, n_ant=4,
                                                       n_layers=2))
