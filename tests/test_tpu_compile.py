"""The main-path Pallas kernels compile for a TPU v5e at real carrier widths.

No chip is needed: the TPU compiler is installed, and it compiles for a
described ``v5e:2x2`` topology that is not attached.  Every other test runs
these kernels in interpret mode or as their jnp ``ref`` twins, which cannot
show what Mosaic refuses (an unsupported shape cast, an unaligned slice, a
tile that overflows VMEM).  Each case compiles one kernel at U = 16 UEs for
the paper's 106-PRB cell or a 273-PRB (100 MHz at 30 kHz) carrier and
checks that the compiled program holds the ``tpu_custom_call``.  The whole
closed-loop slot step, compiled at a small width, keeps its kernels' names
and its six stage scopes (``repro.tracing``), which the benchmark's trace
readers rely on.

The topology is described inside a module-scoped fixture, never at import:
only the worker that runs this file loads the TPU library.  JAX's
persistent compilation cache is off around these compiles, since an entry
compiled for a described chip cannot be read back without one.
"""

import importlib.util
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.gated_expert import gated_expert_apply
from repro.kernels.lmmse_detect import lmmse_detect
from repro.kernels.mmse_interp import mmse_interp
from repro.kernels.switch_select.ops import (
    switch_gather_batched_leaf,
    switch_select_batched_leaf,
)
from repro.kernels.tree_infer import pack_tree, tree_infer
from repro.phy.ai_estimator import AiEstimatorConfig, fold_ai_params, init_params
from repro.phy.equalizer import time_weights
from repro.phy.nr import SlotConfig

N_UES = 16
WIDTHS = (106, 273)
NET = AiEstimatorConfig(channels=32, n_res_blocks=4)


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e, with the persistent cache turned off.

    Skips only where the TPU compiler (``libtpu``) is not installed; any
    other failure to describe the topology fails every case."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    if importlib.util.find_spec("libtpu") is None:
        pytest.skip("libtpu is not installed: no TPU compiler here")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def _mmse(cfg, s):
    w = jnp.zeros((cfg.n_pilot_sc, cfg.n_sc), jnp.complex64)
    h = s((N_UES, cfg.n_ant, cfg.n_dmrs_sym, cfg.n_pilot_sc), jnp.complex64)
    return _compiled_text(lambda h: mmse_interp(h, w, interpret=False), h)


def _estimates(cfg, s, n=N_UES):
    return s((n, cfg.n_ant, 1, cfg.n_sc, cfg.n_dmrs_sym), jnp.complex64)


def _switch_select(cfg, s):
    return _compiled_text(
        lambda m, a, d: switch_select_batched_leaf(m, [a], d, interpret=False),
        s((N_UES,), jnp.int32), _estimates(cfg, s), _estimates(cfg, s),
    )


def _switch_gather(cfg, s):
    return _compiled_text(
        lambda src, c, d: switch_gather_batched_leaf(src, c, d, interpret=False),
        s((N_UES,), jnp.int32), _estimates(cfg, s, N_UES // 2),
        _estimates(cfg, s),
    )


def _tree(cfg, s):
    del cfg  # the policy's input is the KPM vector, whatever the carrier
    depth, n_features = 2, 10
    tree = pack_tree(
        np.array([5, 1, 3]), np.array([18.0, 9.0, 0.5], np.float32),
        np.array([1.0, 0.0, 1.0, 0.0], np.float32), n_features, depth,
    )
    return _compiled_text(
        lambda x: tree_infer(x, tree, interpret=False),
        s((N_UES, n_features), jnp.float32),
    )


def _gated_expert(cfg, s):
    folded = fold_ai_params(
        init_params(jax.random.PRNGKey(0), cfg, NET), cfg.n_dmrs_sym
    )
    return _compiled_text(
        lambda idx, src, h, d: gated_expert_apply(
            idx, src, h, d, folded, backend="pallas", interpret=False
        ),
        s((N_UES // 2,), jnp.int32), s((N_UES,), jnp.int32),
        s((N_UES, cfg.n_ant, cfg.n_dmrs_sym, cfg.n_pilot_sc), jnp.complex64),
        _estimates(cfg, s),
    )


def _lmmse(cfg, s):
    """Two layers, the cell's 4 RX, at the carrier's width."""
    tw = jnp.asarray(time_weights(cfg), jnp.float32)
    return _compiled_text(
        lambda h, y, nv: lmmse_detect(h, y, nv, tw, interpret=False),
        s((N_UES, cfg.n_ant, 2, cfg.n_sc, cfg.n_dmrs_sym), jnp.complex64),
        s((N_UES, cfg.n_ant, cfg.n_sc, cfg.n_sym), jnp.complex64),
        s((N_UES,), jnp.float32),
    )


KERNELS = {
    "mmse_interp": _mmse,
    "switch_select_batched_leaf": _switch_select,
    "switch_gather_batched_leaf": _switch_gather,
    "tree_infer": _tree,
    "gated_expert_fused": _gated_expert,
    "lmmse_detect": _lmmse,
}


@pytest.mark.parametrize("n_prb", WIDTHS)
@pytest.mark.parametrize("kernel", list(KERNELS))
def test_kernel_compiles_for_v5e(one_chip, kernel, n_prb):
    cfg = SlotConfig(n_prb=n_prb)
    text = KERNELS[kernel](cfg, lambda shape, dt: _spec(one_chip, shape, dt))
    assert "tpu_custom_call" in text, f"{kernel} lowered without a Mosaic kernel"


def _slot_step_text(one_chip, cfg):
    from test_tracing import _slot, step_inputs

    from repro.phy import pipeline

    x = step_inputs(cfg, n_ues=4)
    spec = lambda tree: jax.tree.map(  # noqa: E731
        lambda v: _spec(one_chip, np.shape(v), jnp.asarray(v).dtype), tree)
    args = [spec(a) if i not in (0, 1) else a
            for i, a in enumerate(_slot(x, x["params"], 3))]
    try:
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(jax, "default_backend", lambda: "tpu")
            jax.clear_caches()
            lowered = pipeline._closed_slot_step.lower(x["eng"], *args)
    finally:
        jax.clear_caches()
    return lowered.compile().as_text()


def _kernel_names(text):
    return re.findall(r"^\s*(?:ROOT )?%([\w.\-]+) = .*"
                      r'custom_call_target="tpu_custom_call"',
                      text, flags=re.M)


def test_two_layer_slot_step_compiles_with_its_detector_for_v5e(one_chip):
    """The two-layer closed-loop step (24 PRB, 4 UEs, 4 RX) compiles for a
    v5e with the ``lmmse_detect`` kernel under its own name, beside the
    bank's kernels, and keeps every stage scope."""
    from repro import tracing

    text = _slot_step_text(one_chip, SlotConfig(n_prb=24, n_layers=2))
    kernels = _kernel_names(text)
    for name in ("lmmse_detect", "mmse_interp", "tree_infer"):
        assert any(k == name or k.startswith(name + ".") for k in kernels), (
            name, kernels)
    names = set(re.findall(r'op_name="([^"]*)"', text))
    for stage in tracing.STAGES:
        assert any(re.search(rf"(?<![\w.]){re.escape(stage)}(?![\w.])", n)
                   for n in names), stage


@pytest.fixture(scope="module")
def slot_step_text(one_chip):
    """The closed-loop slot step (the program the benchmark drives),
    compiled for one v5e chip at 24 PRB and 4 UEs.  ``jax.default_backend``
    is steered to ``"tpu"`` while it is traced, so that the step takes its
    Pallas kernels as it does on the chip.  JAX's trace caches are cleared
    around it: a kernel traced earlier in this process for the CPU would
    be reused in interpret mode, and this trace must not be reused by a
    later CPU test."""
    return _slot_step_text(one_chip, SlotConfig(n_prb=24))


def test_slot_step_keeps_its_kernel_names_for_v5e(slot_step_text):
    """The benchmark's kernel readers match a Pallas kernel's instruction
    by its name: the stage scopes must not rename them."""
    kernels = re.findall(r"^\s*(?:ROOT )?%([\w.\-]+) = .*"
                         r'custom_call_target="tpu_custom_call"',
                         slot_step_text, flags=re.M)
    for name in ("mmse_interp", "tree_infer"):
        assert any(k == name or k.startswith(name + ".") for k in kernels), (
            name, kernels)


def test_slot_step_carries_every_stage_scope_for_v5e(slot_step_text):
    from repro import tracing

    names = set(re.findall(r'op_name="([^"]*)"', slot_step_text))
    for stage in tracing.STAGES:
        assert any(re.search(rf"(?<![\w.]){re.escape(stage)}(?![\w.])", n)
                   for n in names), stage
