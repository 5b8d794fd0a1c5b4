"""Expert B: residual-CNN channel estimator in pure JAX (paper 5.2).

Mirrors the architectures the paper cites for OFDM channel estimation
(residual CNNs treating the time-frequency response as a 2-D image, paper
refs [3, 17]): LS estimates at DMRS locations in, frequency-interpolated
full-band estimates out.  The TensorRT engine of the paper becomes a jitted
JAX apply function; training happens in-framework (``train_ai_estimator``)
on simulated OTA slots, per the build-everything rule (DESIGN.md 2).

Structure (per antenna, vmapped) — EDSR-style: signed regression, so blocks
keep linear outputs and the network predicts a *correction* on top of a
naive linear-interpolation baseline (global skip):
  input    (2, n_pilot_sc, n_dmrs_sym)    re/im as channels
  baseline naive comb-2 -> full-band linear interpolation of the LS input
  stem     3x3 conv -> C channels (linear)
  body     R residual blocks (conv-relu-conv + skip, linear output)
  upsample frequency x2 via sub-pixel shuffle
  head     3x3 conv -> 2 channels (linear)
  output   baseline + head                 (2, n_sc, n_dmrs_sym)
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.optim import AdamWConfig, adamw_init, adamw_update
from repro.phy.nr import SlotConfig


@dataclasses.dataclass(frozen=True)
class AiEstimatorConfig:
    channels: int = 32
    n_res_blocks: int = 4
    kernel_hw: tuple[int, int] = (3, 3)

    def flops(self, cfg: SlotConfig) -> float:
        """Conv MACs x2, all blocks, one image per receive antenna and
        layer (cost-model input)."""
        kh, kw = self.kernel_hw
        hw_in = cfg.n_pilot_sc * cfg.n_dmrs_sym
        hw_out = cfg.n_sc * cfg.n_dmrs_sym
        c = self.channels
        per_ant = (
            2 * kh * kw * 2 * c * hw_in  # stem
            + self.n_res_blocks * 2 * (2 * kh * kw * c * c * hw_in)  # body
            + 2 * kh * kw * c * (2 * c) * hw_in  # up-projection
            + 2 * kh * kw * (2 * c) * 2 * hw_out  # head (on upsampled grid)
        )
        return float(cfg.n_ant * cfg.n_layers * per_ant)


def _conv(x: jax.Array, w: jax.Array, b: jax.Array) -> jax.Array:
    """NCHW 'same' conv. x (C,H,W), w (O,I,kh,kw), b (O,).

    Uses the native conv primitive: fastest for the *eager* single-image
    paths (host pipeline, training).  Do NOT call this inside a
    ``lax.scan`` body — XLA:CPU's fast conv thunk does not run inside loop
    bodies (~40x fallback); the batched scan engine uses the matmul-based
    ``_forward_batched`` instead.
    """
    y = jax.lax.conv_general_dilated(
        x[None],
        w,
        window_strides=(1, 1),
        padding="SAME",
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
    )[0]
    return y + b[:, None, None]


def init_params(
    key: jax.Array, cfg: SlotConfig, net: AiEstimatorConfig = AiEstimatorConfig()
) -> dict[str, Any]:
    kh, kw = net.kernel_hw
    c = net.channels
    keys = jax.random.split(key, 3 + 2 * net.n_res_blocks)

    def he(k, o, i, scale=2.0):
        s = jnp.sqrt(scale / (i * kh * kw))
        return jax.random.normal(k, (o, i, kh, kw), jnp.float32) * s

    params = {
        "stem_w": he(keys[0], c, 2),
        "stem_b": jnp.zeros(c),
        "up_w": he(keys[1], 2 * c, c),  # sub-pixel: 2x along frequency
        "up_b": jnp.zeros(2 * c),
        # near-zero head so the net starts at the baseline interpolation
        "head_w": he(keys[2], 2, c, scale=1e-4),
        "head_b": jnp.zeros(2),
        "res": [],
    }
    for r in range(net.n_res_blocks):
        params["res"].append(
            {
                "w1": he(keys[3 + 2 * r], c, c),
                "b1": jnp.zeros(c),
                "w2": he(keys[4 + 2 * r], c, c, scale=0.2),
                "b2": jnp.zeros(c),
            }
        )
    return params


def _baseline_interp(x: jax.Array) -> jax.Array:
    """Naive comb-2 -> full-band interpolation, (..., Np, S) -> (..., 2*Np, S).

    Even output subcarriers take the pilot value; odd ones the midpoint of
    the two neighbouring pilots (edge clamped).  Leading dims (channels,
    batch) pass through.
    """
    nxt = jnp.concatenate([x[..., 1:, :], x[..., -1:, :]], axis=-2)
    mid = 0.5 * (x + nxt)
    out = jnp.stack([x, mid], axis=-2)  # (..., Np, 2, S)
    return out.reshape(*x.shape[:-2], 2 * x.shape[-2], x.shape[-1])


def _forward_one_antenna(params: dict[str, Any], x: jax.Array) -> jax.Array:
    """(2, n_pilot_sc, n_dmrs_sym) -> (2, n_sc, n_dmrs_sym)."""
    base = _baseline_interp(x)
    h = _conv(x, params["stem_w"], params["stem_b"])
    for blk in params["res"]:
        y = jax.nn.relu(_conv(h, blk["w1"], blk["b1"]))
        y = _conv(y, blk["w2"], blk["b2"])
        h = h + y  # linear block output (signed regression)
    # sub-pixel upsample x2 in frequency (comb-2 -> full band)
    u = _conv(h, params["up_w"], params["up_b"])  # (2C, Np, S)
    c = u.shape[0] // 2
    u = u.reshape(2, c, u.shape[1], u.shape[2])  # (2, C, Np, S)
    u = jnp.moveaxis(u, 0, 2).reshape(c, 2 * u.shape[2], u.shape[3])
    corr = _conv(u, params["head_w"], params["head_b"])
    return base + corr


@jax.jit
def ai_estimate_from_ls(params: dict[str, Any], h_ls: jax.Array) -> jax.Array:
    """(n_ant, n_dmrs_sym, n_pilot_sc) complex LS -> hat{H}_AI
    (n_ant, 1, n_sc, n_dmrs_sym) complex (same contract as Expert A)."""
    # to image layout (ant, 2, pilot_sc, dmrs_sym)
    x = jnp.stack([h_ls.real, h_ls.imag], axis=1).astype(jnp.float32)
    x = jnp.swapaxes(x, -1, -2)
    out = jax.vmap(_forward_one_antenna, in_axes=(None, 0))(params, x)
    h = (out[:, 0] + 1j * out[:, 1]).astype(jnp.complex64)  # (ant, n_sc, sym)
    return h[:, None]  # (ant, 1, n_sc, dmrs_sym)


# -- batched (multi-UE) forward -----------------------------------------------
#
# The batched slot engine evaluates the estimator for n_ues * n_ant images
# per slot inside a ``lax.scan`` body, where XLA:CPU's conv thunk doesn't
# run (~40x fallback) and vmapped small matmuls serialize.  The forward is
# therefore re-expressed as one large matmul per layer:
#
# * activations live in a channel-leading ``(C, W, B, H)`` layout, so the
#   flattening to matmul operands is reshape-only (no transposes between
#   layers);
# * the symbol axis ``W`` (= n_dmrs_sym, tiny) is folded into the mixing
#   matrix: a kh x kw conv becomes a kh-tap 1-D conv over frequency with
#   ``(O*W, C*W)`` tap matrices whose structure bakes in the W-direction
#   'SAME' padding.  Per layer that is kh shift-copies of the activation
#   (instead of kh*kw) and a single ``(O*W, kh*C*W) x (kh*C*W, B*H)``
#   contraction — identical math to the eager conv, BLAS/MXU-friendly
#   everywhere.
#
# Batch-composition stability (load-bearing for gated execution): every
# per-UE output column of these GEMMs is bitwise-identical regardless of
# the batch size B or the UE's position in it (the K-dim accumulation order
# is per-column).  The compaction-gated bank relies on this to be
# bitwise-equal to the concurrent path after gathering a capacity-K
# sub-batch; the gated==concurrent equality tests
# (tests/test_gated_execution.py) pin the property per backend.


def _wfold_matrices(w: jax.Array, width: int) -> jax.Array:
    """Fold the W axis of a conv kernel into tap-mixing matrices.

    ``w`` (O, C, kh, kw) -> (kh, O*width, C*width) where entry
    ``[d, o*width + wo, c*width + wi] = w[o, c, d, wi - wo + pad]``
    (zero outside the kernel — the W-direction 'SAME' padding).
    """
    o, c, kh, kw = w.shape
    pad = (kw - 1) // 2
    m = jnp.zeros((kh, o * width, c * width), w.dtype)
    for wo in range(width):
        for wi in range(width):
            dj = wi - wo + pad
            if 0 <= dj < kw:
                m = m.at[:, wo::width, wi::width].set(
                    jnp.transpose(w[:, :, :, dj], (2, 0, 1))
                )
    return m


def fold_ai_params(params: dict[str, Any], width: int) -> dict[str, Any]:
    """Pre-fold every conv kernel for width-``width`` images.

    Each layer becomes a single ``(O*width, kh*C*width)`` GEMM operand (tap
    matrices flattened tap-major to match the tap stacking in
    ``_conv_wfold``).  Done once per engine — inside the scan body only the
    GEMMs remain.
    """

    def fold(w):
        m = _wfold_matrices(w, width)  # (kh, O*W, C*W)
        kh = m.shape[0]
        return jnp.transpose(m, (1, 0, 2)).reshape(m.shape[1], kh * m.shape[2])

    return {
        "kh": int(params["stem_w"].shape[2]),
        "width": width,
        "stem_w": fold(params["stem_w"]),
        "stem_b": params["stem_b"],
        "up_w": fold(params["up_w"]),
        "up_b": params["up_b"],
        "head_w": fold(params["head_w"]),
        "head_b": params["head_b"],
        "res": [
            {
                "w1": fold(blk["w1"]),
                "b1": blk["b1"],
                "w2": fold(blk["w2"]),
                "b2": blk["b2"],
            }
            for blk in params["res"]
        ],
    }


def _conv_wfold(
    x: jax.Array,
    m2: jax.Array,
    b: jax.Array,
    kh: int,
    compute_dtype: Any = None,
) -> jax.Array:
    """'SAME' conv on channel-leading activations via one GEMM.

    ``x`` (C, W, B, H); ``m2`` (O*W, kh*C*W) pre-folded tap matrices.
    ``compute_dtype`` (e.g. ``jnp.bfloat16``) casts the GEMM *operands*
    only; accumulation stays f32 (``preferred_element_type``), as does the
    bias add — the MXU-style mixed-precision contract.  ``None`` keeps the
    original f32 ``@`` bitwise.
    """
    c, width, bsz, h = x.shape
    o = m2.shape[0] // width
    pad = (kh - 1) // 2
    xp = jnp.pad(
        x.reshape(c * width, bsz, h), ((0, 0), (0, 0), (pad, kh - 1 - pad))
    )
    taps = jnp.stack(
        [xp[:, :, d : d + h] for d in range(kh)], axis=0
    )  # (kh, C*W, B, H)
    rhs = taps.reshape(kh * c * width, bsz * h)
    if compute_dtype is None:
        y = m2 @ rhs  # (O*W, B*H)
    else:
        y = jax.lax.dot(
            m2.astype(compute_dtype),
            rhs.astype(compute_dtype),
            preferred_element_type=jnp.float32,
        )
    return y.reshape(o, width, bsz, h) + b[:, None, None, None]


def _forward_batched(
    folded: dict[str, Any], x: jax.Array, compute_dtype: Any = None
) -> jax.Array:
    """(2, W, B, n_pilot_sc) -> (2, W, B, n_sc), channel-leading layout."""
    kh = folded["kh"]
    # baseline comb-2 interpolation along the (trailing) frequency axis
    nxt = jnp.concatenate([x[..., 1:], x[..., -1:]], axis=-1)
    base = jnp.stack([x, 0.5 * (x + nxt)], axis=-1).reshape(
        *x.shape[:-1], 2 * x.shape[-1]
    )
    cd = compute_dtype
    h = _conv_wfold(x, folded["stem_w"], folded["stem_b"], kh, cd)
    for blk in folded["res"]:
        y = jax.nn.relu(_conv_wfold(h, blk["w1"], blk["b1"], kh, cd))
        y = _conv_wfold(y, blk["w2"], blk["b2"], kh, cd)
        h = h + y
    u = _conv_wfold(h, folded["up_w"], folded["up_b"], kh, cd)  # (2C,W,B,Np)
    c = u.shape[0] // 2
    u = u.reshape(2, c, *u.shape[1:])  # (2, C, W, B, Np)
    u = jnp.moveaxis(u, 0, -1).reshape(c, *u.shape[2:4], 2 * u.shape[4])
    corr = _conv_wfold(u, folded["head_w"], folded["head_b"], kh, cd)
    return base + corr


def ai_estimate_folded(
    folded: dict[str, Any],
    h_ls: jax.Array,
    *,
    compute_dtype: Any = None,
) -> jax.Array:
    """(n_ues, n_ant, n_dmrs_sym, n_pilot_sc) LS -> (n_ues, n_ant, 1, n_sc,
    n_dmrs_sym), with pre-folded params (see ``fold_ai_params``).

    ``compute_dtype=jnp.bfloat16`` runs every GEMM with bf16 operands and
    f32 accumulation (half the weight/activation bytes through the MXU);
    ``None`` is the bitwise f32 path.
    """
    n_ues, n_ant, n_sym, n_p = h_ls.shape
    x = jnp.stack([h_ls.real, h_ls.imag], axis=0).astype(jnp.float32)
    # (2, U, ant, S, Np) -> channel-leading (2, W=S, B=U*ant, H=Np)
    x = jnp.transpose(x, (0, 3, 1, 2, 4)).reshape(2, n_sym, n_ues * n_ant, n_p)
    out = _forward_batched(folded, x, compute_dtype)  # (2, S, B, n_sc)
    h = (out[0] + 1j * out[1]).astype(jnp.complex64)  # (S, B, n_sc)
    h = jnp.transpose(h, (1, 2, 0)).reshape(n_ues, n_ant, -1, n_sym)
    return h[:, :, None]  # (U, ant, 1, n_sc, S)


@jax.jit
def ai_estimate_from_ls_batched(
    params: dict[str, Any], h_ls: jax.Array
) -> jax.Array:
    """(n_ues, n_ant, n_dmrs_sym, n_pilot_sc) LS -> (n_ues, n_ant, 1, n_sc,
    n_dmrs_sym) — the multi-UE analogue of ``ai_estimate_from_ls``."""
    return ai_estimate_folded(fold_ai_params(params, h_ls.shape[2]), h_ls)


# -- in-framework training ----------------------------------------------------


def _loss(params, h_ls, h_true):
    """Task-aligned loss: the estimator's post-MRC EVM contribution.

    Plain per-element MSE is the wrong objective for a receiver: the MRC
    combiner cancels estimation error *parallel* to the channel vector
    (num/den both scale) and is hurt by the component that rotates the
    combining direction.  First-order, the symbol error an estimate
    contributes at RE (sc, sym) is

        |sum_a conj(delta_a) h_a|^2 / (sum_a |h_a|^2)^2,

    so that is exactly what we train on, with a small plain-MSE anchor for
    early-training stability.
    """
    pred = ai_estimate_from_ls(params, h_ls)
    err = pred - h_true  # (ant, 1, sc, sym)
    # MRC-aligned term
    num = jnp.abs(jnp.sum(jnp.conj(err) * h_true, axis=0)) ** 2  # (1, sc, sym)
    den = jnp.sum(jnp.abs(h_true) ** 2, axis=0) + 1e-3
    e2e = jnp.mean(num / den**2)
    mse = jnp.mean(err.real**2 + err.imag**2)
    return e2e + 0.1 * mse


@partial(jax.jit, static_argnames=("opt_cfg",))
def _train_step(params, opt_state, h_ls, h_true, lr, opt_cfg):
    loss, grads = jax.value_and_grad(_loss)(params, h_ls, h_true)
    params, opt_state = adamw_update(
        grads, opt_state, params, opt_cfg, learning_rate=lr
    )
    return params, opt_state, loss


def train_ai_estimator(
    key: jax.Array,
    cfg: SlotConfig,
    sample_fn,
    *,
    net: AiEstimatorConfig = AiEstimatorConfig(),
    steps: int = 600,
    lr: float = 1e-3,
    lr_final_frac: float = 0.05,
) -> tuple[dict[str, Any], list[float]]:
    """Train Expert B on simulated slots (AdamW + cosine decay).

    ``sample_fn(key) -> (h_ls, h_true_at_dmrs)`` with shapes
    (n_ant, n_dmrs_sym, n_pilot_sc) and (n_ant, 1, n_sc, n_dmrs_sym).
    """
    k_init, k_data = jax.random.split(key)
    params = init_params(k_init, cfg, net)
    opt_cfg = AdamWConfig(learning_rate=lr, weight_decay=0.0)
    opt_state = adamw_init(params, opt_cfg)
    losses = []
    for i in range(steps):
        k_data, k = jax.random.split(k_data)
        h_ls, h_true = sample_fn(k)
        frac = i / max(steps - 1, 1)
        cur_lr = lr * (lr_final_frac + (1 - lr_final_frac) * 0.5 * (
            1 + np.cos(np.pi * frac)))
        params, opt_state, loss = _train_step(
            params, opt_state, h_ls, h_true, cur_lr, opt_cfg
        )
        losses.append(float(loss))
    return params, losses
