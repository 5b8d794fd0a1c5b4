"""Pallas TPU kernel: the fused gated expert hot path (ROADMAP "Raw speed").

The unfused gated path pays three ops per scan step — cumsum compaction
(gather to a capacity-``K`` sub-batch), the folded-GEMM AI expert on that
sub-batch, and the ``switch_scatter`` un-compaction.  Between them the
compact sub-batch is materialized in HBM twice (input gather out, expert
output in) before the scatter reads it back.

This kernel fuses all three: the gather indirection that
``switch_gather_batched_2d`` already uses to steer its DMA *source* becomes
the *input* stage of one ``pallas_call`` whose grid walks the ``K`` compact
rows times the receive antennas.  Step ``(k, a)``:

* DMAs antenna ``a`` of UE ``idx[k]``'s LS-input tile straight from the full
  batch (the compaction index vector is scalar-prefetched to SMEM so it can
  steer the BlockSpec index maps before the grid runs — no materialized
  sub-batch);
* runs the expert forward on that one tile in VMEM (``_forward_2d``);
* writes the result directly into the UE's designated buffer, which the
  output *aliases* (``input_output_aliases``) — the scatter is just the
  output DMA.

Rows past the last selected UE (``valid[k] == 0`` — the capacity padding
the unfused path pays GEMM FLOPs for) identity-rewrite their UE's baseline
tile instead: ``idx`` is a slice of a permutation, so ``idx[k]`` is a
distinct, valid UE index even for padding rows, and the rewrite is a
single-tile round-trip, not a wasted forward pass.  UEs outside ``idx``
are never visited; aliasing leaves their baseline bytes untouched in HBM.

Kernel-local forward.  The XLA path's ``_forward_batched`` stacks the conv
taps of a 4-D ``(C, W, B, H)`` activation and flattens them into one GEMM
operand, a reshape that merges the frequency (lane) axis with others —
Mosaic refuses that shape cast.  ``_forward_2d`` computes the same network
on 2-D ``(rows, lanes)`` tiles only: rows are ``channel * W + symbol``
(zero-padded to the f32 sublane quantum), lanes are the frequency axis of
one antenna (zero-padded to the lane quantum).  A conv tap is a lane
rotation (``pltpu.roll``) plus an edge mask, the taps stack along rows
into one f32-accumulated GEMM per layer, and the sub-pixel upsample is
kept in polyphase form (even / odd output subcarriers as two tiles), so
nothing ever interleaves lanes in the kernel; ``ops.py`` does the
(de)interleave as plain data movement.  The arithmetic is the XLA path's,
in a different GEMM blocking: results agree with it to f32 rounding, not
bitwise.  ``forward_2d_ref`` is the same forward in plain jnp, which the
interpret-mode kernel equals bitwise; each served UE's result is also
independent of which other UEs share the call (one tile per grid step).
The tests pin all three.

Layout contract (``ops.py`` builds these views): LS input ``(n_ues, n_ant,
R_in, Hp)``, designated buffers ``(n_ues, n_ant, 2, R_out, Hp)`` aliased
in/out (axis 2 is the output subcarrier parity), weights from
``kernel_params``; ``Hp`` is the pilot count padded to a lane multiple.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANE = 128
_SUBLANE = 8


def _round_up(n: int, q: int) -> int:
    return -(-n // q) * q


def pad_rows(n: int) -> int:
    """Row extent of an ``n``-row activation inside the kernel."""
    return _round_up(n, _SUBLANE)


def pad_lanes(n: int) -> int:
    """Lane extent of one antenna's pilot axis inside the kernel."""
    return _round_up(n, _LANE)


def kernel_params(folded: dict) -> dict:
    """Re-lay the folded expert params for the 2-D kernel forward.

    Every layer's ``(O*W, kh*C*W)`` folded matrix keeps its tap-major
    column order, with each tap's ``C*W`` block and the ``O*W`` rows
    zero-padded to the sublane quantum; biases become ``(O_pad, 1)``
    per-row columns.  The up-projection is split into its two sub-pixel
    phases (rows ``[0, C*W)`` feed even output subcarriers, ``[C*W, 2C*W)``
    odd ones).  Zero padding adds exact zeros only.
    """
    kh, width = int(folded["kh"]), int(folded["width"])

    def layer(m2, b):
        ow = m2.shape[0]
        cw = m2.shape[1] // kh
        o_pad, c_pad = pad_rows(ow), pad_rows(cw)
        m = m2.reshape(ow, kh, cw)
        m = jnp.pad(m, ((0, o_pad - ow), (0, 0), (0, c_pad - cw)))
        rows = jnp.pad(jnp.repeat(b, width), (0, o_pad - ow))
        return m.reshape(o_pad, kh * c_pad), rows[:, None]

    c = folded["up_b"].shape[0] // 2
    up_w, up_b = folded["up_w"], folded["up_b"]
    return {
        "kh": kh,
        "width": width,
        "stem": layer(folded["stem_w"], folded["stem_b"]),
        "res": [
            (layer(blk["w1"], blk["b1"]), layer(blk["w2"], blk["b2"]))
            for blk in folded["res"]
        ],
        "up": (
            layer(up_w[: c * width], up_b[:c]),
            layer(up_w[c * width:], up_b[c:]),
        ),
        "head": layer(folded["head_w"], folded["head_b"]),
    }


def _shift(x: jax.Array, off: int, n_valid: int, roll) -> jax.Array:
    """``y[:, h] = x[:, h + off]`` on the first ``n_valid`` lanes, zero where
    ``h + off`` leaves ``[0, n_valid)`` (the conv's 'SAME' zero padding).
    Lanes past ``n_valid`` are don't-care."""
    if off == 0:
        return x
    lanes = x.shape[-1]
    rolled = roll(x, (-off) % lanes, 1)
    h = jax.lax.broadcasted_iota(jnp.int32, (1, lanes), 1) + off
    keep = jnp.logical_and(h >= 0, h < n_valid)
    return jnp.where(keep, rolled, jnp.zeros_like(rolled))


def _gemm(w, rhs, compute_dtype):
    if compute_dtype is not None:
        w, rhs = w.astype(compute_dtype), rhs.astype(compute_dtype)
    return jnp.dot(w, rhs, preferred_element_type=jnp.float32)


def _forward_2d(kp: dict, x: jax.Array, n_valid: int, compute_dtype=None,
                roll=pltpu.roll):
    """One antenna's expert forward on a 2-D tile.

    ``x`` is ``(R_in, Hp)`` — rows ``re/im * W + symbol`` — with the pilot
    axis on the first ``n_valid`` lanes.  Returns the even- and
    odd-subcarrier output tiles, each ``(R_out, Hp)`` in the same row
    order.  ``roll`` rotates lanes (``pltpu.roll`` in the kernel).
    """
    kh = kp["kh"]
    pad = (kh - 1) // 2

    def conv_taps(taps, layer):
        w, b = layer
        return _gemm(w, jnp.concatenate(taps, axis=0), compute_dtype) + b

    def conv(v, layer):
        taps = [_shift(v, d - pad, n_valid, roll) for d in range(kh)]
        return conv_taps(taps, layer)

    # baseline comb-2 interpolation: even = pilot, odd = neighbour midpoint
    # (edge clamped)
    nxt = jnp.where(
        jax.lax.broadcasted_iota(jnp.int32, (1, x.shape[-1]), 1) == n_valid - 1,
        x, _shift(x, 1, n_valid, roll),
    )
    base = (x, 0.5 * (x + nxt))

    h = conv(x, kp["stem"])
    for l1, l2 in kp["res"]:
        y = jnp.maximum(conv(h, l1), 0.0)
        h = h + conv(y, l2)
    u = tuple(conv(h, layer) for layer in kp["up"])  # sub-pixel phases
    out = []
    for j in range(2):
        # full-band tap j + d - pad lands on phase (t % 2), pilot offset t // 2
        taps = []
        for d in range(kh):
            t = j + d - pad
            taps.append(_shift(u[t % 2], t // 2, n_valid, roll))
        out.append(base[j] + conv_taps(taps, kp["head"]))
    return out


def forward_2d_ref(kp: dict, x: jax.Array, n_valid: int, compute_dtype=None):
    """``_forward_2d`` in plain jnp (``jnp.roll`` for the lane rotation): the
    kernel's arithmetic outside Pallas, the same padding and per-layer
    GEMMs, so the interpret-mode kernel equals it bitwise."""
    return _forward_2d(kp, x, n_valid, compute_dtype, roll=jnp.roll)


def gated_expert_fused(
    idx: jax.Array,
    valid: jax.Array,
    x_all: jax.Array,
    designated: jax.Array,
    kp: dict,
    *,
    n_valid: int,
    compute_dtype=None,
    interpret: bool = False,
) -> jax.Array:
    """Fused compact -> expert -> scatter over the kernel's real views.

    Args:
      idx: ``(capacity,)`` int32 — UE index of each compact row (a slice of
        a permutation: entries are distinct and in ``[0, n_ues)``).
      valid: ``(capacity,)`` int32 — 1 where the row is a selected UE
        (compute + scatter), 0 for capacity padding (identity rewrite).
      x_all: ``(n_ues, n_ant, R_in, Hp)`` f32 LS-input view of the *full*
        batch; the kernel reads only rows named by ``idx``.
      designated: ``(n_ues, n_ant, 2, R_out, Hp)`` f32 baseline view
        (aliased to the output).
      kp: ``kernel_params`` of the folded expert.
      n_valid: pilots per antenna (the unpadded lane count).
      compute_dtype: GEMM operand dtype (``None`` = f32, ``jnp.bfloat16`` =
        half the MXU operand bytes, f32 accumulation).
      interpret: run in Pallas interpret mode (CPU validation).

    Returns:
      ``(n_ues, n_ant, 2, R_out, Hp)`` array aliased onto ``designated``.
    """
    capacity = idx.shape[0]
    n_ues, n_ant, r_in, hp = x_all.shape
    r_out = designated.shape[3]
    if designated.shape != (n_ues, n_ant, 2, r_out, hp):
        raise ValueError(f"x_all {x_all.shape} vs designated {designated.shape}")
    if valid.shape != (capacity,):
        raise ValueError(f"valid {valid.shape} vs idx {idx.shape}")

    idx = jnp.asarray(idx, jnp.int32)
    valid = jnp.asarray(valid, jnp.int32)
    static = {k: kp[k] for k in ("kh", "width")}
    leaves, treedef = jax.tree.flatten(
        {k: v for k, v in kp.items() if k not in static}
    )

    def kernel(idx_ref, valid_ref, x_ref, des_ref, *rest):
        *leaf_refs, out_ref = rest
        k = pl.program_id(0)

        @pl.when(valid_ref[k] == 1)
        def _compute_path():
            params = {**jax.tree.unflatten(treedef, [r[...] for r in leaf_refs]),
                      **static}
            even, odd = _forward_2d(params, x_ref[0, 0], n_valid, compute_dtype)
            out_ref[0, 0, 0] = even
            out_ref[0, 0, 1] = odd

        @pl.when(valid_ref[k] == 0)
        def _pad_path():
            out_ref[...] = des_ref[...]

    def ue_index(k, a, idx_ref, valid_ref):
        del valid_ref
        return (idx_ref[k], a, 0, 0)

    def des_index(k, a, idx_ref, valid_ref):
        del valid_ref
        return (idx_ref[k], a, 0, 0, 0)

    def const_index(ndim):
        def index(k, a, idx_ref, valid_ref):
            del k, a, idx_ref, valid_ref
            return (0,) * ndim

        return index

    des_spec = pl.BlockSpec((1, 1, 2, r_out, hp), des_index)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(capacity, n_ant),
        in_specs=[pl.BlockSpec((1, 1, r_in, hp), ue_index), des_spec]
        + [pl.BlockSpec(leaf.shape, const_index(leaf.ndim)) for leaf in leaves],
        out_specs=des_spec,
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(designated.shape, designated.dtype),
        input_output_aliases={3: 0},  # designated buffer -> output (zero-gap)
        interpret=interpret,
        name="gated_expert",
    )(idx, valid, x_all, designated, *leaves)
