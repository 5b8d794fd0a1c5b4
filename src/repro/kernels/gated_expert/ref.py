"""Pure-jnp oracle for the fused gated expert kernel.

Literally the unfused composition the kernel replaces — gather the selected
UEs' inputs to a compact sub-batch, run the folded-GEMM expert, scatter the
results back over the baseline — built from the exact same jnp ops as
``ExpertBank._run_gated``'s unfused path, so bitwise equality with it holds
by construction.  It is what ``backend="auto"`` runs off the chip (CPU
runs and tests); on a TPU ``auto`` always runs the compiled kernel.
"""

from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.switch_select.ops import switch_scatter
from repro.phy.ai_estimator import ai_estimate_folded


def gated_expert_apply_ref(
    idx, src, h_ls, designated, folded, *, compute_dtype=None
):
    """Compact -> folded-GEMM expert -> scatter, unfused reference.

    Args:
      idx: ``(capacity,)`` int32 compact-row -> UE index map.
      src: ``(n_ues,)`` int32 UE -> compact-row map (negative == keep the
        designated baseline).
      h_ls: ``(n_ues, n_ant, n_dmrs_sym, n_pilot_sc)`` complex LS input.
      designated: ``(n_ues, n_ant, 1, n_sc, n_dmrs_sym)`` complex baseline.
      folded: pre-folded expert params (``fold_ai_params``).
      compute_dtype: GEMM operand dtype (``None`` = f32).

    Returns:
      The baseline with the gated expert's outputs scattered in.
    """
    compact_in = jnp.take(h_ls, idx, axis=0)
    compact_out = ai_estimate_folded(
        folded, compact_in, compute_dtype=compute_dtype
    )
    # the same jit'd scatter the unfused bank path calls, so both paths
    # trace to the same program on CPU (bitwise AND wall-time parity)
    return switch_scatter(src, compact_out, designated, backend="ref")
