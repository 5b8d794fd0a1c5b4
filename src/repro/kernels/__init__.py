# Pallas TPU kernels for the ARCHES hot spots (validated on CPU via
# interpret=True; see per-kernel ref.py for the pure-jnp oracles):
#   switch_select — the paper's CUDA switch kernel (zero-gap output selection)
#   mmse_interp   — MMSE/Wiener frequency-domain interpolation (MXU matmul)
#   tree_infer    — vectorized decision-tree policy inference
#   lmmse_detect  — per-RE LMMSE detection of a multi-layer uplink (VPU)
