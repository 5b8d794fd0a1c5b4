"""Work of the ``mmse_interp`` kernel (Wiener interpolation) from shapes.

One call interpolates ``B`` pilot vectors of ``Np`` complex values to
``Nsc`` subcarriers: ``out (B, Nsc) = H (B, Np) @ W (Np, Nsc)``, complex,
held as f32 real and imaginary planes.  ``B`` is UEs x receive ports x
DMRS symbols.  Unpadded shapes throughout.
"""


def shapes(config: dict) -> tuple:
    n_sc = 12 * config["n_prb"]
    b = config["n_ues"] * config["n_ant"] * len(config["dmrs_symbols"])
    return b, n_sc // 2, n_sc


def kernel_flops(b: int, n_p: int, n_sc: int) -> float:
    """The least real flops: the Gauss three-product complex matmul."""
    return 6.0 * b * n_p * n_sc


def kernel_bytes(b: int, n_p: int, n_sc: int) -> float:
    """H, W and the output, each read or written once, f32 planes."""
    return 8.0 * (b * n_p + n_p * n_sc + b * n_sc)


def expert_flops_per_ue(config: dict) -> float:
    """The MMSE expert's count per UE, as the repository's cost model
    (``repro.phy.estimators.estimator_flops``) states it: a four-product
    complex matmul, 8 real flops per complex multiply-add."""
    _, n_p, n_sc = shapes(config)
    return 8.0 * config["n_ant"] * len(config["dmrs_symbols"]) * n_p * n_sc
