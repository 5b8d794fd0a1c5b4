"""Work of the AI expert (residual CNN channel estimator) from shapes.

Per UE, receive port and layer (one estimate each, over the
configuration's pilot comb), 2 flops per multiply-add of the 3x3 ('same')
convolutions: a stem from 2 to C channels and R residual blocks of two
C -> C convolutions on the pilot grid (Np x S), a C -> 2C up-projection on
the pilot grid, a sub-pixel shuffle to C channels on the full grid
(Nsc x S), and a head from C to 2 channels there.

``repro.phy.ai_estimator.AiEstimatorConfig.flops`` counts the head from 2C
input channels; the head has C (the shuffle halves the channels), so that
count is higher by ``head_overcount`` (about 2% at C = 32).
"""


def _dims(config: dict):
    kh, kw = config["ai_kernel_hw"]
    n_sc = 12 * config["n_prb"]
    s = len(config["dmrs_symbols"])
    return kh * kw, config["ai_channels"], (n_sc // 2) * s, n_sc * s


def _ports(config: dict) -> int:
    """Channel estimates per UE: receive ports x layers."""
    return config["n_ant"] * config.get("n_layers", 1)


def flops_per_ue(config: dict) -> float:
    k, c, hw_in, hw_out = _dims(config)
    per_port = (
        2 * k * 2 * c * hw_in
        + config["ai_res_blocks"] * 2 * (2 * k * c * c * hw_in)
        + 2 * k * c * (2 * c) * hw_in
        + 2 * k * c * 2 * hw_out
    )
    return float(_ports(config) * per_port)


def head_overcount(config: dict) -> float:
    k, c, _, hw_out = _dims(config)
    return float(_ports(config) * 2 * k * c * 2 * hw_out)
