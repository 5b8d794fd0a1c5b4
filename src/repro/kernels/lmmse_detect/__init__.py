from repro.kernels.lmmse_detect.lmmse_detect import lmmse_detect_planes
from repro.kernels.lmmse_detect.ops import lmmse_detect
from repro.kernels.lmmse_detect.ref import lmmse_detect_ref

__all__ = ["lmmse_detect", "lmmse_detect_planes", "lmmse_detect_ref"]
