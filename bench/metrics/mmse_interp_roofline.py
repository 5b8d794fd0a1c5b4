"""Share of its roofline that the ``mmse_interp`` kernel reaches.

Least time of one call at the chip's peaks, the larger of its flops over
the bf16 peak and its bytes over the HBM bandwidth (``bench/work/
mmse_interp.py``), over the kernel's device time per call in the trace.
Also prints which of the two bounds binds."""

import sys

KERNEL = "mmse_interp"


def read(run):
    tr = run.trace
    if tr is None:
        return None
    match = tr.kernel(KERNEL)
    calls = tr.op_count(match)
    if calls == 0:
        return None
    per_call = tr.op_seconds(match) / calls
    work = run.work(KERNEL)
    shape = work.shapes(run.config)
    t_flops = work.kernel_flops(*shape) / run.peaks["bf16_flops_per_s"]
    t_bytes = work.kernel_bytes(*shape) / run.peaks["hbm_bytes_per_s"]
    bound = "memory" if t_bytes >= t_flops else "compute"
    print(f"mmse_interp: {calls} calls, {per_call * 1e6:.3f} us per call, "
          f"least time {max(t_flops, t_bytes) * 1e6:.3f} us ({bound}-bound; "
          f"flops {t_flops * 1e6:.3f} us, bytes {t_bytes * 1e6:.3f} us)",
          file=sys.stderr)
    return 100.0 * max(t_flops, t_bytes) / per_call
