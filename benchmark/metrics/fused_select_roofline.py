"""Kernel layer: the least time the chip could take for one
``oktopk_fused_select`` call over the time it took. The kernel is bound by
memory traffic (``benchlib/kernels.py``), so the least time is its bytes
over the chip's published memory bandwidth."""
from benchlib import kernels, peaks

KERNEL = "oktopk_fused_select"


def read(ctx):
    hit = lambda o: o.mentions(KERNEL)
    calls = ctx.trace.count(hit)
    seconds = ctx.trace.seconds(hit)
    if not calls or seconds <= 0:
        return None
    least = (kernels.fused_select_bytes(ctx.n)
             / peaks.peak(ctx.device_kind, "hbm_bytes_per_s"))
    return 100.0 * least / (seconds / calls)
