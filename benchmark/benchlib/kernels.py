"""What a kernel's call has to do, from its shapes: operations and bytes.

``oktopk_fused_select`` (``oktopk_tpu/ops/fused_select.py``) sweeps a
worker's gradient and residual once and writes their sum; everything else
it produces (staging rows, counts) is of the order of the selected count,
2 % of n here, and is left out, which makes the share of the roofline a
little low, never high. Its arithmetic is a few
operations an element against 12 bytes an element, so memory bounds it.
"""


def fused_select_bytes(n: int) -> int:
    """Reads grad and residual, writes acc: three float32 vectors of n."""
    return 12 * int(n)
