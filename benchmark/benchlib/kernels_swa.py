"""What the scores of a sliding-window attention layer have to compute and
move, from shapes alone: the floating-point operations and the bytes of the
BAND, for the share of the chip's roofline that
``metrics/window_attention_roofline.py`` reports.

Query i of a sequence of T tokens reads the keys ``i - W < j <= i``: a
sequence holds ``W (W + 1) / 2 + (T - W) W`` query-key pairs a head (``T (T
+ 1) / 2`` where ``W >= T``). Counted from below and from the band, not
from what implements it, so that the share reads the same work whatever
does (blocks of queries in plain XLA, a kernel) and cannot pass 100 %:

- operations: ``q . k`` (2 d) and ``p v`` (2 d) a pair and query head. The
  pairs a block of queries computes outside the band and masks are in the
  measured time and not in the count, and so is everything computed again
  in the backward pass;
- bytes: q read and the output written once a pass (every query head), k
  and v read once a pass (every key-value head), float32; the scores never
  leave the chip's fast memory in the count;
- passes: one forward and the backward at twice a forward.

Roofline time = max(operations / matrix peak, bytes / memory bandwidth).
Nothing of the program is imported here.
"""

from __future__ import annotations

from benchlib import peaks

# one forward pass and a backward pass at twice a forward one
PASSES = 3
FLOAT = 4


def band_pairs(tokens: int, window: int) -> int:
    """Query-key pairs a head of one sequence: ``sum_i min(i + 1, W)``."""
    w = min(int(window), int(tokens))
    return w * (w + 1) // 2 + (int(tokens) - w) * w


def window_layers(config) -> int:
    layers = int(config["num_hidden_layers"])
    return sum(1 for on in config["sliding_window_layout"][:layers] if on)


def window_scores_flops_a_step(config, sequences: int) -> float:
    """The scores and weighted sums of every windowed layer over
    ``sequences`` sequences of ``seq_len``, forward and backward."""
    pairs = band_pairs(config["seq_len"], config["sliding_window_size"])
    a_pair = 4 * int(config["head_dim"]) * int(config["num_attention_heads"])
    return float(window_layers(config) * sequences * pairs * a_pair * PASSES)


def window_scores_bytes_a_step(config, sequences: int) -> float:
    """q read, the output written, k and v read, once a pass, every windowed
    layer."""
    a_token = 2 * int(config["head_dim"]) * (
        int(config["num_attention_heads"])
        + int(config["num_key_value_heads"]))
    tokens = sequences * int(config["seq_len"])
    return float(window_layers(config) * tokens * a_token * FLOAT * PASSES)


def window_scores_roofline_seconds(config, sequences: int, device_kind: str):
    """The least time a step's banded scores could take on this chip, and
    which of the two bounds it."""
    compute = window_scores_flops_a_step(config, sequences) / peaks.peak(
        device_kind, "flops_bf16")
    memory = window_scores_bytes_a_step(config, sequences) / peaks.peak(
        device_kind, "hbm_bytes_per_s")
    return max(compute, memory), ("compute" if compute >= memory
                                  else "memory")
