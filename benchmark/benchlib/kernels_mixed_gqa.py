"""What the scores of a decoder whose layers differ in KIND and in HEAD COUNT
have to compute and move, from shapes alone: query-key pairs, floating-point
operations and bytes by layer, for the shares of the chip's roofline that
``metrics/full_scores_roofline.py`` and ``metrics/sliding_scores_roofline.py``
report.

The configuration's own published keys are read: ``layer_types`` (layer l is
``full_attention`` or ``sliding_attention``), ``num_attention_heads_per_layer``
(query heads of layer l), ``sliding_window``, ``head_dim``,
``num_key_value_heads``, ``num_hidden_layers`` (how many entries of the lists
are run) and ``seq_len``.

Query i of a sequence of T tokens reads, in a full layer, the keys ``j <= i``:
``T (T + 1) / 2`` pairs a head (the TRIANGLE); in a sliding layer the keys
``i - W < j <= i``: ``W (W + 1) / 2 + (T - W) W`` pairs a head (the BAND; the
triangle where ``W >= T``). Counted from below and from the triangle and the
band, not from what implements them (blocks of queries in plain XLA, the
tiles a kernel visits), so that a share reads the same work whatever does it
and cannot pass 100 %:

- operations: ``q . k`` (2 d) and ``p v`` (2 d) a pair and query head. The
  pairs a tile computes outside the mask are in the measured time and not in
  the count, and so is everything computed again in the backward pass;
- bytes: q read and the output written once a pass (every query head of the
  layer), k and v read once a pass (every key-value head), float32; the
  scores never leave the chip's fast memory in the count;
- passes: one forward and the backward at twice a forward.

Roofline time = max(operations / matrix peak, bytes / memory bandwidth).
Nothing of the program is imported here: its sub-scope names reach
:func:`roofline_share` through ``benchlib/kernels_lm.py``.
"""

from __future__ import annotations

from typing import List, Optional, Tuple

from benchlib import kernels_lm, peaks
# one forward pass and a backward pass at twice a forward one; float32; the
# pairs of a band: as one window's counts have them
from benchlib.kernels_swa import FLOAT, PASSES, band_pairs

FULL, SLIDING = "full_attention", "sliding_attention"


def triangle_pairs(tokens: int) -> int:
    """Query-key pairs a head of one sequence, causal: ``sum_i (i + 1)``."""
    return int(tokens) * (int(tokens) + 1) // 2


def layers_of(config, kind: str) -> List[Tuple[int, int]]:
    """(layer, its query heads) of every layer of ``kind`` that is run."""
    n = int(config["num_hidden_layers"])
    return [(i, int(config["num_attention_heads_per_layer"][i]))
            for i, k in enumerate(config["layer_types"][:n]) if k == kind]


def pairs_a_head(config, kind: str) -> int:
    t = int(config["seq_len"])
    return (triangle_pairs(t) if kind == FULL
            else band_pairs(t, config["sliding_window"]))


def scores_flops_a_step(config, kind: str, sequences: int) -> float:
    """The scores and weighted sums of every layer of ``kind`` over
    ``sequences`` sequences of ``seq_len``, forward and backward."""
    a_pair_head = 4 * int(config["head_dim"])
    heads = sum(h for _, h in layers_of(config, kind))
    return float(sequences * pairs_a_head(config, kind) * heads
                 * a_pair_head * PASSES)


def scores_bytes_a_step(config, kind: str, sequences: int) -> float:
    """q read, the output written, k and v read, once a pass, every layer
    of ``kind``."""
    kv = int(config["num_key_value_heads"])
    heads = sum(h + kv for _, h in layers_of(config, kind))
    tokens = sequences * int(config["seq_len"])
    return float(tokens * 2 * int(config["head_dim"]) * heads * FLOAT
                 * PASSES)


def scores_roofline_seconds(config, kind: str, sequences: int,
                            device_kind: str):
    """The least time a step's scores of the layers of ``kind`` could take
    on this chip, and which of the two bounds it."""
    compute = scores_flops_a_step(config, kind, sequences) / peaks.peak(
        device_kind, "flops_bf16")
    memory = scores_bytes_a_step(config, kind, sequences) / peaks.peak(
        device_kind, "hbm_bytes_per_s")
    return max(compute, memory), ("compute" if compute >= memory
                                  else "memory")


def roofline_share(ctx, kind: str, sub: str) -> Optional[float]:
    """That least time over the device time a step under the sub-scope
    ``sub``, in %; None where the trace holds nothing under it."""
    seconds = kernels_lm.sub_seconds(ctx, (sub,))
    if not seconds:
        return None
    least, _ = scores_roofline_seconds(ctx.config, kind, ctx.global_batch,
                                       ctx.device_kind)
    return 100.0 * least / (seconds / ctx.trace.steps)
