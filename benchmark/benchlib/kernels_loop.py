"""What a LOOPED decoder's step has to compute and move, from shapes alone:
one stack of ``num_hidden_layers`` layers run ``total_ut_steps`` times on
the same weights, an exit (the whole head) after every pass. For the shares
that ``metrics/loop_scores_roofline.py``, ``metrics/exit_head_mxu_share.py``
and ``metrics/mlp_mxu_share.py`` report.

The configuration's own published keys are read: ``num_hidden_layers`` (L),
``total_ut_steps`` (R), ``num_attention_heads``, ``num_key_value_heads``,
``head_dim``, ``hidden_size``, ``intermediate_size``, ``vocab_size`` and
``seq_len``. A step runs R x L layer APPLICATIONS and R exits, whatever the
parameter tree holds. Counted from below, USEFUL work only (one forward pass
and the backward at twice a forward one, nothing that the backward pass
computes again), so that no share can pass 100 %:

- (a) the scores of an application: query i reads the keys ``j <= i``, ``T
  (T + 1) / 2`` pairs a head (the TRIANGLE), ``q . k`` (2 d) and ``p v`` (2
  d) operations a pair and query head; bytes: q read and the output written
  once a pass (every query head), k and v read once a pass (every key-value
  head), float32; the scores never leave the chip's fast memory in the
  count. As ``benchlib/kernels_mixed_gqa.py`` counts a full layer, whose
  ``num_attention_heads_per_layer`` this configuration does not have;
- (b) an exit's head: one product of ``2 x tokens x hidden x vocab``;
- (c) an application's SwiGLU: three matrices of ``hidden x intermediate``,
  ``2 x tokens x hidden x intermediate`` each.

Nothing of the program is imported here: its sub-scope names reach the
readers through ``benchlib/kernels_lm.py``.
"""

from __future__ import annotations

from typing import Optional

from benchlib import kernels_lm, peaks
# query-key pairs a head of one causal sequence, ``T (T + 1) / 2``
from benchlib.kernels_mixed_gqa import triangle_pairs
# one forward pass and a backward pass at twice a forward one; float32
from benchlib.kernels_swa import FLOAT, PASSES


def applications(config) -> int:
    """Layer applications a step: every pass runs every layer."""
    return int(config["total_ut_steps"]) * int(config["num_hidden_layers"])


def scores_flops_a_step(config, sequences: int) -> float:
    """(a): scores and weighted sums of every application over
    ``sequences`` sequences of ``seq_len``, forward and backward."""
    a_pair = 4 * int(config["head_dim"]) * int(config["num_attention_heads"])
    return float(applications(config) * sequences
                 * triangle_pairs(config["seq_len"]) * a_pair * PASSES)


def scores_bytes_a_step(config, sequences: int) -> float:
    """(a): q read, the output written, k and v read, once a pass, every
    application."""
    heads = int(config["num_attention_heads"]) + int(
        config["num_key_value_heads"])
    tokens = sequences * int(config["seq_len"])
    return float(applications(config) * tokens * 2 * int(config["head_dim"])
                 * heads * FLOAT * PASSES)


def scores_roofline_seconds(config, sequences: int, device_kind: str):
    """The least time a step's scores could take on this chip, and which
    of the two bounds it."""
    compute = scores_flops_a_step(config, sequences) / peaks.peak(
        device_kind, "flops_bf16")
    memory = scores_bytes_a_step(config, sequences) / peaks.peak(
        device_kind, "hbm_bytes_per_s")
    return max(compute, memory), ("compute" if compute >= memory
                                  else "memory")


def head_flops_a_step(config, sequences: int) -> float:
    """(b): the head product of every exit, forward and backward."""
    tokens = sequences * int(config["seq_len"])
    one = 2 * tokens * int(config["hidden_size"]) * int(config["vocab_size"])
    return float(int(config["total_ut_steps"]) * one * PASSES)


def mlp_flops_a_step(config, sequences: int) -> float:
    """(c): the SwiGLU's three products of every application, forward and
    backward."""
    tokens = sequences * int(config["seq_len"])
    one = 3 * 2 * tokens * int(config["hidden_size"]) * int(
        config["intermediate_size"])
    return float(applications(config) * one * PASSES)


def scores_roofline_share(ctx, sub: str) -> Optional[float]:
    """(a)'s least time over the device time a step under the sub-scope
    ``sub``, in %; None where the trace holds nothing under it."""
    seconds = kernels_lm.sub_seconds(ctx, (sub,))
    if not seconds:
        return None
    least, _ = scores_roofline_seconds(ctx.config, ctx.global_batch,
                                       ctx.device_kind)
    return 100.0 * least / (seconds / ctx.trace.steps)
