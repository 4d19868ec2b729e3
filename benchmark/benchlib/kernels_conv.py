"""What a decoder whose sequence mixer is a double-gated SHORT CONVOLUTION in
some layers and grouped softmax attention in the others has to compute and
move, from shapes alone. For the shares that
``metrics/gated_conv_roofline.py``, ``metrics/short_conv_mxu_share.py`` and
``metrics/narrow_head_scores_roofline.py`` report.

The configuration's own keys are read: ``layer_types_run`` (the kinds of the
layers this chip runs, ``conv`` or ``full_attention``, in order),
``hidden_size`` (D), ``conv_L_cache`` (the taps K), ``num_attention_heads``,
``num_key_value_heads``, ``head_dim`` and ``seq_len``. Counted from below,
USEFUL work only (one forward pass and the backward at twice a forward one,
nothing that the backward pass computes again), so that no share can pass
100 %:

- (a) the gated convolution of a ``conv`` layer, ``C * conv(B * z)``: B, C
  and z read and the result written once a pass, float32, 16 bytes a
  channel and token; the taps' ``2 K`` operations a channel and token (the
  two gates' products are left out). Elementwise: the bytes bound it on any
  chip whose table is in ``benchlib/peaks.py``;
- (b) the operator's two products, ``W_in`` [D, 3 D] and ``W_out`` [D, D]:
  ``2 x tokens x D x 4 D`` a ``conv`` layer;
- (c) the scores of a ``full_attention`` layer: query i reads the keys ``j
  <= i``, ``T (T + 1) / 2`` pairs a head (the TRIANGLE), ``q . k`` (2 d) and
  ``p v`` (2 d) operations a pair and query head; bytes: q read and the
  output written once a pass (every query head), k and v read once a pass
  (every key-value head), float32; the scores never leave the chip's fast
  memory in the count: ``benchlib/kernels_mixed_gqa.py``'s count of a full
  layer, handed the ``num_attention_heads_per_layer`` this configuration
  does not have.

Nothing of the program is imported here: its sub-scope names reach the
readers through ``benchlib/kernels_lm.py``.
"""

from __future__ import annotations

from typing import Optional

from benchlib import kernels_lm, kernels_mixed_gqa, peaks
# one forward pass and a backward pass at twice a forward one; float32
from benchlib.kernels_swa import FLOAT, PASSES

CONV, FULL = "conv", "full_attention"


def layers_run(config, kind: str) -> int:
    """How many of the layers this chip runs are of ``kind``."""
    return sum(k == kind for k in config["layer_types_run"])


def _tokens(config, sequences: int) -> int:
    return int(sequences) * int(config["seq_len"])


def gated_conv_bytes_a_step(config, sequences: int) -> float:
    """(a): B, C, z read and the result written, once a pass, every
    ``conv`` layer."""
    return float(layers_run(config, CONV) * _tokens(config, sequences)
                 * int(config["hidden_size"]) * 4 * FLOAT * PASSES)


def gated_conv_flops_a_step(config, sequences: int) -> float:
    """(a): the taps' multiply-adds, every ``conv`` layer."""
    return float(layers_run(config, CONV) * _tokens(config, sequences)
                 * int(config["hidden_size"]) * 2
                 * int(config["conv_L_cache"]) * PASSES)


def gated_conv_roofline_seconds(config, sequences: int, device_kind: str):
    """The least time a step's gated convolutions could take on this chip,
    and which of the two bounds it."""
    compute = gated_conv_flops_a_step(config, sequences) / peaks.peak(
        device_kind, "flops_bf16")
    memory = gated_conv_bytes_a_step(config, sequences) / peaks.peak(
        device_kind, "hbm_bytes_per_s")
    return max(compute, memory), ("compute" if compute >= memory
                                  else "memory")


def products_flops_a_step(config, sequences: int) -> float:
    """(b): ``W_in`` and ``W_out`` of every ``conv`` layer, forward and
    backward."""
    d = int(config["hidden_size"])
    one = 2 * _tokens(config, sequences) * d * (3 * d + d)
    return float(layers_run(config, CONV) * one * PASSES)


def _as_mixed(config):
    """The layers run, as ``benchlib/kernels_mixed_gqa.py`` reads a
    configuration whose layers differ in kind: every layer at the one head
    count this configuration has."""
    run = list(config["layer_types_run"])
    return dict(config, num_hidden_layers=len(run), layer_types=run,
                num_attention_heads_per_layer=[
                    int(config["num_attention_heads"])] * len(run))


def scores_flops_a_step(config, sequences: int) -> float:
    """(c): scores and weighted sums of every ``full_attention`` layer
    over ``sequences`` sequences of ``seq_len``, forward and backward."""
    return kernels_mixed_gqa.scores_flops_a_step(_as_mixed(config), FULL,
                                                 sequences)


def scores_bytes_a_step(config, sequences: int) -> float:
    """(c): q read, the output written, k and v read, once a pass, every
    ``full_attention`` layer."""
    return kernels_mixed_gqa.scores_bytes_a_step(_as_mixed(config), FULL,
                                                 sequences)


def scores_roofline_seconds(config, sequences: int, device_kind: str):
    """The least time a step's scores could take on this chip, and which
    of the two bounds it."""
    return kernels_mixed_gqa.scores_roofline_seconds(
        _as_mixed(config), FULL, sequences, device_kind)


def roofline_share(ctx, seconds_of, sub: str) -> Optional[float]:
    """``seconds_of``'s least time (one of the two ``*_roofline_seconds``)
    over the device time a step under the sub-scope ``sub``, in %; None
    where the trace holds nothing under it or the configuration does not
    say which layers run."""
    seconds = kernels_lm.sub_seconds(ctx, (sub,))
    if not seconds or "layer_types_run" not in ctx.config:
        return None
    least, _ = seconds_of(ctx.config, ctx.global_batch, ctx.device_kind)
    return 100.0 * least / (seconds / ctx.trace.steps)
