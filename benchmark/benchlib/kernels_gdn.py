"""What the gated delta rule of a linear-attention layer has to compute and
move, from shapes alone: the floating-point operations and the bytes of the
TOKEN recurrence, for the share of the chip's roofline that
``metrics/delta_rule_roofline.py`` reports.

A value head's state S [d_k, d_v], token by token::

    S <- exp(g_t) S ;  S <- S + k_t (beta_t (v_t - S^T k_t))^T ;  o_t = S^T q_t

Counted from below and from the recurrence, not from what implements it, so
that the share reads the same work whatever does (a chunked scan in plain
XLA, a kernel) and cannot pass 100 %:

- operations: the decay (d_k d_v), ``S^T k`` (2 d_k d_v), the rank-one
  update (2 d_k d_v) and ``S^T q`` (2 d_k d_v): 7 d_k d_v a token and value
  head. The chunked form's own work (the chunk-local triangular system, the
  products inside a chunk) is in the measured time and not in the count,
  and so is everything computed again in the backward pass;
- bytes: q, k (a key head's, read once), v, g, beta read and o written once
  a pass, float32; the state never leaves the chip's fast memory in the
  count;
- passes: one forward and the backward at twice a forward.

Roofline time = max(operations / matrix peak, bytes / memory bandwidth).
Nothing of the program is imported here.
"""

from __future__ import annotations

from benchlib import peaks

# one forward pass and a backward pass at twice a forward one
PASSES = 3
FLOAT = 4


def _linear_layers(config) -> int:
    every = int(config["full_attention_interval"])
    return sum(1 for layer in range(int(config["num_hidden_layers"]))
               if (layer + 1) % every)


def _heads(config):
    return (int(config["linear_num_key_heads"]),
            int(config["linear_num_value_heads"]),
            int(config["linear_key_head_dim"]),
            int(config["linear_value_head_dim"]))


def delta_rule_flops_a_step(config, tokens: int) -> float:
    """``tokens`` tokens through the recurrence of every linear-attention
    layer, forward and backward."""
    _, hv, dk, dv = _heads(config)
    return float(_linear_layers(config) * tokens * hv * 7 * dk * dv * PASSES)


def delta_rule_bytes_a_step(config, tokens: int) -> float:
    """q, k, v, g, beta read and o written once a pass, every
    linear-attention layer."""
    hk, hv, dk, dv = _heads(config)
    a_token = 2 * hk * dk + 2 * hv * dv + 2 * hv
    return float(_linear_layers(config) * tokens * a_token * FLOAT * PASSES)


def delta_rule_roofline_seconds(config, tokens: int, device_kind: str):
    """The least time a step's recurrence could take on this chip, and
    which of the two bounds it."""
    compute = delta_rule_flops_a_step(config, tokens) / peaks.peak(
        device_kind, "flops_bf16")
    memory = delta_rule_bytes_a_step(config, tokens) / peaks.peak(
        device_kind, "hbm_bytes_per_s")
    return max(compute, memory), ("compute" if compute >= memory
                                  else "memory")
