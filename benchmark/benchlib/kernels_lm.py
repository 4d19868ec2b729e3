"""What a decoder language model's layers have to compute, from their
shapes and from what the program counted: floating-point operations of the
attention block and of the routed experts, for the shares of the chip's
matrix peak that ``metrics/attention_mxu_share.py`` and
``metrics/experts_mxu_share.py`` report. And the reading of the program's
``fwd_bwd`` sub-scopes from a device operation's scope path.

Counted from below, so that no share can read over 100 %:

- attention's scores over the causal half only (T (T + 1) / 2 pairs a head
  and sequence), though the program computes whole blocks of 512 queries;
- the experts' products over the rows the program COUNTED at its held
  experts (``expert_rows``), never tokens x experts-a-token and never the
  rows of padding a buffer adds; the router's own product is left out;
- USEFUL work only: one forward pass and the backward pass at twice a
  forward one (a product's two gradients). What the program computes again
  in the backward pass is in the measured time and not in the operations,
  so nothing typed into a configuration file says how often it recomputes,
  and a recomputation taken out raises the share.

Nothing of the program is imported here: its sub-scope names come from its
own snapshot, through ``benchlib/progspans.py``.
"""

from __future__ import annotations

import re
from typing import Optional, Sequence

from benchlib import peaks, progspans

PHASE = "fwd_bwd"
_PARENS = re.compile(r"[()]")


def sub_of(path: str, subs: Sequence[str]) -> Optional[str]:
    """``.../transpose(jvp(anat/fwd_bwd/experts))/dot_general`` ->
    ``experts``: the innermost ``anat/fwd_bwd/<sub>`` of a scope path. JAX
    wraps the scopes of differentiated code in ``jvp(...)`` and
    ``transpose(...)``, so a part may carry a bracket."""
    parts = [p for p in _PARENS.sub("/", path).split("/") if p]
    for i in range(len(parts) - 1, 1, -1):
        if (parts[i] in subs and parts[i - 1] == PHASE
                and parts[i - 2] == "anat"):
            return parts[i]
    return None


def subs_of(ctx) -> Optional[Sequence[str]]:
    """The program's own names of its ``fwd_bwd`` sub-scopes; None for a
    program that has none (the parent of the PR that added them)."""
    return progspans.program_snapshot(ctx).get("sub_scopes", {}).get(PHASE)


# XLA:TPU compiles ``lax.ragged_dot`` (the routed experts' grouped
# products) to kernels of its own and names them so, in the instruction's
# name and in its ``op_name``: the scope path of the program is gone from
# them, so they are found by name. Only the routed experts call it.
RAGGED_DOT = "ragged-dot"


def is_ragged_dot(op) -> bool:
    return op.mentions(RAGGED_DOT)


def sub_seconds(ctx, wanted: Sequence[str],
                kernels: Sequence[str] = ()) -> Optional[float]:
    """Device seconds of the traced window under the sub-scopes ``wanted``,
    kernels included, and of the operations that mention one of
    ``kernels`` (which carry no scope); None where the program names none
    of the sub-scopes or the trace holds no operation of theirs."""
    subs = subs_of(ctx)
    if ctx.trace is None or not subs or not set(wanted) <= set(subs):
        return None
    s = ctx.trace.seconds(
        lambda o: (o.phase == PHASE and sub_of(o.path, subs) in wanted)
        or any(o.mentions(k) for k in kernels))
    return s if s > 0 else None


def sub_ms(ctx, wanted: Sequence[str],
           kernels: Sequence[str] = ()) -> Optional[float]:
    s = sub_seconds(ctx, wanted, kernels)
    return None if s is None else 1e3 * s / ctx.trace.steps


def counter_mean(ctx, name: str) -> Optional[float]:
    """A counter's mean over the traced window's steps."""
    v = progspans.view(ctx)
    col = v and v.column(name)
    return sum(col) / len(col) if col else None


# ---- operations ------------------------------------------------------------

# one forward pass, and a backward pass that makes two products (a
# product's two gradients) for each product of the forward one
PASSES = 3


def expert_flops_a_step(config, rows: float) -> float:
    """``rows`` token-expert pairs through one SwiGLU expert: three products
    of hidden x moe_intermediate, 2 operations a multiply-add."""
    one = 3 * 2 * int(config["hidden_size"]) * int(
        config["moe_intermediate_size"])
    return rows * one * PASSES


def attention_flops_a_step(config, sequences: int) -> float:
    """The attention block of every layer over ``sequences`` sequences of
    ``seq_len``: the four projections a token, and scores and weighted sum
    over the causal half."""
    d, h = int(config["hidden_size"]), int(config["num_attention_heads"])
    nope, rope, v = (int(config[k]) for k in (
        "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim"))
    rank, t = int(config["kv_lora_rank"]), int(config["seq_len"])
    proj = 2 * (d * h * (nope + rope) + d * (rank + rope)
                + rank * h * (nope + v) + h * v * d)
    pairs = t * (t + 1) // 2
    scores = 2 * h * pairs * (nope + rope + v)
    layers = int(config["num_hidden_layers"])
    return layers * sequences * (t * proj + scores) * PASSES


def mxu_share(ctx, flops: Optional[float], seconds: Optional[float]):
    """``flops`` a step over the chip's published matrix peak times the
    measured seconds a step, in %."""
    if not flops or not seconds:
        return None
    peak = peaks.peak(ctx.device_kind, "flops_bf16")
    return 100.0 * flops / (peak * seconds / ctx.trace.steps)
