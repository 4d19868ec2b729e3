"""The routed-expert layer of the decoder models (:class:`MoE`), told which
experts this chip holds. Five models build on it, each with its own
settings: ``models/deepseek_v2.py`` (softmax scores, two ungated shared
experts), ``models/qwen3_next.py`` (``shared_gate``),
``models/smallthinker.py`` (``router_input``, ``hidden_act="relu"``, no
shared expert), ``models/laguna.py`` (``scoring="sigmoid"``) and
``models/lfm2.py`` (sigmoid scores under a selection bias,
``expert_bias``, and the published block's ``norm_eps``). No model file is
imported here.

**Routing**: ``s = softmax(h W_r)`` over ALL ``n_routed_experts`` in float32
at ``highest`` precision (``scoring`` ``"sigmoid"``: each expert's own
sigmoid), greedy top-k, weights unrenormalised unless ``norm_topk_prob``
(then over the k, held or not, their sum plus ``norm_eps``); where
``expert_bias``, the k are the largest of ``s + b`` and their weights
still the unbiased ``s`` (``b`` a ``bias`` leaf that only chooses: no
gradient reaches it); the shared experts sit behind a sigmoid gate where
``shared_gate``. ``held_experts`` says which experts this chip
holds (expert parallelism: the others live on other chips); the layer
computes ``sum_{e in topk, e held} s_e E_e(h)`` plus the shared experts,
and what the absent experts would add is left out: no code stands in for
the other chips or their exchange.

**The held experts' work** (:func:`routed_experts`): no token routed to a
held expert is dropped, at any imbalance: the token-expert pairs of the
held experts are sorted by expert into ONE buffer and go through grouped
products (``lax.ragged_dot``), so the work does not follow the busiest
expert. The buffer holds ``CAPACITY_FACTOR`` times their mean number and
its empty rows are computed as zeros, so every step that fits does the same
work; a step with more pairs than that computes every expert over all rows,
masked, instead (``lax.cond`` on the number of pairs). Each branch
recomputes itself in the backward pass (``jax.checkpoint``), so the routed
experts' products run twice (nothing in a recomputed decoder layer's
backward pass needs their output, so the layer's own recomputation of them
is dead code).

Parameter leaves are ``kernel`` (the router's, the shared experts'),
``bias`` (the router's selection bias, where it has one) and ``experts``
(a stack of kernels, expert axis first). The scopes ``router``,
``experts`` and ``shared`` are what readers of a trace look for
(``obs/anatomy.SUB_SCOPES["fwd_bwd"]``).
"""

from __future__ import annotations

import math
from functools import lru_cache, partial
from typing import Any, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from oktopk_tpu.models.layers import ACTIVATIONS, HIGHEST, SwiGLU, swiglu
from oktopk_tpu.obs.anatomy import phase_scope


def _grouped_branch(rows: int, x, weights, routed, counts,
                    w_gate, w_up, w_down, act=jax.nn.silu):
    """The token-expert pairs of the held experts, sorted by expert into
    one buffer of ``rows`` rows (enough for all of them: the caller
    checks), through grouped products (``lax.ragged_dot``: expert h's
    weights for the rows of its group), weighted and added back to their
    tokens. The rows past the last pair are zeros in the LAST expert's
    group: XLA:TPU's kernel works on the rows that lie in a group and
    leaves the others unwritten, so with every row in a group each step
    does the work of ``rows`` rows, however many pairs its routing made,
    and every row of a product is written."""
    tokens = x.shape[0]
    # expert-major, so that a stable sort leaves the pairs grouped by
    # expert, in token order
    pair = jnp.argsort(~routed.T.reshape(-1), stable=True)[:rows]
    token = pair % tokens
    pairs = jnp.sum(counts)
    groups = counts.at[-1].add(rows - pairs)
    # a row past the last pair reads nothing and adds nothing (``keep``
    # cuts a cotangent too)
    valid = (lax.iota(jnp.int32, rows) < pairs)[:, None]
    keep = lambda a: jnp.where(valid, a, 0.0)
    xg = keep(x[token])
    g = lax.ragged_dot(xg, w_gate, groups)
    u = lax.ragged_dot(xg, w_up, groups)
    y = lax.ragged_dot(act(g) * u, w_down, groups)
    w = keep(weights.T.reshape(-1)[pair][:, None])
    return jnp.zeros_like(x).at[token].add(y * w.astype(y.dtype))


def _all_rows_branch(x, weights, routed, counts, w_gate, w_up, w_down,
                     act=jax.nn.silu):
    """More pairs than the buffer holds: each held expert over all rows,
    masked by the routing, one expert at a time."""
    del counts

    @jax.checkpoint
    def one(out, operand):
        wg, wu, wd, w = operand
        y = swiglu(x, wg, wu, wd, act)
        return out + y * w[:, None].astype(y.dtype), None

    w = jnp.where(routed, weights, 0.0).T
    out, _ = lax.scan(one, jnp.zeros_like(x), (w_gate, w_up, w_down, w))
    return out


# the grouped branch's buffer, in mean numbers of token-expert pairs. At
# seeded weights a layer's pairs lie 0.75-1.48 of their mean, batch by batch
# (DeepSeek-V2-Lite's widths, 16,384 tokens; 192 readings, s.d. 0.11), and a
# layer that passes the buffer runs all rows, at twice the grouped branch's
# time: the factor keeps that rare
CAPACITY_FACTOR = 1.5


def expert_capacity(tokens: int, held: int, k: int, experts: int) -> int:
    """The grouped branch's buffer in rows: ``CAPACITY_FACTOR`` times the
    mean number of token-expert pairs at the ``held`` of ``experts`` experts
    (``k`` a token), rounded up to 128, and never over the most there can
    be (a token meets a held expert at most once)."""
    mean, most = tokens * k * held / experts, tokens * min(k, held)
    return min(most, -(-math.ceil(CAPACITY_FACTOR * mean) // 128) * 128)


def routed_experts(x, weights, routed, w_gate, w_up, w_down,
                   capacity: int, k: int, act=jax.nn.silu):
    """``sum_h weights[:, h] * E_h(x)`` over the held experts h (``E_h(x) =
    (act(x W_gate) * x W_up) W_down``), for the tokens ``routed`` [T, H]
    gives each (``k`` experts a token, held or not). Returns it and the
    rows each held expert computed, i32[H]. The pairs go through the
    grouped branch where its ``capacity`` rows hold them all, else every
    expert runs over all rows, masked. Each branch is recomputed in the
    backward pass, so that neither's intermediates are kept (a ``cond``
    keeps those of both)."""
    counts = jnp.sum(routed, axis=0, dtype=jnp.int32)
    operands = (x, weights, routed, counts, w_gate, w_up, w_down)
    grouped = jax.checkpoint(partial(_grouped_branch, capacity, act=act))
    if capacity >= x.shape[0] * min(k, routed.shape[1]):    # holds any step
        return grouped(*operands), counts
    return lax.cond(jnp.sum(counts) <= capacity, grouped,
                    jax.checkpoint(_all_rows(act)), *operands), counts


@lru_cache(maxsize=None)
def _all_rows(act):
    """``_all_rows_branch`` under ``act``: one function an activation, so
    that every layer's ``cond`` traces the same branch (jax shares a trace
    by the function's identity, and the step program one body)."""
    return partial(_all_rows_branch, act=act)


# a router's scores from its logits [T, experts], by the published
# ``scoring_func``: over all experts, or each expert's own
SCORINGS = {"softmax": partial(jax.nn.softmax, axis=-1),
            "sigmoid": jax.nn.sigmoid}


def choose(scores, k: int, bias=None):
    """(a token's ``k`` chosen experts' scores, their ids) from ``scores``
    [T, experts]: the k largest, or, under a selection ``bias`` [experts],
    the k largest of ``scores + bias`` with their UNBIASED scores (the bias
    chooses and no more: no gradient reaches it)."""
    if bias is None:
        return lax.top_k(scores, k)
    _, top_i = lax.top_k(scores + lax.stop_gradient(bias), k)
    return jnp.take_along_axis(scores, top_i, axis=-1), top_i


class ExpertStack(nn.Module):
    """One projection of every held expert: ``experts`` [held, in, out]."""
    held: int
    features: int

    @nn.compact
    def __call__(self, fan_in: int):
        init = nn.initializers.variance_scaling(
            1.0, "fan_in", "normal", in_axis=-2, out_axis=-1, batch_axis=(0,))
        return self.param("experts", init,
                          (self.held, fan_in, self.features))


class MoE(nn.Module):
    n_routed_experts: int
    held_experts: Tuple[int, ...]
    num_experts_per_tok: int
    moe_intermediate_size: int
    n_shared_experts: int
    routed_scaling_factor: float
    norm_topk_prob: bool
    dtype: Any = jnp.float32
    # the shared experts' output times sigmoid(x w_g), a scalar a token
    shared_gate: bool = False
    # the routed experts' gate activation, a key of ACTIVATIONS
    hidden_act: str = "silu"
    # what turns the router's logits into scores, a key of SCORINGS
    scoring: str = "softmax"
    # a selection bias: the k are the largest of scores + ``bias`` [experts]
    # (a buffer in the published block, zeros at initialisation, moved by
    # a balancing rule outside the gradient), the weights the chosen's
    # unbiased scores
    expert_bias: bool = False
    # what the renormalised weights' sum is added to
    norm_eps: float = 1e-20

    @nn.compact
    def __call__(self, h, router_input=None):
        """``router_input`` (None: ``h``): what the router scores, where
        that is not what the experts read (``models/smallthinker.py``
        routes from the layer's normalised input, before attention)."""
        shape = h.shape
        x = h.reshape(-1, shape[-1])
        tokens, d = x.shape
        held, k = len(self.held_experts), self.num_experts_per_tok
        with phase_scope("fwd_bwd", sub="router"):
            w_r = self.param("kernel", nn.initializers.lecun_normal(),
                             (d, self.n_routed_experts))
            r = x if router_input is None else router_input.reshape(-1, d)
            scores = SCORINGS[self.scoring](
                jnp.dot(r.astype(jnp.float32), w_r, precision=HIGHEST))
            bias = (self.param("bias", nn.initializers.zeros,
                               (self.n_routed_experts,))
                    if self.expert_bias else None)
            top_w, top_i = choose(scores, k, bias)
            if self.norm_topk_prob:
                top_w = top_w / (jnp.sum(top_w, -1, keepdims=True)
                                 + self.norm_eps)
            top_w = top_w * self.routed_scaling_factor
            # [T, k, H] -> this chip's experts only
            hit = top_i[..., None] == jnp.asarray(self.held_experts,
                                                  jnp.int32)
            routed = jnp.any(hit, axis=1)
            weights = jnp.sum(jnp.where(hit, top_w[..., None], 0.0), axis=1)
        with phase_scope("fwd_bwd", sub="experts"):
            f = self.moe_intermediate_size
            w_gate = ExpertStack(held, f, name="routed_gate")(d)
            w_up = ExpertStack(held, f, name="routed_up")(d)
            w_down = ExpertStack(held, d, name="routed_down")(f)
            y, counts = routed_experts(
                x.astype(self.dtype), weights, routed,
                w_gate.astype(self.dtype), w_up.astype(self.dtype),
                w_down.astype(self.dtype),
                expert_capacity(tokens, held, k, self.n_routed_experts), k,
                ACTIVATIONS[self.hidden_act])
        if self.n_shared_experts:
            with phase_scope("fwd_bwd", sub="shared"):
                shared = SwiGLU(f * self.n_shared_experts, self.dtype,
                                name="shared_ffn")(x)
                if self.shared_gate:
                    shared = shared * jax.nn.sigmoid(nn.Dense(
                        1, use_bias=False, dtype=self.dtype,
                        name="shared_gate")(x))
                y = y + shared
        return y.reshape(shape), counts


def held_ids(held, experts: int) -> Tuple[int, ...]:
    """A configuration's ``held_experts`` as a tuple of distinct ids under
    ``experts``, at least one; None: all of them."""
    held = tuple(int(e) for e in (range(experts) if held is None else held))
    if not held or len(set(held)) != len(held) or not all(
            0 <= e < experts for e in held):
        raise ValueError(f"held_experts {held}: distinct ids under "
                         f"{experts}, at least one")
    return held

