"""LFM2 (models/lfm2.py) against its plain reference
(benchmark/reference/lfm2.py) at ``lfm2_tiny``, on seeded weights made by
the benchmark's own rules (benchlib/weights.py): loss, every gradient leaf
and three SGD steps; the short convolution alone against a token-by-token
loop, and its causality; the query/key norm before rotary at a narrow head;
the router's selection bias apart from its weights; the share cut of
eight-way expert parallelism; the tied head; each broken path the chip's
check has to catch; the leaves' names, the parameter count of the chip's
share, the sub-scopes, the call record, the benchmark's counts, and steps
through the ``Trainer``.
"""

import contextlib
import dataclasses
import json
import math
import os
import re
import sys

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))

from benchlib import discover, kernels_conv, kernels_lm, weights  # noqa: E402

from oktopk_tpu.config import TrainConfig  # noqa: E402
from oktopk_tpu.models import attention, layers, moe  # noqa: E402
from oktopk_tpu.models import create_model  # noqa: E402
from oktopk_tpu.models import lfm2  # noqa: E402
from oktopk_tpu.models.registry import TOKEN_LMS  # noqa: E402
from oktopk_tpu.obs import anatomy  # noqa: E402
from oktopk_tpu.train.trainer import Trainer  # noqa: E402
from oktopk_tpu.utils import profiling  # noqa: E402

REF = discover.load_module(
    os.path.join(ROOT, "benchmark", "reference", "lfm2.py"))
CONFIG = os.path.join(ROOT, "benchmark", "configs", "lfm2_24b_a2b_ep8.json")

# float32 on the CPU: program and reference differ by the order of float32
# sums (3e-6 the worst gradient leaf read here, 1e-7 the loss); bfloat16
# compute reads 1e-2 at its best leaf and 3e-4 in the loss. About ten times
# the sound reading.
LOSS_TOL, GRAD_TOL = 2e-6, 3e-5
HELD = (1, 2, 5, 6)


def spec_of(cfg, held=None, block=24):
    """The reference's ``spec`` for a model configuration."""
    n = cfg.num_hidden_layers
    return dict(
        num_hidden_layers=n, num_dense_layers=cfg.num_dense_layers,
        layer_types=list(cfg.layer_types[:n]),
        num_attention_heads=cfg.num_attention_heads,
        num_key_value_heads=cfg.num_key_value_heads,
        rope_theta=cfg.rope_theta, norm_eps=cfg.norm_eps,
        conv_L_cache=cfg.conv_L_cache, num_experts=cfg.num_experts,
        num_experts_per_tok=cfg.num_experts_per_tok,
        norm_topk_prob=cfg.norm_topk_prob,
        routed_scaling_factor=cfg.routed_scaling_factor,
        held_experts=list(cfg.held_experts if held is None else held),
        attn_block=block, mlp_block=32, head_block=32)


def seeded(model, example, seed=7, bias_seed=None):
    """Benchmark-made weights; ``bias_seed``: the routers' selection biases
    drawn too (the benchmark's rule leaves a ``bias`` at zero), wide enough
    to change most tokens' choice."""
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), example(2), train=False))["params"]
    params = weights.make_params(shapes, seed)
    if bias_seed is not None:
        for i, p in enumerate(p for p in params.values() if "moe" in p):
            p["moe"]["bias"] = 0.1 * jax.random.normal(
                jax.random.PRNGKey(bias_seed + i), p["moe"]["bias"].shape)
    return params


def batch_of(seqs=4, t=64, vocab=512, seed=0):
    toks = np.random.default_rng(seed).integers(
        0, vocab, size=(seqs, t + 1)).astype(np.int32)
    return {"tokens": toks[:, :-1], "targets": toks[:, 1:]}


def program_loss(model, batch):
    def loss(p):
        logits, stats = model.apply({"params": p}, batch["tokens"])
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, batch["targets"]).mean(), stats["expert_rows"]
    return jax.jit(jax.value_and_grad(loss, has_aux=True))


def leaf_gaps(prog, ref):
    """A leaf's |program - reference| over the reference's norm; a leaf
    whose reference is all zeros (a selection bias's gradient) reads the
    program's own norm."""
    flat = jax.tree_util.tree_flatten_with_path(prog)[0]
    return {jax.tree_util.keystr(path): float(
        jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) or 1.0))
        for (path, a), b in zip(flat, jax.tree.leaves(ref))}


def partial_conv(p, spec):
    return lambda s: REF.short_conv(p["conv"], s, spec)


@pytest.fixture(scope="module")
def tiny():
    # layers 0-4 (conv and dense, then full, conv, conv, conv with experts),
    # 4 x 64 tokens, 2 of 8 experts a token, 4 held, NONZERO biases
    model, example = create_model("lfm2_tiny", held_experts=HELD)
    params = seeded(model, example, bias_seed=3)
    batch = batch_of()
    ref = jax.jit(jax.value_and_grad(
        lambda p: REF.loss(p, batch, spec_of(model.cfg))))
    return model, params, batch, ref, program_loss(model, batch)


class TestAgainstReference:
    def test_loss_and_every_gradient_leaf(self, tiny):
        model, params, batch, ref, step = tiny
        ref_loss, ref_grads = ref(params)
        (loss, _), grads = step(params)
        assert abs(loss - ref_loss) / abs(ref_loss) < LOSS_TOL
        gaps = leaf_gaps(grads, ref_grads)
        # embed, final norm, 5 x 2 norms, 4 conv mixers of 3, an attention
        # mixer of 6, a dense layer's 3 kernels, 4 x (router, bias, 3 stacks)
        assert len(gaps) == 2 + 5 * 2 + 4 * 3 + 6 + 3 + 4 * 5
        assert max(gaps.values()) < GRAD_TOL, gaps

    def test_three_sgd_steps(self, tiny):
        """Plain SGD at lr 0.1, each side by its own gradients from the
        same start: the losses and the parameters stay together."""
        model, params, batch, ref, step = tiny
        p, r = params, params
        for _ in range(3):
            (loss, _), g = step(p)
            ref_loss, ref_g = ref(r)
            assert abs(loss - ref_loss) / abs(ref_loss) < 5 * LOSS_TOL
            p = jax.tree.map(lambda a, b: a - 0.1 * b, p, g)
            r = jax.tree.map(lambda a, b: a - 0.1 * b, r, ref_g)
        moved = leaf_gaps(jax.tree.map(jnp.subtract, p, params),
                          jax.tree.map(jnp.subtract, r, params))
        assert max(moved.values()) < 10 * GRAD_TOL, moved
        assert float(loss) < float(step(params)[0][0])

    def test_bfloat16_compute_fails_the_tolerances(self, tiny):
        _, params, batch, ref, _ = tiny
        ref_loss, ref_grads = ref(params)
        model, _ = create_model("lfm2_tiny", held_experts=HELD,
                                dtype=jnp.bfloat16)
        (loss, _), grads = program_loss(model, batch)(params)
        gaps = leaf_gaps(grads, ref_grads)
        assert abs(loss - ref_loss) / abs(ref_loss) > LOSS_TOL
        live = [v for k, v in gaps.items() if "bias" not in k]
        assert min(live) > GRAD_TOL

    def test_counters_equal_the_reference_routing(self, tiny):
        """``expert_rows``: the reference's own routing of each expert
        layer's post-mixer state, counted at the held experts."""
        model, params, batch, _, step = tiny
        rows = step(params)[0][1]
        cfg, spec = model.cfg, spec_of(model.cfg)

        @jax.jit
        def reference_rows(params):
            x = params["embed"]["embedding"][batch["tokens"]]
            want = []
            for i in range(cfg.num_hidden_layers):
                p = params[f"layers_{i}"]
                if i >= cfg.num_dense_layers:
                    u = REF._norm(x, p["operator_norm"]["scale"],
                                  cfg.norm_eps)
                    mix = (partial_conv(p, spec) if "conv" in p
                           else (lambda s: REF.attention(p["attn"], s, spec)))
                    h = REF._norm(x + jax.vmap(mix)(u),
                                  p["ffn_norm"]["scale"], cfg.norm_eps)
                    w = REF.routing(h.reshape(-1, h.shape[-1]),
                                    p["moe"]["kernel"], p["moe"]["bias"],
                                    spec)
                    want.append(jnp.sum(w > 0, axis=0)[jnp.asarray(HELD)])
                x = jax.vmap(lambda s: REF.layer(p, s, spec, i))(x)
            return jnp.stack(want)
        want = reference_rows(params)
        assert np.array_equal(np.asarray(rows), np.asarray(want))
        # the dense layer counts nothing: four expert layers' rows
        assert rows.shape == (4, len(HELD)) and int(rows.min()) > 0


def conv_of(seed=3, t=20, d=16, taps=3):
    """One short-convolution operator at toy widths, its parameters and an
    input [2, t, d]."""
    op = lfm2.ShortConv(taps)
    u = jax.random.normal(jax.random.PRNGKey(seed), (2, t, d))
    shapes = jax.eval_shape(lambda: op.init(jax.random.PRNGKey(1), u))
    return op, weights.make_params(shapes, seed + 1), u


class TestTheShortConvolution:
    @pytest.mark.parametrize("taps", [1, 3, 4])
    def test_it_is_the_token_by_token_loop(self, taps):
        """``m_t = (C_t * sum_j w[j] (B z)_{t-(K-1)+j}) W_out``, a token
        and a tap at a time in numpy float64."""
        op, params, u = conv_of(taps=taps)
        got = np.asarray(op.apply(params, u))
        p = jax.tree.map(lambda a: np.asarray(a, np.float64),
                         params["params"])
        d = u.shape[-1]
        for s in range(u.shape[0]):
            bcz = np.asarray(u[s], np.float64) @ p["in_proj"]["kernel"]
            b, c, z = bcz[:, :d], bcz[:, d:2 * d], bcz[:, 2 * d:]
            for t in range(u.shape[1]):
                conv = np.zeros(d)
                for j in range(taps):
                    back = taps - 1 - j         # the last tap: this token
                    if t - back >= 0:
                        conv += (p["taps"]["kernel"][j] * b[t - back]
                                 * z[t - back])
                want = (c[t] * conv) @ p["out_proj"]["kernel"]
                np.testing.assert_allclose(got[s, t], want, rtol=2e-5,
                                           atol=2e-6)

    def test_the_reference_is_the_same_loop(self):
        op, params, u = conv_of()
        spec = {"conv_L_cache": 3}
        want = jax.vmap(lambda s: REF.short_conv(params["params"], s, spec))(
            u)
        np.testing.assert_allclose(op.apply(params, u), want, rtol=1e-5,
                                   atol=1e-6)

    @pytest.mark.parametrize("t", [0, 7, 19])
    def test_a_change_at_token_t_moves_no_output_before_t(self, t):
        """Causal, and short: outputs t .. t + K - 1 move, nothing before t
        and nothing from t + K on (the gates are of the token itself)."""
        op, params, u = conv_of()
        moved = op.apply(params, u.at[:, t].add(1.0)) - op.apply(params, u)
        moved = np.abs(np.asarray(moved)).max(axis=(0, 2))
        assert not moved[:t].any()
        assert moved[t:t + 3].all()
        assert not moved[t + 3:].any()

    def test_sequences_do_not_meet(self):
        """The left padding is each sequence's own: the first tokens of a
        batch's second sequence read nothing of the first's last."""
        op, params, u = conv_of()
        alone = op.apply(params, u[1:])
        np.testing.assert_array_equal(op.apply(params, u)[1:], alone)

    def test_the_call_is_recorded_by_its_shape(self):
        conv_of(t=20, d=16, taps=3)
        assert {"tokens": 20, "channels": 16, "taps": 3} in (
            profiling.snapshot()["short_conv"])


class TestTheNarrowHeadUnderItsNorm:
    def attention_of(self, seed=5):
        attn = lfm2.Attention(4, 2, 32, 10000.0, 1e-5, 16)
        u = jax.random.normal(jax.random.PRNGKey(seed), (2, 64, 128))
        shapes = jax.eval_shape(lambda: attn.init(jax.random.PRNGKey(1), u))
        return attn, weights.make_params(shapes, seed), u

    def test_it_is_the_reference_with_a_gain_that_is_not_one(self):
        attn, params, u = self.attention_of()
        p = params["params"]
        for name in ("q_layernorm", "k_layernorm"):
            p[name]["scale"] = 1.0 + 0.5 * jax.random.normal(
                jax.random.PRNGKey(len(name)), (32,))
        spec = dict(num_attention_heads=4, num_key_value_heads=2,
                    rope_theta=10000.0, norm_eps=1e-5, attn_block=24)
        want = jax.vmap(lambda s: REF.attention(p, s, spec))(u)
        np.testing.assert_allclose(attn.apply(params, u), want, rtol=2e-5,
                                   atol=2e-6)

    def test_a_queries_length_does_not_reach_the_scores(self):
        """The norm takes a head's length away before the product: W_q
        times 7 is the same attention (without the norm the scores would be
        7 times as sharp), and rotary, which comes after, keeps lengths."""
        attn, params, u = self.attention_of()
        p = params["params"]
        scaled = dict(p, q_proj={"kernel": 7.0 * p["q_proj"]["kernel"]})
        np.testing.assert_allclose(
            attn.apply({"params": scaled}, u), attn.apply(params, u),
            rtol=1e-4, atol=1e-5)

    def test_rotary_turns_all_of_a_heads_dims(self):
        cos, sin = attention.rotary_table(attention.Rope(1e6), 64, 48)
        assert cos.shape == sin.shape == (48, 32)
        x = jax.random.normal(jax.random.PRNGKey(2), (48, 3, 64))
        y = attention.rotate_half_partial(x, cos, sin)
        # every dim turns (the slowest by 1e-6 a position at this theta)
        assert float(jnp.min(jnp.abs(sin[1]))) > 0
        assert float(jnp.min(jnp.max(jnp.abs(y - x), axis=(0, 1)))) > 0
        np.testing.assert_allclose(y, REF._rotary(x, 1e6), rtol=1e-5,
                                   atol=1e-5)


def moe_params(d, f, e, seed=11, bias=0.0):
    stack = lambda s: {"experts": jax.ShapeDtypeStruct(s, jnp.float32)}
    tree = {"kernel": jax.ShapeDtypeStruct((d, e), jnp.float32),
            "bias": jax.ShapeDtypeStruct((e,), jnp.float32),
            "routed_gate": stack((e, d, f)), "routed_up": stack((e, d, f)),
            "routed_down": stack((e, f, d))}
    p = weights.make_params(tree, seed)
    if bias:
        p["bias"] = bias * jax.random.normal(jax.random.PRNGKey(seed), (e,))
    return p


def share_of(full, ids):
    ids = np.asarray(list(ids))
    return {k: ({"experts": v["experts"][ids]} if k.startswith("routed")
                else v) for k, v in full.items()}


class TestTheSelectionBias:
    D, F, E, K = 128, 64, 16, 4
    SPEC = {"num_experts_per_tok": 4, "routed_scaling_factor": 1.0,
            "norm_topk_prob": True}

    def layer(self, eps=lfm2.NORM_EPS):
        return moe.MoE(self.E, tuple(range(self.E)), self.K, self.F, 0, 1.0,
                       True, jnp.float32, scoring="sigmoid",
                       expert_bias=True, norm_eps=eps)

    def test_selection_follows_the_sum_and_weights_the_scores(self):
        full = moe_params(self.D, self.F, self.E, bias=0.5)
        h = jax.random.normal(jax.random.PRNGKey(4), (96, self.D))
        scores = jax.nn.sigmoid(jnp.dot(h, full["kernel"],
                                        precision=layers.HIGHEST))
        w = REF.routing(h, full["kernel"], full["bias"], self.SPEC)
        chosen = np.asarray(w > 0)
        assert np.array_equal(chosen.sum(axis=1), np.full(96, self.K))
        # chosen by score + bias ...
        summed = np.asarray(scores + full["bias"])
        for t in range(96):
            assert set(np.argsort(-summed[t])[:self.K]) == set(
                np.flatnonzero(chosen[t]))
        # ... which is another choice than the scores' own for most tokens
        plain = np.asarray(REF.routing(h, full["kernel"],
                                       jnp.zeros(self.E), self.SPEC) > 0)
        assert (plain != chosen).any(axis=1).mean() > 0.5
        # the weights are the chosen's UNBIASED scores over their sum + 1e-6
        top = jnp.where(w > 0, scores, 0.0)
        np.testing.assert_allclose(
            w, top / (jnp.sum(top, axis=1, keepdims=True) + 1e-6),
            rtol=1e-6)
        # and the module is the reference's layer
        y, rows = self.layer().apply({"params": full}, h)
        spec = dict(self.SPEC, held_experts=range(self.E))
        want = REF.experts(full, h, w, spec)
        assert float(jnp.max(jnp.abs(y - want))) < 1e-5 * float(
            jnp.max(jnp.abs(want)))
        assert np.array_equal(np.asarray(rows), chosen.sum(axis=0))

    def test_the_biass_gradient_is_exactly_zero(self):
        full = moe_params(self.D, self.F, self.E, bias=0.5)
        h = jax.random.normal(jax.random.PRNGKey(4), (96, self.D))
        loss = lambda p: jnp.sum(self.layer().apply({"params": p}, h)[0] ** 2)
        g = jax.grad(loss)(full)
        assert not np.asarray(g["bias"]).any()
        assert float(jnp.linalg.norm(g["kernel"])) > 0
        ref = jax.grad(lambda p: jnp.sum(REF.experts(
            p, h, REF.routing(h, p["kernel"], p["bias"], self.SPEC),
            dict(self.SPEC, held_experts=range(self.E))) ** 2))(full)
        assert not np.asarray(ref["bias"]).any()
        np.testing.assert_allclose(g["kernel"], ref["kernel"], rtol=1e-3,
                                   atol=1e-4)

    def test_a_zero_bias_is_the_plain_sigmoid_router_but_for_its_epsilon(
            self):
        """At ``bias`` zeros and the old epsilon the two fields change
        nothing: bit for bit ``models/laguna.py``'s router."""
        full = moe_params(self.D, self.F, self.E)
        h = jax.random.normal(jax.random.PRNGKey(5), (96, self.D))
        plain = moe.MoE(self.E, tuple(range(self.E)), self.K, self.F, 0, 1.0,
                        True, jnp.float32, scoring="sigmoid")
        assert not plain.expert_bias and plain.norm_eps == 1e-20
        without = {k: v for k, v in full.items() if k != "bias"}
        y, rows = plain.apply({"params": without}, h)
        z, rows_b = self.layer(eps=1e-20).apply({"params": full}, h)
        assert np.array_equal(y, z) and np.array_equal(rows, rows_b)

    def test_the_defaults_make_no_leaf(self):
        """Left at their defaults the two fields add nothing: no ``bias``
        leaf (the four older models' trees are as they were; their lowered
        programs are held by the parent digests of tests/test_attention.py,
        tests/test_deepseek_v2.py and tests/test_qwen3_next.py)."""
        h = jnp.zeros((8, self.D))
        default = moe.MoE(self.E, (1, 2), self.K, self.F, 0, 1.0, True,
                          jnp.float32)
        shapes = jax.eval_shape(lambda: default.init(
            jax.random.PRNGKey(0), h))["params"]
        assert set(shapes) == {"kernel", "routed_gate", "routed_up",
                               "routed_down"}


class TestShare:
    def test_8_shares_make_the_uncut_layer(self, tiny):
        """Expert parallelism's cut (guide, section 4) at the published 64
        experts, 4 a token: 8 chips hold 8 each; their routed parts, with
        the mixer and the residual (what every chip computes alike) counted
        once, add up to the reference's uncut layer. There is no shared
        expert. The dense layer has no routed part: it is the same on
        every chip."""
        model, params, batch, _, step = tiny
        cfg = dataclasses.replace(model.cfg, num_experts=64,
                                  num_experts_per_tok=4, held_experts=None)
        e, f, d = cfg.num_experts, cfg.moe_intermediate_size, cfg.hidden_size
        x = params["embed"]["embedding"][batch["tokens"]][:2]
        for index in (1, 2):                    # an attention and a conv layer
            p = dict(params[f"layers_{index}"])
            p["moe"] = moe_params(d, f, e, bias=0.3)
            spec = spec_of(cfg, held=range(e))
            uncut = jax.vmap(lambda s: REF.layer(p, s, spec, index))(x)
            # what every chip computes alike: x' = x + the mixer
            u = REF._norm(x, p["operator_norm"]["scale"], cfg.norm_eps)
            mix = (partial_conv(p, spec) if "conv" in p
                   else (lambda s: REF.attention(p["attn"], s, spec)))
            mid = x + jax.vmap(mix)(u)
            h = REF._norm(mid, p["ffn_norm"]["scale"], cfg.norm_eps)
            total, rows = mid, 0
            for chip in range(8):
                held = tuple(range(8 * chip, 8 * chip + 8))
                layer = moe.MoE(e, held, 4, f, 0, 1.0, True, jnp.float32,
                                scoring="sigmoid", expert_bias=True,
                                norm_eps=lfm2.NORM_EPS)
                y, counts = layer.apply(
                    {"params": share_of(p["moe"], held)}, h)
                total = total + y
                rows += int(counts.sum())
                if chip == 0:   # ... and the layer is x' + the module's part
                    out, _ = lfm2.DecoderLayer(dataclasses.replace(
                        cfg, held_experts=held), index).apply(
                        {"params": dict(p, moe=share_of(p["moe"], held))}, x)
                    np.testing.assert_allclose(out, mid + y, rtol=1e-5,
                                               atol=1e-5)
            assert rows == x.shape[0] * x.shape[1] * 4      # every pair, once
            assert float(jnp.max(jnp.abs(total - uncut))) < 2e-5 * float(
                jnp.max(jnp.abs(uncut)))
        # the dense layer: whatever is held
        p0, spec0 = params["layers_0"], spec_of(model.cfg)
        want = jax.vmap(lambda s: REF.layer(p0, s, spec0, 0))(x)
        for held in ((0, 1), (7,)):
            out, counts = lfm2.DecoderLayer(dataclasses.replace(
                model.cfg, held_experts=held), 0).apply({"params": p0}, x)
            np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-5)
            assert not counts.any()

    def test_capacity_of_the_cells_share(self):
        # 16,384 tokens, 4 of 64 a token, 8 held: 8,192 pairs on average
        assert moe.expert_capacity(16384, 8, 4, 64) == 12288


# ---- broken paths: what the chip's check has to catch -----------------------

@contextlib.contextmanager
def _swap(owner, name, new):
    old = getattr(owner, name)
    setattr(owner, name, new)
    try:
        yield
    finally:
        setattr(owner, name, old)


def _taps_reversed(b, c, z, w):
    return c * layers.causal_conv(b * z, w[::-1])


def _b_dropped(b, c, z, w):
    return c * layers.causal_conv(z, w)


def _c_dropped(b, c, z, w):
    return layers.causal_conv(b * z, w)


class _Passes(nn.Module):
    """In the place of the query/key norm: passes its input (the ``scale``
    leaf is still made, and gets no gradient)."""

    @nn.compact
    def __call__(self, x):
        self.param("scale", nn.initializers.ones, (x.shape[-1],))
        return x


def _qk_norm_left_out():
    real = lfm2.RMSNorm
    return _swap(lfm2, "RMSNorm", lambda eps, dtype, name=None: (
        _Passes(name=name) if name in ("q_layernorm", "k_layernorm")
        else real(eps, dtype, name=name)))


def _bias_in_the_weights():
    """The weights are ``top_k``'s own values, of ``scores + bias``: the
    selection bias leaks into the combine weights."""
    return _swap(moe, "choose", lambda scores, k, bias=None: jax.lax.top_k(
        scores if bias is None else scores + bias, k))


def _normaliser_left_out():
    real = lfm2.MoE
    return _swap(lfm2, "MoE", lambda *a, **kw: real(
        *a[:6], False, *a[7:], **kw))


def _rotary_left_out():
    return _swap(lfm2, "rotate_half_partial", lambda x, cos, sin: x)


def _head_untied():
    """The logits from a matrix that is not the embedding (a fixed draw of
    the benchmark's own rule for a kernel): what an untied head computes."""
    def attend(self, x):
        k = jax.random.normal(jax.random.PRNGKey(5), (
            x.shape[-1], self.num_embeddings)) / math.sqrt(x.shape[-1])
        return x @ k
    return _swap(nn.Embed, "attend", attend)


BROKEN = {
    "taps_reversed": lambda: _swap(lfm2, "gated_conv", _taps_reversed),
    "b_dropped": lambda: _swap(lfm2, "gated_conv", _b_dropped),
    "c_dropped": lambda: _swap(lfm2, "gated_conv", _c_dropped),
    "bias_in_the_weights": _bias_in_the_weights,
    "normaliser_left_out": _normaliser_left_out,
    "qk_norm_left_out": _qk_norm_left_out,
    "rotary_left_out": _rotary_left_out,
    "head_untied": _head_untied,
}


class TestBrokenPathsAreCaught:
    @pytest.mark.parametrize("variant", sorted(BROKEN))
    def test_a_broken_path_fails_the_tolerances(self, tiny, variant):
        _, params, batch, ref, _ = tiny
        ref_loss, ref_grads = ref(params)
        model, _ = create_model("lfm2_tiny", held_experts=HELD)
        with BROKEN[variant]():     # read when the model is traced
            (loss, _), grads = program_loss(model, batch)(params)
        gaps = leaf_gaps(grads, ref_grads)
        assert (abs(loss - ref_loss) / abs(ref_loss) > 10 * LOSS_TOL
                or max(gaps.values()) > 10 * GRAD_TOL), (variant, gaps)

    def test_the_patches_leave_the_module_as_it_was(self, tiny):
        model, params, batch, ref, _ = tiny
        (loss, _), _ = program_loss(model, batch)(params)
        assert abs(loss - ref(params)[0]) / abs(loss) < LOSS_TOL


def count(tree):
    return sum(math.prod(s.shape) for s in jax.tree.leaves(tree))


class TestRegistryAndScopes:
    def test_the_chips_share_of_the_published_model_is_469_million(self):
        with open(CONFIG) as f:
            config = json.load(f)
        model, example = create_model("lfm2_24b_a2b",
                                      **config["model_kwargs"])
        shapes = jax.eval_shape(lambda: model.init(
            jax.random.PRNGKey(0), example(2), train=False))["params"]
        assert count(shapes) == config["n_params"] == 469_285_248
        assert count(shapes["embed"]) == 16_777_216 and "lm_head" not in (
            shapes)
        assert count(shapes["embedding_norm"]) == 2048
        for i in (0, 2, 3, 4):
            assert count(shapes[f"layers_{i}"]["conv"]) == 16_783_360
        assert count(shapes["layers_1"]["attn"]) == 10_485_888
        assert count(shapes["layers_0"]["ffn"]) == 72_351_744
        assert count(shapes["layers_1"]["moe"]) == 75_628_608
        assert shapes["layers_1"]["moe"]["bias"].shape == (64,)
        assert shapes["layers_1"]["moe"]["kernel"].shape == (2048, 64)
        assert shapes["layers_1"]["moe"]["routed_up"]["experts"].shape == (
            8, 2048, 1536)
        assert [count(shapes[f"layers_{i}"]) for i in range(5)] == [
            89_139_200, 86_118_592, 92_416_064, 92_416_064, 92_416_064]
        names = {str(p[-1].key) for p, _ in
                 jax.tree_util.tree_flatten_with_path(shapes)[0]}
        assert names == {"kernel", "embedding", "scale", "bias", "experts"}

    def test_the_configuration_keeps_every_published_width(self):
        with open(CONFIG) as f:
            config = json.load(f)
        cfg = lfm2.Lfm2Config()
        same = [f.name for f in dataclasses.fields(cfg) if f.name in config
                and f.name not in config["reduced"]]
        assert len(same) >= 14
        for k in same:
            value = getattr(cfg, k)
            assert config[k] == (list(value) if isinstance(value, tuple)
                                 else value), k
        assert sorted(config["reduced"]) == sorted(
            k for k in config["published"] if k != "n_params")
        for k in config["reduced"]:
            assert config["published"][k] == getattr(cfg, k), k
        assert config["rope_parameters"]["rope_theta"] == cfg.rope_theta
        assert config["head_dim"] == cfg.head_dim == 64
        assert tuple(config["layer_types"]) == lfm2.PUBLISHED
        assert cfg.layer_types.count(lfm2.CONV) == 30
        spec, run = config["spec"], config["layer_types_run"]
        assert run == config["layer_types"][1:6] == spec["layer_types"]
        assert run == config["model_kwargs"]["layer_types"]
        assert spec["num_experts"] == cfg.num_experts == 64
        for k in ("num_attention_heads", "num_key_value_heads", "norm_eps",
                  "conv_L_cache", "num_experts_per_tok", "norm_topk_prob",
                  "routed_scaling_factor", "rope_theta"):
            assert spec[k] == getattr(cfg, k), k
        # the keys an accepted reader looks up under DeepSeek's names
        assert config["first_k_dense_replace"] == config["num_dense_layers"]
        assert config["n_routed_experts"] == config["num_experts"] == len(
            config["model_kwargs"]["held_experts"])

    def test_token_models_share_one_example_shape_rule(self):
        assert TOKEN_LMS["lfm2_24b_a2b"] == (8192, 65536)
        _, example = create_model("lfm2_tiny")
        assert example(3).shape == (3, TOKEN_LMS["lfm2_tiny"][0])

    @pytest.mark.parametrize("kwargs", [
        dict(held_experts=()), dict(held_experts=(8,)),
        dict(layer_types=["conv"]), dict(layer_types=["mamba"] * 5),
        dict(conv_bias=True), dict(conv_L_cache=0),
        dict(num_attention_heads=3)])
    def test_a_shape_that_cannot_be_is_refused(self, kwargs):
        with pytest.raises(ValueError):
            lfm2.Lfm2Config.tiny(**kwargs)

    def test_the_head_is_the_embedding(self, tiny):
        """Tied: no leaf beside the embedding, whose gradient is the sum of
        both uses (the rows of tokens never fed still move, through the
        head)."""
        model, params, batch, _, step = tiny
        assert "lm_head" not in params
        g = step(params)[1]["embed"]["embedding"]
        fed = np.zeros(512, bool)
        fed[np.asarray(batch["tokens"]).ravel()] = True
        assert (~fed).any()
        assert float(jnp.min(jnp.linalg.norm(g[~fed], axis=1))) > 0

    def test_forward_recomputed_and_backward_ops_carry_the_sub_scopes(
            self, tiny):
        model, params, batch, _, step = tiny

        def loss(p):
            with anatomy.phase_scope("fwd_bwd"):
                return program_loss(model, batch).__wrapped__(p)[0][0]
        text = jax.jit(jax.grad(loss)).lower(params).compile().as_text()
        paths = set(re.findall(r'op_name="([^"]*)"', text))
        subs = anatomy.SUB_SCOPES["fwd_bwd"]
        by_sub = {sub: [p for p in paths if kernels_lm.sub_of(p, subs) == sub]
                  for sub in ("short_conv", "gated_conv", "attention",
                              "full_scores", "router", "experts", "mlp",
                              "head")}
        for sub, mine in by_sub.items():
            assert mine, sub
            assert any("transpose" not in p for p in mine), sub  # forward
            assert any("transpose" in p for p in mine), sub      # backward
        # the gated convolution lies inside the operator, the scores inside
        # attention; the operator is the conv layers' alone
        assert all("/short_conv/" in p for p in by_sub["gated_conv"])
        assert all("/attention/" in p for p in by_sub["full_scores"])
        assert {re.search(r"layers_(\d)", p).group(1)
                for p in by_sub["short_conv"]} == {"0", "2", "3", "4"}
        assert {re.search(r"layers_(\d)", p).group(1)
                for p in by_sub["full_scores"]} == {"1"}
        assert {re.search(r"layers_(\d)", p).group(1)
                for p in by_sub["mlp"]} == {"0"}
        # recomputed: the layer's remat runs W_in's product again, a conv
        # layer keeping its input alone: two [4, 64, 384] products a layer
        jaxpr = str(jax.make_jaxpr(jax.grad(loss))(params))
        assert jaxpr.count("f32[4,64,384] = dot_general") == 2 * 4
        # no flax module is named like a sub-scope
        for path, _ in jax.tree_util.tree_flatten_with_path(params)[0]:
            assert not any(str(k.key) in subs for k in path[:-1]), path

    def test_the_snapshot_names_the_scopes_and_the_calls(self, tiny):
        snap = profiling.snapshot()
        assert {"short_conv", "gated_conv"} <= set(
            snap["sub_scopes"]["fwd_bwd"])
        assert {"tokens": 64, "channels": 128, "taps": 3} in (
            snap["short_conv"])


def run_steps(trainer, steps, seed=0):
    workers = trainer.algo_cfg.num_workers
    losses, m = [], None
    for _ in range(steps):
        b = batch_of(seqs=2 * workers, seed=seed)      # one batch, learnt
        m = trainer.train_step({k: jnp.asarray(v) for k, v in b.items()})
        losses.append(float(m["loss"]))
    return losses, m


class TestTrainer:
    @pytest.mark.parametrize("compressor", ["dense", "oktopk"])
    def test_steps_on_four_workers(self, mesh4, compressor):
        cfg = TrainConfig(dnn="lfm2_tiny", dataset="ptb", batch_size=2,
                          lr=0.05, momentum=0.9, weight_decay=0.0,
                          compressor=compressor, density=0.05, grad_clip=1.0)
        tr = Trainer(cfg, mesh=mesh4, warmup=False,
                     model_kwargs={"held_experts": [0, 1, 2, 3]})
        losses, m = run_steps(tr, 4)
        assert all(np.isfinite(losses)) and losses[-1] < losses[0], losses
        for leaf in jax.tree.leaves(tr.state.params):
            shards = [np.asarray(s.data) for s in leaf.addressable_shards]
            assert all(np.array_equal(s, shards[0]) for s in shards[1:])
        # the selection bias stays where it started: no gradient, no decay
        for i in range(1, 5):
            assert not np.asarray(
                tr.state.params[f"layers_{i}"]["moe"]["bias"]).any()
        from oktopk_tpu.collectives.state import COUNTERS
        c = dict(zip(COUNTERS, np.asarray(m["counters"]).tolist()))
        # 4 workers x 2 sequences x 64 tokens x 2 experts a token, of which
        # the share routed to 4 held experts of 8; four expert layers
        assert 0 < c["expert_rows_max"] <= 4 * 128
        assert c["expert_rows_max"] <= c["expert_rows"] <= 4 * 4 * 128 * 2


class TestBenchmarkCounts:
    """benchmark/benchlib/kernels_conv.py behind ``gated_conv_roofline``,
    ``short_conv_mxu_share`` and ``narrow_head_scores_roofline``."""

    def test_counted_from_the_published_widths(self):
        with open(CONFIG) as f:
            config = json.load(f)
        k = kernels_conv
        assert k.layers_run(config, k.CONV) == 4
        assert k.layers_run(config, k.FULL) == 1
        assert k.gated_conv_bytes_a_step(config, 2) == (
            4 * 16384 * 2048 * 4 * 4 * 3)
        assert k.products_flops_a_step(config, 2) == (
            4 * 2 * 16384 * 2048 * 8192 * 3)
        assert k.scores_flops_a_step(config, 2) == (
            2 * 33_558_528 * 32 * 4 * 64 * 3)
        # useful work: under what the operator's own parameters make a
        # token do (the issue's 36 % of the forward pass)
        conv = 4 * 16_783_360
        assert k.products_flops_a_step(config, 2) <= 2 * 16384 * conv * 3

    def test_the_readers_take_the_innermost_sub_scope(self):
        subs = anatomy.SUB_SCOPES["fwd_bwd"]
        base = "jit(shard_fn)/anat/fwd_bwd/"
        for path, want in [
            (base + "jvp(Lfm2)/layers_0/anat/fwd_bwd/short_conv/conv/"
             "in_proj/dot_general", "short_conv"),
            (base + "transpose(jvp(Lfm2))/layers_2/anat/fwd_bwd/short_conv/"
             "conv/anat/fwd_bwd/gated_conv/vmap(mul)", "gated_conv"),
            (base + "jvp(Lfm2)/layers_1/anat/fwd_bwd/attention/attn/"
             "anat/fwd_bwd/full_scores/checkpoint/dot_general",
             "full_scores"),
            (base + "jvp(Lfm2)/layers_0/anat/fwd_bwd/mlp/ffn/dot_general",
             "mlp")]:
            assert kernels_lm.sub_of(path, subs) == want, path
