"""SmallThinker (models/smallthinker.py) against its plain reference
(benchmark/reference/smallthinker.py) at ``smallthinker_tiny``, on seeded
weights made by the benchmark's own rules (benchlib/weights.py): loss, every
gradient leaf and three SGD steps; the windowed attention against a masked
full-score computation; the two kinds of layer (mask and position); the
router's input; the ReLU gate; the share cut of expert parallelism; the
leaves' names, the sub-scopes, the counters, the band's operation count and
three steps through the ``Trainer``.
"""

import collections
import dataclasses
import json
import math
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))

from benchlib import discover, kernels_lm, kernels_swa, weights  # noqa: E402

from oktopk_tpu.config import TrainConfig  # noqa: E402
from oktopk_tpu.models import attention, layers, moe  # noqa: E402
from oktopk_tpu.models import create_model  # noqa: E402
from oktopk_tpu.models import smallthinker as st  # noqa: E402
from oktopk_tpu.models.registry import TOKEN_LMS  # noqa: E402
from oktopk_tpu.obs import anatomy  # noqa: E402
from oktopk_tpu.train.trainer import Trainer  # noqa: E402

REF = discover.load_module(
    os.path.join(ROOT, "benchmark", "reference", "smallthinker.py"))
CONFIG = os.path.join(ROOT, "benchmark", "configs",
                      "smallthinker_21b_a3b_ep8.json")

# float32 on the CPU: program and reference differ by the order of float32
# sums (2e-6 the worst gradient leaf read here, 1e-7 the loss); bfloat16
# compute reads 5e-2 and 3e-4. About ten times the sound reading.
LOSS_TOL, GRAD_TOL = 2e-6, 3e-5
HELD = (1, 2, 5, 6, 9, 12)


def spec_of(cfg, held=None, block=24):
    """The reference's ``spec`` for a model configuration."""
    layers = cfg.num_hidden_layers
    return dict(
        num_hidden_layers=layers,
        num_attention_heads=cfg.num_attention_heads,
        num_key_value_heads=cfg.num_key_value_heads, head_dim=cfg.head_dim,
        rope_theta=cfg.rope_theta, rope_layout=list(cfg.rope_layout[:layers]),
        sliding_window_layout=list(cfg.sliding_window_layout[:layers]),
        sliding_window_size=cfg.sliding_window_size,
        rms_norm_eps=cfg.rms_norm_eps,
        moe_num_primary_experts=cfg.moe_num_primary_experts,
        moe_num_active_primary_experts=cfg.moe_num_active_primary_experts,
        held_experts=list(cfg.held_experts if held is None else held),
        attn_block=block, head_block=32)


def seeded(model, example, seed=7):
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), example(2), train=False))["params"]
    return weights.make_params(shapes, seed)


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
    flat = jax.tree_util.tree_flatten_with_path(prog)[0]
    return {jax.tree_util.keystr(path): float(
        jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-30))
        for (path, a), b in zip(flat, jax.tree.leaves(ref))}


@pytest.fixture(scope="module")
def tiny():
    # one period (global, window, window, window), 4 x 64 tokens, a window
    # of 24 in blocks of 16, 4 of 16 experts a token, 6 held
    model, example = create_model("smallthinker_tiny", held_experts=HELD)
    params = seeded(model, example)
    batch = batch_of()
    ref = jax.jit(jax.value_and_grad(
        lambda p: REF.loss(p, batch, spec_of(model.cfg))))
    return model, params, batch, ref


class TestAgainstReference:
    def test_loss_and_every_gradient_leaf(self, tiny):
        model, params, batch, ref = tiny
        ref_loss, ref_grads = ref(params)
        (loss, _), grads = program_loss(model, batch)(params)
        assert abs(loss - ref_loss) / abs(ref_loss) < LOSS_TOL
        gaps = leaf_gaps(grads, ref_grads)
        # embed, 4 x (2 norms, 4 projections, router, 3 stacks), norm, head
        assert len(gaps) == 43 and max(gaps.values()) < GRAD_TOL, gaps

    def test_three_sgd_steps(self, tiny):
        """Plain SGD at lr 0.1, each side by its own gradients from the
        same start: the losses and the parameters stay together."""
        model, params, batch, ref = tiny
        step = program_loss(model, batch)
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
        _, params, batch, ref = tiny
        ref_loss, ref_grads = ref(params)
        model, _ = create_model("smallthinker_tiny", held_experts=HELD,
                                dtype=jnp.bfloat16)
        (loss, _), grads = program_loss(model, batch)(params)
        gaps = leaf_gaps(grads, ref_grads)
        assert abs(loss - ref_loss) / abs(ref_loss) > LOSS_TOL
        assert min(gaps.values()) > GRAD_TOL

    def test_counters_equal_the_reference_routing(self, tiny):
        """``expert_rows``: the reference's own routing of each layer's
        normalised INPUT, counted at the held experts."""
        model, params, batch, _ = tiny
        rows = model.apply({"params": params},
                           batch["tokens"])[1]["expert_rows"]
        cfg, spec = model.cfg, spec_of(model.cfg)
        x = params["embed"]["embedding"][batch["tokens"]]
        want = []
        for i in range(cfg.num_hidden_layers):
            p = params[f"layers_{i}"]
            h = REF._norm(x, p["attn_norm"]["scale"], cfg.rms_norm_eps)
            w = REF.routing(h.reshape(-1, h.shape[-1]), p["moe"]["kernel"],
                            spec)
            want.append(np.asarray(jnp.sum(w > 0, axis=0))[list(HELD)])
            x = jax.vmap(lambda s: REF.layer(p, s, spec, i))(x)
        assert np.array_equal(np.asarray(rows), np.stack(want))
        assert rows.shape == (4, len(HELD)) and int(rows.sum()) > 0

    def test_what_the_backward_pass_computes_again(self, tiny):
        """Every block's scores are in the gradient's program twice before
        their backward pass, and a windowed block's reach over the keys
        ``[max(0, start - window + 1), end)`` only."""
        model, params, batch, _ = tiny
        text = str(jax.make_jaxpr(
            lambda p: program_loss(model, batch).__wrapped__(p)[1])(params))
        cfg, t = model.cfg, batch["tokens"].shape[1]
        heads = (f"f32[{cfg.num_key_value_heads},"
                 f"{cfg.num_attention_heads // cfg.num_key_value_heads}")
        blk, w = cfg.attn_block, cfg.sliding_window_size
        # one global layer's blocks read [0, end), three windowed layers'
        # the cut keys; by the width of a block's scores
        want = collections.Counter()
        for start in range(0, t, blk):
            end = start + blk
            want[end] += 2
            want[end - max(0, start - w + 1)] += 2 * 3
        assert len(want) == 5       # 16, 32, 48, 64 and the cut 39
        for keys, count in want.items():
            assert text.count(f"{heads},{blk},{keys}] = exp ") == count, keys


def masked_attention(q, k, v, scale, window):
    """The whole score matrix, masked: q [B, T, H, d], k, v [B, T, G, d]."""
    b, t, h, d = q.shape
    k, v = (jnp.repeat(x, h // k.shape[2], axis=2) for x in (k, v))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    i, j = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    seen = j <= i
    if window is not None:
        seen = seen & (i - j < window)
    p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def qkv(t=64, heads=4, kv=2, d=32, seed=5):
    kq, kk, kv_ = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(kq, (2, t, heads, d)),
            jax.random.normal(kk, (2, t, kv, d)),
            jax.random.normal(kv_, (2, t, kv, d)))


class TestWindowedAttention:
    # window under, at and over the sequence, one key, and blocks that do
    # not divide the window or the sequence
    @pytest.mark.parametrize("window, block", [
        (24, 16), (24, 10), (17, 24), (1, 16), (16, 16), (63, 16),
        (64, 16), (100, 16), (24, 64), (None, 16)])
    def test_blocked_window_is_the_masked_full_scores(self, window, block):
        q, k, v = qkv()
        got = attention.blocked_causal_gqa(q, k, v, 32 ** -0.5, block, window)
        want = masked_attention(q, k, v, 32 ** -0.5, window)
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)

    @pytest.mark.parametrize("window, block", [(24, 16), (17, 24)])
    def test_its_gradients_too(self, window, block):
        q, k, v = qkv()
        w = jax.random.normal(jax.random.PRNGKey(9), q.shape)
        got = jax.grad(lambda *a: jnp.sum(w * attention.blocked_causal_gqa(
            *a, 32 ** -0.5, block, window)), argnums=(0, 1, 2))(q, k, v)
        want = jax.grad(lambda *a: jnp.sum(w * masked_attention(
            *a, 32 ** -0.5, window)), argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(got, want):
            np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)

    def test_a_window_ignored_is_caught(self):
        q, k, v = qkv()
        got = attention.blocked_causal_gqa(q, k, v, 32 ** -0.5, 16, None)
        want = masked_attention(q, k, v, 32 ** -0.5, 24)
        assert float(jnp.max(jnp.abs(got - want)[:, 24:])) > 1e-2
        np.testing.assert_allclose(got[:, :24], want[:, :24], rtol=2e-5,
                                   atol=2e-6)

    def test_a_window_block_reads_only_its_keys(self):
        """Keys before ``start - window + 1`` do not reach a block, not
        even as masked scores: a NaN there leaves the output finite."""
        q, k, v = qkv()
        k = k.at[:, :9].set(jnp.nan)
        v = v.at[:, :9].set(jnp.nan)
        got = attention.blocked_causal_gqa(q, k, v, 32 ** -0.5, 16, 24)
        assert bool(jnp.all(jnp.isfinite(got[:, 32:])))
        assert not bool(jnp.any(jnp.isfinite(got[:, :9])))


def attention_of(rotary, window, seed=3):
    attn = st.Attention(4, 2, 32, 10000.0, rotary, window, 16)
    h = jax.random.normal(jax.random.PRNGKey(seed), (1, 64, 128))
    params = attn.init(jax.random.PRNGKey(1), h)
    return attn, params, h


class TestTwoKindsOfLayer:
    @pytest.mark.parametrize("rotary", [False, True])
    def test_only_a_rotary_layer_reads_position(self, rotary):
        """Without position encoding the last query's output depends on
        WHICH tokens came before it and not on their order."""
        attn, params, h = attention_of(rotary, None)
        order = np.random.default_rng(0).permutation(63)
        shuffled = jnp.concatenate([h[:, order], h[:, 63:]], axis=1)
        a = attn.apply(params, h)[0, -1]
        b = attn.apply(params, shuffled)[0, -1]
        gap = float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(a)))
        assert (gap > 1e-2) if rotary else (gap < 1e-5), gap

    def test_the_window_cuts_what_the_last_query_sees(self):
        attn, params, h = attention_of(True, 24)
        far = h.at[:, :40].set(0.0)             # keys 0..39: out of reach
        near = h.at[:, 40:41].set(0.0)          # key 40: the window's first
        a = attn.apply(params, h)[0, -1]
        assert np.allclose(a, attn.apply(params, far)[0, -1], atol=1e-6)
        assert not np.allclose(a, attn.apply(params, near)[0, -1], atol=1e-4)

    @pytest.mark.parametrize("layers", [4, 8, 6])
    def test_one_global_layer_to_three_windowed(self, layers):
        cfg = st.SmallThinkerConfig.tiny(num_hidden_layers=layers)
        assert cfg.rope_layout[:layers] == (st.PERIOD * 13)[:layers]
        assert cfg.sliding_window_layout[:layers] == cfg.rope_layout[:layers]
        model, example = create_model("smallthinker_tiny",
                                      num_hidden_layers=layers)
        shapes = jax.eval_shape(lambda: model.init(
            jax.random.PRNGKey(0), example(2), train=False))["params"]
        assert len([k for k in shapes if k.startswith("layers_")]) == layers

    def test_the_layouts_are_read_layer_by_layer(self, tiny):
        """A layout that makes every layer global without position is
        another function; the kinds come from the two lists alone."""
        model, params, batch, _ = tiny
        flat, _ = create_model("smallthinker_tiny", held_experts=HELD,
                               rope_layout=[0] * 4,
                               sliding_window_layout=[0] * 4)
        a = program_loss(model, batch)(params)[0][0]
        b = program_loss(flat, batch)(params)[0][0]
        spec = dict(spec_of(flat.cfg))
        assert abs(a - b) > 1e-3
        assert abs(b - REF.loss(params, batch, spec)) / b < LOSS_TOL
        with pytest.raises(ValueError):
            st.SmallThinkerConfig.tiny(rope_layout=[0, 1])


def moe_params(d, f, e, seed=11):
    stack = lambda s: {"experts": jax.ShapeDtypeStruct(s, jnp.float32)}
    return weights.make_params({
        "kernel": jax.ShapeDtypeStruct((d, e), jnp.float32),
        "routed_gate": stack((e, d, f)), "routed_up": stack((e, d, f)),
        "routed_down": stack((e, f, d))}, seed)


def share_of(full, ids):
    ids = np.asarray(list(ids))
    return {k: ({"experts": v["experts"][ids]} if k.startswith("routed")
                else v) for k, v in full.items()}


class TestRouterAndGate:
    def test_the_router_reads_the_pre_attention_input(self):
        """The experts read ``h2``, the router ``h``: another ``h2`` leaves
        every token's experts as they were, another ``h`` does not."""
        d, f, e, k = 128, 64, 16, 4
        full = moe_params(d, f, e)
        layer = moe.MoE(e, tuple(range(e)), k, f, 0, 1.0, True, jnp.float32,
                        hidden_act="relu")
        h, h2, h3 = (jax.random.normal(jax.random.PRNGKey(s), (96, d))
                     for s in (1, 2, 3))
        y, rows = layer.apply({"params": full}, h2, router_input=h)
        _, same = layer.apply({"params": full}, h3, router_input=h)
        _, other = layer.apply({"params": full}, h2, router_input=h3)
        _, own = layer.apply({"params": full}, h2)
        assert np.array_equal(rows, same)
        assert not np.array_equal(rows, other)
        assert not np.array_equal(rows, own)
        w = REF.routing(h, full["kernel"], {
            "moe_num_active_primary_experts": k})
        want = REF.experts(full, h2, w, {"held_experts": range(e)})
        np.testing.assert_allclose(y, want, rtol=1e-4, atol=1e-5)

    def test_in_the_layer_the_attention_moves_no_token(self, tiny):
        """Perturb x' only (the attention's output projection): the
        layer's output moves, the rows each held expert computes do not."""
        model, params, batch, _ = tiny
        layer = st.DecoderLayer(model.cfg, 1)
        x = params["embed"]["embedding"][batch["tokens"]]
        p = params["layers_1"]
        moved = jax.tree.map(lambda a: a, p)
        moved["attn"]["o_proj"]["kernel"] = 3.0 * p["attn"]["o_proj"]["kernel"]
        out, rows = layer.apply({"params": p}, x)
        out2, rows2 = layer.apply({"params": moved}, x)
        assert np.array_equal(rows, rows2) and int(rows.sum()) > 0
        assert float(jnp.max(jnp.abs(out - out2))) > 1e-2

    def test_the_softmax_over_the_k_is_the_renormalised_softmax(self):
        d, e, k = 128, 16, 4
        h = jax.random.normal(jax.random.PRNGKey(4), (96, d))
        w_r = moe_params(d, 8, e)["kernel"]
        w = REF.routing(h, w_r, {"moe_num_active_primary_experts": k})
        assert np.array_equal(np.asarray(jnp.sum(w > 0, axis=1)),
                              np.full(96, k))
        np.testing.assert_allclose(jnp.sum(w, axis=1), 1.0, rtol=1e-6)
        scores = jax.nn.softmax(jnp.dot(h, w_r, precision=layers.HIGHEST), -1)
        top = jnp.where(w > 0, scores, 0.0)
        np.testing.assert_allclose(
            w, top / jnp.sum(top, axis=1, keepdims=True), rtol=1e-5)

    @pytest.mark.parametrize("act", ["relu", "silu"])
    def test_the_gate_is_the_configured_activation(self, act):
        d, f, e, k = 128, 64, 16, 4
        full = moe_params(d, f, e)
        h = jax.random.normal(jax.random.PRNGKey(5), (96, d))
        layer = moe.MoE(e, tuple(range(e)), k, f, 0, 1.0, True, jnp.float32,
                        hidden_act=act)
        y, _ = layer.apply({"params": full}, h)
        w = REF.routing(h, full["kernel"], {
            "moe_num_active_primary_experts": k})
        relu = REF.experts(full, h, w, {"held_experts": range(e)})
        gap = float(jnp.max(jnp.abs(y - relu)) / jnp.max(jnp.abs(relu)))
        assert (gap < 1e-5) if act == "relu" else (gap > 1e-2), gap

    @pytest.mark.parametrize("act", ["relu", "silu"])
    def test_both_branches_of_the_experts_take_it(self, act):
        """A buffer too small for the step's pairs sends them through the
        all-rows branch: the same function of the same activation."""
        d, f, e, k = 128, 64, 8, 2
        full = moe_params(d, f, e)
        x = jax.random.normal(jax.random.PRNGKey(6), (64, d))
        scores = jax.nn.softmax(x @ full["kernel"], axis=-1)
        top_w, top_i = jax.lax.top_k(scores, k)
        hit = top_i[..., None] == jnp.arange(e)
        routed = jnp.any(hit, axis=1)
        wts = jnp.sum(jnp.where(hit, top_w[..., None], 0.0), axis=1)
        stacks = [full[n]["experts"] for n in (
            "routed_gate", "routed_up", "routed_down")]
        fn = layers.ACTIVATIONS[act]
        grouped, _ = moe.routed_experts(x, wts, routed, *stacks, 128, k, fn)
        rows, _ = moe.routed_experts(x, wts, routed, *stacks, 64, k, fn)
        np.testing.assert_allclose(grouped, rows, rtol=1e-4, atol=1e-5)
        want = sum(layers.swiglu(x, *(s[i] for s in stacks), fn)
                   * wts[:, i:i + 1] for i in range(e))
        np.testing.assert_allclose(grouped, want, rtol=1e-4, atol=1e-5)


class TestShare:
    def test_eight_shares_with_attention_and_residual_once_make_the_uncut_layer(
            self, tiny):
        """Expert parallelism's cut (guide, section 4): eight chips hold
        two experts each; their expert outputs, with attention and the
        residual (what every chip computes alike) counted once, add up to
        the reference's uncut layer."""
        model, params, batch, _ = tiny
        cfg = model.cfg
        e, f, d = (cfg.moe_num_primary_experts, cfg.moe_ffn_hidden_size,
                   cfg.hidden_size)
        p = dict(params["layers_2"])
        p["moe"] = moe_params(d, f, e)
        x = params["embed"]["embedding"][batch["tokens"]]
        spec = spec_of(cfg, held=range(e))
        uncut = jax.vmap(lambda s: REF.layer(p, s, spec, 2))(x)
        # what every chip computes alike: x' = x + attention
        h = REF._norm(x, p["attn_norm"]["scale"], cfg.rms_norm_eps)
        alike = x + jax.vmap(lambda s: REF.attention(
            p["attn"], s, spec, 2))(h)
        total, rows = alike, 0
        for chip in range(8):
            held = (2 * chip, 2 * chip + 1)
            layer = st.DecoderLayer(dataclasses.replace(
                cfg, held_experts=held), 2)
            out, counts = layer.apply(
                {"params": dict(p, moe=share_of(p["moe"], held))}, x)
            total = total + (out - alike)
            rows += int(counts.sum())
        tokens = x.shape[0] * x.shape[1]
        # every pair, once
        assert rows == tokens * cfg.moe_num_active_primary_experts
        assert float(jnp.max(jnp.abs(total - uncut))) < 2e-5 * float(
            jnp.max(jnp.abs(uncut)))

    def test_capacity_of_the_cells_share(self):
        # 16,384 tokens, 6 of 64 a token, 8 held: 12,288 pairs on average
        assert moe.expert_capacity(16384, 8, 6, 64) == 18432


class TestRegistryAndScopes:
    def test_the_chips_share_of_the_published_model_is_371_million(self):
        with open(CONFIG) as f:
            config = json.load(f)
        model, example = create_model("smallthinker_21b_a3b",
                                      **config["model_kwargs"])
        shapes = jax.eval_shape(lambda: model.init(
            jax.random.PRNGKey(0), example(2), train=False))["params"]
        count = lambda tree: sum(math.prod(s.shape)
                                 for s in jax.tree.leaves(tree))
        assert count(shapes) == config["n_params"] == 370_547_200
        assert count(shapes["layers_0"]["attn"]) == 20_971_520
        assert count(shapes["layers_1"]["moe"]) == 163_840 + 8 * 5_898_240
        assert count(shapes["layers_0"]) == count(shapes["layers_3"])
        names = {str(p[-1].key) for p, _ in
                 jax.tree_util.tree_flatten_with_path(shapes)[0]}
        assert names == {"kernel", "embedding", "scale", "experts"}

    def test_the_configuration_keeps_every_published_width(self):
        with open(CONFIG) as f:
            config = json.load(f)
        cfg = st.SmallThinkerConfig()
        same = [f.name for f in dataclasses.fields(cfg) if f.name in config
                and f.name not in config["reduced"]]
        assert len(same) >= 13
        for k in same:
            value = getattr(cfg, k)
            assert config[k] == (list(value) if isinstance(value, tuple)
                                 else value), k
        for k in config["reduced"]:
            assert config["published"][k] == getattr(cfg, k), k
        assert config["spec"]["moe_num_primary_experts"] == (
            cfg.moe_num_primary_experts)
        assert config["seq_len"] == cfg.max_position_embeddings
        layers = config["num_hidden_layers"]
        for k in ("rope_layout", "sliding_window_layout"):
            assert config["spec"][k] == config[k][:layers] == [0, 1, 1, 1]

    def test_token_models_share_one_example_shape_rule(self):
        assert TOKEN_LMS["smallthinker_21b_a3b"] == (16384, 151936)
        _, example = create_model("smallthinker_tiny")
        assert example(3).shape == (3, TOKEN_LMS["smallthinker_tiny"][0])

    @pytest.mark.parametrize("held", [(), (0, 0), (16,), (-1,)])
    def test_held_experts_have_to_exist(self, held):
        with pytest.raises(ValueError):
            st.SmallThinkerConfig.tiny(held_experts=held)

    def test_forward_and_backward_ops_carry_the_sub_scopes(self, tiny):
        model, params, batch, _ = tiny

        def loss(p):
            with anatomy.phase_scope("fwd_bwd"):
                return program_loss(model, batch).__wrapped__(p)[0][0]
        text = jax.jit(jax.grad(loss)).lower(params).compile().as_text()
        paths = set(re.findall(r'op_name="([^"]*)"', text))
        subs = anatomy.SUB_SCOPES["fwd_bwd"]
        for sub in ("window_attention", "window_scores", "attention",
                    "router", "experts", "head"):
            mine = [p for p in paths if kernels_lm.sub_of(p, subs) == sub]
            assert mine, sub
            assert any("transpose" in p for p in mine), sub  # backward too
        # the banded scores lie inside window_attention (a reader takes the
        # innermost), and the global layer's scores under no window scope
        scores = [p for p in paths
                  if kernels_lm.sub_of(p, subs) == "window_scores"]
        assert all("window_attention" in p for p in scores)
        assert all("layers_0" not in p for p in scores)
        assert not any("window" in p for p in paths if "layers_0/" in p)
        # no flax module is named like a sub-scope
        for path, _ in jax.tree_util.tree_flatten_with_path(params)[0]:
            assert not any(str(k.key) in subs for k in path[:-1]), path


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
    def test_three_steps_on_four_workers(self, mesh4, compressor):
        cfg = TrainConfig(dnn="smallthinker_tiny", dataset="ptb",
                          batch_size=2, lr=0.05, momentum=0.9,
                          weight_decay=0.0, compressor=compressor,
                          density=0.05, grad_clip=1.0)
        tr = Trainer(cfg, mesh=mesh4, warmup=False,
                     model_kwargs={"held_experts": [0, 1, 2, 3]})
        losses, m = run_steps(tr, 3)
        assert all(np.isfinite(losses)) and losses[-1] < losses[0], losses
        for leaf in jax.tree.leaves(tr.state.params):
            shards = [np.asarray(s.data) for s in leaf.addressable_shards]
            assert all(np.array_equal(s, shards[0]) for s in shards[1:])
        from oktopk_tpu.collectives.state import COUNTERS
        c = dict(zip(COUNTERS, np.asarray(m["counters"]).tolist()))
        # 4 workers x 2 sequences x 64 tokens x 4 experts a token, of which
        # the share routed to 4 held experts of 16; four expert layers
        assert 0 < c["expert_rows_max"] <= 4 * 128
        assert c["expert_rows_max"] <= c["expert_rows"] <= 4 * 4 * 128 * 4


class TestBenchmarkCounts:
    """benchmark/benchlib/kernels_swa.py: the band's pairs, operations and
    bytes behind ``window_attention_roofline``."""

    @pytest.mark.parametrize("t, w", [(64, 24), (64, 64), (64, 100), (5, 1),
                                      (16384, 4096)])
    def test_the_bands_pairs(self, t, w):
        i = np.arange(t, dtype=np.int64)
        assert kernels_swa.band_pairs(t, w) == int(
            np.minimum(i + 1, w).sum())

    def test_counted_from_the_published_widths(self):
        with open(CONFIG) as f:
            config = json.load(f)
        assert kernels_swa.band_pairs(16384, 4096) == 58_722_304
        assert kernels_swa.window_layers(config) == 3
        # 3 windowed layers x the band x 28 heads x (q.k + p v) of 128;
        # forward + backward at twice a forward
        assert kernels_swa.window_scores_flops_a_step(config, 1) == (
            3 * 58_722_304 * 28 * 4 * 128 * 3)
        # q and the output of 28 heads, k and v of 4; float32
        a_token = 2 * 128 * (28 + 4) * 4
        assert kernels_swa.window_scores_bytes_a_step(config, 1) == (
            3 * 16384 * a_token * 3)
        least, bound = kernels_swa.window_scores_roofline_seconds(
            config, 1, "TPU v5 lite")
        assert bound == "compute" and 38e-3 < least < 39e-3

    def test_the_readers_take_the_innermost_sub_scope(self):
        subs = anatomy.SUB_SCOPES["fwd_bwd"]
        base = "jit(shard_fn)/anat/fwd_bwd/"
        for path, want in [
            (base + "jvp(SmallThinker)/layers_1/anat/fwd_bwd/"
             "window_attention/attn/q_proj/dot_general", "window_attention"),
            (base + "transpose(jvp(SmallThinker))/layers_1/anat/fwd_bwd/"
             "window_attention/attn/anat/fwd_bwd/window_scores/checkpoint/"
             "dot_general", "window_scores"),
            (base + "jvp(SmallThinker)/layers_0/anat/fwd_bwd/attention/attn/"
             "checkpoint/dot_general", "attention")]:
            assert kernels_lm.sub_of(path, subs) == want, path
