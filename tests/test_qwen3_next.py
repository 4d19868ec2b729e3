"""Qwen3-Next (models/qwen3_next.py) against its plain reference
(benchmark/reference/qwen3_next.py) at ``qwen3_next_tiny``, on seeded
weights made by the benchmark's own rules (benchlib/weights.py): loss and
every gradient leaf, the chunked delta rule against the token recurrence,
the share cut of expert parallelism, the renormalised routing, the partial
rotary, grouped-head attention, the layer pattern, the leaves' names, the
sub-scopes, the counters, and three steps through the ``Trainer``.
"""

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

from benchlib import discover, kernels_gdn, kernels_lm, weights  # noqa: E402

from oktopk_tpu.config import TrainConfig  # noqa: E402
from oktopk_tpu.models import attention, layers, moe  # noqa: E402
from oktopk_tpu.models import create_model  # noqa: E402
from oktopk_tpu.models import qwen3_next as qn  # noqa: E402
from oktopk_tpu.models.registry import TOKEN_LMS  # noqa: E402
from oktopk_tpu.obs import anatomy  # noqa: E402
from oktopk_tpu.train.trainer import Trainer  # noqa: E402

REF = discover.load_module(
    os.path.join(ROOT, "benchmark", "reference", "qwen3_next.py"))
CONFIG = os.path.join(ROOT, "benchmark", "configs",
                      "qwen3_next_80b_a3b_ep32.json")

# float32 on the CPU: program and reference differ by the order of float32
# sums and by the chunked form of the recurrence (3e-5 the worst leaf read
# here, a decay's gradient; 1.5e-7 the loss); bfloat16 compute reads 1e-1
# and 3e-4. About ten times the sound reading.
LOSS_TOL, GRAD_TOL = 2e-6, 3e-4
HELD = (1, 2, 5, 6, 9, 12)


def spec_of(cfg, held=None, block=16):
    """The reference's ``spec`` for a model configuration."""
    return dict(
        num_hidden_layers=cfg.num_hidden_layers,
        full_attention_interval=cfg.full_attention_interval,
        num_attention_heads=cfg.num_attention_heads,
        num_key_value_heads=cfg.num_key_value_heads, head_dim=cfg.head_dim,
        partial_rotary_factor=cfg.partial_rotary_factor,
        rope_theta=cfg.rope_theta,
        linear_num_key_heads=cfg.linear_num_key_heads,
        linear_num_value_heads=cfg.linear_num_value_heads,
        linear_key_head_dim=cfg.linear_key_head_dim,
        linear_value_head_dim=cfg.linear_value_head_dim,
        rms_norm_eps=cfg.rms_norm_eps, num_experts=cfg.num_experts,
        num_experts_per_tok=cfg.num_experts_per_tok,
        held_experts=list(cfg.held_experts if held is None else held),
        norm_topk_prob=cfg.norm_topk_prob, recurrence_block=block)


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
    # one period (3 linear + 1 full), 256 tokens, 4 of 16 experts a token,
    # 6 held; chunks of 8 and segments of 32 in sequences of 64
    model, example = create_model("qwen3_next_tiny", held_experts=HELD)
    params = seeded(model, example)
    batch = batch_of()
    ref = jax.jit(jax.value_and_grad(
        lambda p: REF.loss(p, batch, spec_of(model.cfg))))(params)
    return model, params, batch, ref


class TestAgainstReference:
    def test_loss_and_every_gradient_leaf(self, tiny):
        model, params, batch, (ref_loss, ref_grads) = tiny
        (loss, _), grads = program_loss(model, batch)(params)
        assert abs(loss - ref_loss) / abs(ref_loss) < LOSS_TOL
        gaps = leaf_gaps(grads, ref_grads)
        assert len(gaps) == 70 and max(gaps.values()) < GRAD_TOL, gaps

    def test_bfloat16_compute_fails_the_tolerances(self, tiny):
        _, params, batch, (ref_loss, ref_grads) = tiny
        model, _ = create_model("qwen3_next_tiny", held_experts=HELD,
                                dtype=jnp.bfloat16)
        (loss, _), grads = program_loss(model, batch)(params)
        gaps = leaf_gaps(grads, ref_grads)
        assert abs(loss - ref_loss) / abs(ref_loss) > LOSS_TOL
        assert min(gaps.values()) > GRAD_TOL

    def test_counters_equal_the_reference_routing(self, tiny):
        """``expert_rows``: the reference's own routing, layer by layer on
        the program's hidden states' twin, counted at the held experts."""
        model, params, batch, _ = tiny
        rows = model.apply({"params": params},
                           batch["tokens"])[1]["expert_rows"]
        cfg, spec = model.cfg, spec_of(model.cfg)
        x = params["embed"]["embedding"][batch["tokens"]]
        want = []
        for i in range(cfg.num_hidden_layers):
            p, full = params[f"layers_{i}"], REF.is_full(i, spec)
            h = jax.vmap(lambda s: REF._norm(
                REF.mixer(p, s, spec, full), p["ffn_norm"]["bias"],
                cfg.rms_norm_eps))(x)
            w = REF.routing(h.reshape(-1, h.shape[-1]), p["moe"]["kernel"],
                            spec)
            want.append(np.asarray(jnp.sum(w > 0, axis=0))[list(HELD)])
            x = jax.vmap(lambda s: REF._layer(p, s, spec, full))(x)
        assert np.array_equal(np.asarray(rows), np.stack(want))
        assert rows.shape == (4, len(HELD)) and int(rows.sum()) > 0

    def test_what_the_backward_pass_computes_again(self, tiny):
        """The recurrence's chunk scan is in the gradient's program three
        times a linear layer (forward, its segment's recomputation, and the
        backward scan), the attention's score blocks twice before their
        backward pass: the layer's own recomputation starts from the kept
        ``ATTN_OUT`` and runs neither."""
        model, params, batch, _ = tiny
        text = str(jax.make_jaxpr(
            lambda p: program_loss(model, batch).__wrapped__(p)[1])(params))
        cfg, t = model.cfg, batch["tokens"].shape[1]
        for end in range(cfg.attn_block, t + 1, cfg.attn_block):
            scores = (f"f32[{cfg.num_key_value_heads},"
                      f"{cfg.num_attention_heads // cfg.num_key_value_heads}"
                      f",{cfg.attn_block},{end}] = exp ")
            assert text.count(scores) == 2, end
        # the state a chunk step carries: [B, Hv, dk, dv]
        carry = (f"f32[4,{cfg.linear_num_value_heads},"
                 f"{cfg.linear_key_head_dim},{cfg.linear_value_head_dim}]")
        assert text.count("scan[") >= 3 * 3 and carry in text


def token_recurrence(q, k, v, g, beta):
    """The reference's token recurrence on [B, T, H, d] inputs whose key
    heads are already repeated."""
    return jax.vmap(lambda *x: REF.delta_rule(*x, 16))(q, k, v, g, beta)


def delta_inputs(b, t, hk, hv, dk, dv, decay, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (b, t, hk, dk))) * dk ** -0.5
    k = unit(jax.random.normal(ks[1], (b, t, hk, dk)))
    v = jax.random.normal(ks[2], (b, t, hv, dv))
    # a token's decay exp(g) round ``decay``
    g = math.log(decay) * jax.random.uniform(ks[3], (b, t, hv), minval=0.5,
                                             maxval=1.5)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, t, hv)))
    return q, k, v, g, beta


class TestDeltaRule:
    @pytest.mark.parametrize("t, chunk, segment", [
        (64, 8, 32),        # chunks and segments divide the sequence
        (64, 16, 64),       # one segment
        (50, 8, 16),        # neither divides it: padded tokens
        (24, 32, 1024)])    # one chunk longer than the sequence
    @pytest.mark.parametrize("decay", [0.5, 1e-6, 0.999999])
    def test_chunked_form_is_the_token_recurrence(self, t, chunk, segment,
                                                  decay):
        """Forward and gradient, at chunk sizes that do and do not divide
        the sequence, at a decay a token near 0 and near 1."""
        q, k, v, g, beta = delta_inputs(2, t, 2, 4, 16, 8, decay)
        rep = lambda x: jnp.repeat(x, 2, axis=2)

        def chunked(q, k, v, g, beta):
            o = qn.gated_delta_rule(q, k, v, g, beta, chunk, segment)
            return jnp.sum(o * jnp.cos(o)), o

        def by_token(q, k, v, g, beta):
            o = token_recurrence(rep(q), rep(k), v, g, beta)
            return jnp.sum(o * jnp.cos(o)), o

        args = (q, k, v, g, beta)
        (_, o), grads = jax.value_and_grad(chunked, argnums=range(5),
                                           has_aux=True)(*args)
        (_, want), want_grads = jax.value_and_grad(
            by_token, argnums=range(5), has_aux=True)(*args)
        assert o.shape == (2, t, 4, 8)
        np.testing.assert_allclose(o, want, rtol=2e-4, atol=2e-6)
        for got, ref in zip(grads, want_grads):
            assert bool(jnp.all(jnp.isfinite(got)))
            np.testing.assert_allclose(got, ref, rtol=2e-3, atol=2e-5)

    def test_a_decay_left_out_is_caught(self):
        """The control of the comparison: the same recurrence with the
        state's decay dropped (g = 0) is far outside the tolerance."""
        q, k, v, g, beta = delta_inputs(2, 64, 2, 4, 16, 8, 0.5)
        o = qn.gated_delta_rule(q, k, v, jnp.zeros_like(g), beta, 8, 32)
        want = token_recurrence(*(jnp.repeat(x, 2, axis=2) for x in (q, k)),
                                v, g, beta)
        assert float(jnp.max(jnp.abs(o - want))) > 0.1

    @pytest.mark.parametrize("c", [1, 2, 8, 24, 64])
    def test_inverse_of_a_unit_lower_triangular_matrix(self, c):
        low = jnp.tril(jax.random.normal(jax.random.PRNGKey(c), (3, c, c))
                       * 0.3, -1)
        inv = qn.inv_unit_lower(low)
        eye = jnp.eye(c)
        np.testing.assert_allclose(
            jnp.matmul(inv, eye + low, precision=layers.HIGHEST),
            jnp.broadcast_to(eye, low.shape), atol=2e-5)

    def test_causal_convolution_is_left_padded(self):
        x = jax.random.normal(jax.random.PRNGKey(0), (10, 3))
        w = jax.random.normal(jax.random.PRNGKey(1), (4, 3))
        got = layers.causal_conv(x, w)
        for t in range(10):
            want = sum(w[j] * x[t - 3 + j] for j in range(4) if t - 3 + j >= 0)
            np.testing.assert_allclose(got[t], want, rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(got, REF._conv(x, w), rtol=1e-6,
                                   atol=1e-6)


def moe_params(d, f, e, seed=11):
    stack = lambda s: {"experts": jax.ShapeDtypeStruct(s, jnp.float32)}
    kernel = lambda s: {"kernel": jax.ShapeDtypeStruct(s, jnp.float32)}
    return weights.make_params({
        "kernel": jax.ShapeDtypeStruct((d, e), jnp.float32),
        "routed_gate": stack((e, d, f)), "routed_up": stack((e, d, f)),
        "routed_down": stack((e, f, d)),
        "shared_ffn": {"gate_proj": kernel((d, f)), "up_proj": kernel((d, f)),
                       "down_proj": kernel((f, d))},
        "shared_gate": kernel((d, 1))}, seed)


class TestShare:
    def test_all_shares_and_the_gated_shared_expert_once_make_the_uncut_layer(
            self):
        """Expert parallelism's cut (guide, section 4): sixteen chips hold
        one expert each; their routed parts, with the gated shared expert
        counted once, add up to the reference's uncut layer."""
        cfg = qn.Qwen3NextConfig.tiny()
        d, f, e = cfg.hidden_size, cfg.moe_intermediate_size, cfg.num_experts
        h = jax.random.normal(jax.random.PRNGKey(3), (2, 48, d))
        full = moe_params(d, f, e)
        uncut = REF.experts(full, h.reshape(-1, d),
                            spec_of(cfg, held=range(e)))
        x = h.reshape(-1, d)
        total = layers.swiglu(x, *(full["shared_ffn"][n]["kernel"] for n in (
            "gate_proj", "up_proj", "down_proj"))) * jax.nn.sigmoid(
                x @ full["shared_gate"]["kernel"])
        rows = 0
        for chip in range(e):
            layer = moe.MoE(e, (chip,), cfg.num_experts_per_tok, f, 0, 1.0,
                            True, jnp.float32)
            share = {k: ({"experts": v["experts"][chip:chip + 1]}
                         if k.startswith("routed") else v)
                     for k, v in full.items() if not k.startswith("shared")}
            y, counts = layer.apply({"params": share}, h)
            total = total + y.reshape(-1, d)
            rows += int(counts.sum())
        assert rows == 2 * 48 * cfg.num_experts_per_tok  # every pair, once
        assert float(jnp.max(jnp.abs(total - uncut))) < 1e-5 * float(
            jnp.max(jnp.abs(uncut)))

    def test_renormalised_weights_sum_to_one_over_held_and_absent(self):
        """The k weights of a token are renormalised over the k, held or
        not: all shares' weights of a token add up to 1, one chip's to
        less."""
        cfg = qn.Qwen3NextConfig.tiny()
        d, e, k = cfg.hidden_size, cfg.num_experts, cfg.num_experts_per_tok
        h = jax.random.normal(jax.random.PRNGKey(4), (96, d))
        w_r = moe_params(d, 8, e)["kernel"]
        w = REF.routing(h, w_r, spec_of(cfg))
        assert np.array_equal(np.asarray(jnp.sum(w > 0, axis=1)),
                              np.full(96, k))
        np.testing.assert_allclose(jnp.sum(w, axis=1), 1.0, rtol=1e-6)
        held = jnp.sum(w[:, :4], axis=1)
        assert float(jnp.max(held)) < 1.0 and float(jnp.min(held)) >= 0.0
        # ... and the program's layer weighs its held experts by them
        full = moe_params(d, 8, e)
        layer = moe.MoE(e, (0, 1, 2, 3), k, 8, 0, 1.0, True, jnp.float32)
        share = {n: ({"experts": v["experts"][:4]} if n.startswith("routed")
                     else v) for n, v in full.items()
                 if not n.startswith("shared")}
        y, _ = layer.apply({"params": share}, h)
        want = sum(layers.swiglu(h, full["routed_gate"]["experts"][i],
                                 full["routed_up"]["experts"][i],
                                 full["routed_down"]["experts"][i])
                   * w[:, i:i + 1] for i in range(4))
        np.testing.assert_allclose(y, want, rtol=1e-4, atol=1e-6)

    def test_capacity_of_the_cells_share(self):
        # 16,384 tokens, 10 of 512 a token, 16 held: 5,120 pairs on average
        assert moe.expert_capacity(16384, 16, 10, 512) == 7680


class TestAttentionAndRotary:
    def test_partial_rotary_turns_the_first_quarter_in_halves(self):
        t, hd, rot = 6, 256, 64
        x = jax.random.normal(jax.random.PRNGKey(2), (t, 3, hd))
        freq = 1.0 / 1e7 ** (jnp.arange(0, rot, 2) / rot)
        ang = jnp.arange(t)[:, None] * freq
        got = attention.rotate_half_partial(x, jnp.cos(ang), jnp.sin(ang))
        # dims 64-255 pass untouched
        assert np.array_equal(np.asarray(got[..., rot:]),
                              np.asarray(x[..., rot:]))
        # the half-split convention: dim i pairs with dim i + 32, not i + 1
        z = (x[..., :32] + 1j * x[..., 32:64]) * jnp.exp(1j * ang)[:, None]
        np.testing.assert_allclose(got[..., :32], z.real, atol=1e-5)
        np.testing.assert_allclose(got[..., 32:64], z.imag, atol=1e-5)
        pairs = attention.rotate_pairs(x[..., :rot], jnp.cos(ang),
                                       jnp.sin(ang))
        assert float(jnp.max(jnp.abs(pairs - got[..., :rot]))) > 0.1
        # ... as the reference's own rotary does
        spec = {"partial_rotary_factor": 0.25, "rope_theta": 1e7}
        np.testing.assert_allclose(got, REF._rotary(x, spec), atol=1e-5)

    @pytest.mark.parametrize("block", [16, 24, 64])
    def test_blocked_grouped_attention_is_the_plain_softmax(self, block):
        """16 query heads over 2 key-value heads: query head h reads
        key-value head h // 8."""
        b, t, h, g, d = 2, 64, 16, 2, 8
        ks = jax.random.split(jax.random.PRNGKey(1), 3)
        q = jax.random.normal(ks[0], (b, t, h, d))
        k, v = (jax.random.normal(x, (b, t, g, d)) for x in ks[1:])
        got = attention.blocked_causal_gqa(q, k, v, 0.3, block)
        kk, vv = (jnp.repeat(x, h // g, axis=2) for x in (k, v))
        s = jnp.einsum("bqhd,bkhd->bhqk", q, kk) * 0.3
        s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
        want = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), vv)
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)

    def test_gated_attention_is_the_references(self, tiny):
        """The whole mixer of the full-attention layer, gate and head norms
        included, on the model's own parameters."""
        model, params, batch, _ = tiny
        cfg = model.cfg
        p = params["layers_3"]["attn"]
        h = jax.random.normal(jax.random.PRNGKey(5), (2, 64, cfg.hidden_size))
        attn = qn.GatedAttention(
            cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim,
            cfg.partial_rotary_factor, cfg.rope_theta, cfg.rms_norm_eps,
            cfg.attn_block)
        got = attn.apply({"params": p}, h)
        want = jax.vmap(lambda s: REF._attention(p, s, spec_of(cfg)))(h)
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-6)


class TestRegistryAndScopes:
    def test_the_chips_share_of_the_published_model_is_424_million(self):
        with open(CONFIG) as f:
            config = json.load(f)
        model, example = create_model("qwen3_next_80b_a3b",
                                      **config["model_kwargs"])
        shapes = jax.eval_shape(lambda: model.init(
            jax.random.PRNGKey(0), example(2), train=False))["params"]
        count = lambda tree: sum(math.prod(s.shape)
                                 for s in jax.tree.leaves(tree))
        assert count(shapes) == config["n_params"] == 424_340_544
        assert count(shapes["layers_0"]["linear_attn"]) == 33_718_464
        assert count(shapes["layers_3"]["attn"]) == 27_263_488
        assert count(shapes["layers_0"]) - 33_718_464 == 54_532_096
        assert shapes["layers_0"]["linear_attn"]["conv"]["kernel"].shape == (
            4, 8192)
        names = {str(p[-1].key) for p, _ in
                 jax.tree_util.tree_flatten_with_path(shapes)[0]}
        assert names == {"kernel", "embedding", "scale", "bias", "experts"}

    def test_the_configuration_keeps_every_published_width(self):
        with open(CONFIG) as f:
            config = json.load(f)
        cfg = qn.Qwen3NextConfig()
        same = [f.name for f in dataclasses.fields(cfg) if f.name in config
                and f.name not in config["reduced"]]
        assert len(same) >= 17
        for k in same:
            assert config[k] == getattr(cfg, k), k
        for k in config["reduced"]:
            assert config["published"][k] == getattr(cfg, k), k
        assert config["spec"]["num_experts"] == cfg.num_experts

    @pytest.mark.parametrize("layers", [4, 8, 6])
    def test_three_linear_layers_to_one_full(self, layers):
        cfg = qn.Qwen3NextConfig.tiny(num_hidden_layers=layers)
        model, example = create_model("qwen3_next_tiny",
                                      num_hidden_layers=layers)
        shapes = jax.eval_shape(lambda: model.init(
            jax.random.PRNGKey(0), example(2), train=False))["params"]
        for i in range(layers):
            full = (i + 1) % 4 == 0
            assert cfg.full_attention(i) == full
            assert REF.is_full(i, {"full_attention_interval": 4}) == full
            assert ("attn" in shapes[f"layers_{i}"]) == full
            assert ("linear_attn" in shapes[f"layers_{i}"]) == (not full)

    def test_token_models_share_one_example_shape_rule(self):
        assert TOKEN_LMS["qwen3_next_80b_a3b"] == (8192, 151936)
        _, example = create_model("qwen3_next_tiny")
        assert example(3).shape == (3, TOKEN_LMS["qwen3_next_tiny"][0])

    @pytest.mark.parametrize("held", [(), (0, 0), (16,), (-1,)])
    def test_held_experts_have_to_exist(self, held):
        with pytest.raises(ValueError):
            qn.Qwen3NextConfig.tiny(held_experts=held)

    def test_forward_and_backward_ops_carry_the_sub_scopes(self, tiny):
        model, params, batch, _ = tiny

        def loss(p):
            with anatomy.phase_scope("fwd_bwd"):
                return program_loss(model, batch).__wrapped__(p)[0][0]
        text = jax.jit(jax.grad(loss)).lower(params).compile().as_text()
        paths = set(re.findall(r'op_name="([^"]*)"', text))
        subs = anatomy.SUB_SCOPES["fwd_bwd"]
        for sub in ("linear_attention", "delta_rule", "attention", "router",
                    "experts", "shared", "head"):
            mine = [p for p in paths if kernels_lm.sub_of(p, subs) == sub]
            assert mine, sub
            assert any("transpose" in p for p in mine), sub  # backward too
        # the recurrence lies inside linear_attention: a reader takes the
        # innermost
        assert any("linear_attention" in p for p in paths
                   if kernels_lm.sub_of(p, subs) == "delta_rule")
        # no flax module is named like a sub-scope (the last key is the
        # leaf's name, which is no part of an operation's scope path)
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
        cfg = TrainConfig(dnn="qwen3_next_tiny", dataset="ptb",
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
    """benchmark/benchlib/kernels_gdn.py: the token recurrence's operations
    and bytes behind ``delta_rule_roofline``."""

    def test_counted_from_the_published_widths(self):
        with open(CONFIG) as f:
            config = json.load(f)
        # 3 linear layers x 16,384 tokens x 32 heads x 7 x 128 x 128;
        # forward + backward at twice a forward
        assert kernels_gdn.delta_rule_flops_a_step(config, 16384) == (
            3 * 16384 * 32 * 7 * 128 * 128 * 3)
        # q, k of 16 heads, v and o of 32, g and beta; float32
        a_token = (2 * 16 * 128 + 2 * 32 * 128 + 2 * 32) * 4
        assert kernels_gdn.delta_rule_bytes_a_step(config, 16384) == (
            3 * 16384 * a_token * 3)
        least, bound = kernels_gdn.delta_rule_roofline_seconds(
            config, 16384, "TPU v5 lite")
        assert bound == "memory" and 8e-3 < least < 10e-3

    def test_the_readers_take_the_innermost_sub_scope(self):
        subs = anatomy.SUB_SCOPES["fwd_bwd"]
        base = "jit(shard_fn)/anat/fwd_bwd/"
        for path, want in [
            (base + "jvp(Qwen3Next)/layers_0/anat/fwd_bwd/linear_attention/"
             "linear_attn/out_proj/dot_general", "linear_attention"),
            (base + "transpose(jvp(Qwen3Next))/layers_0/anat/fwd_bwd/"
             "linear_attention/linear_attn/anat/fwd_bwd/delta_rule/"
             "checkpoint/while/body/dot_general", "delta_rule"),
            (base + "jvp(Qwen3Next)/layers_3/anat/fwd_bwd/attention/attn/"
             "q_proj/dot_general", "attention")]:
            assert kernels_lm.sub_of(path, subs) == want, path


class TestDeepseekIsTheParents:
    def test_loss_and_gradients_are_bit_identical_to_the_recorded(self):
        """``models/deepseek_v2.py`` gained one field, off for its own
        model: ``deepseek_v2_tiny``'s loss and every gradient leaf on
        seeded weights hash to what the parent commit's code gave (recorded
        from a checkout of commit 4e39571 by this same function)."""
        assert deepseek_digest() == DEEPSEEK_AT_PARENT


def deepseek_digest():
    import hashlib
    model, example = create_model("deepseek_v2_tiny",
                                  held_experts=(1, 2, 5, 6))
    params = seeded(model, example)
    batch = batch_of()
    (loss, rows), grads = program_loss(model, batch)(params)
    digest = hashlib.sha256()
    for x in [loss, rows] + jax.tree.leaves(grads):
        digest.update(np.asarray(x).tobytes())
    return digest.hexdigest()


DEEPSEEK_AT_PARENT = (
    "acc2ecf97eb274dabae8bbcd425e53ac751132a6385341bdd2efcb85192da3ce")


class TestWindowArgumentAtItsDefault:
    def test_loss_and_gradients_hash_to_the_parents(self, tiny):
        """``blocked_causal_gqa`` gained ``window`` and ``MoE`` three
        fields for ``models/smallthinker.py``: at their defaults
        ``qwen3_next_tiny``'s loss, counters and every gradient leaf on
        seeded weights hash to what the parent commit's code gave
        (recorded from a checkout of commit d8fca06 by these same
        lines)."""
        import hashlib
        model, params, batch, _ = tiny
        (loss, rows), grads = program_loss(model, batch)(params)
        digest = hashlib.sha256()
        for x in [loss, rows] + jax.tree.leaves(grads):
            digest.update(np.asarray(x).tobytes())
        assert digest.hexdigest() == QWEN3_NEXT_AT_PARENT
        # a window that holds the whole sequence is no window: the same
        # program, so the same bits
        kq, kk, kv = jax.random.split(jax.random.PRNGKey(5), 3)
        q = jax.random.normal(kq, (2, 64, 4, 32))
        k = jax.random.normal(kk, (2, 64, 2, 32))
        v = jax.random.normal(kv, (2, 64, 2, 32))
        plain = jax.make_jaxpr(lambda: attention.blocked_causal_gqa(
            q, k, v, 0.2, 16))()
        for window in (None, 64, 1000):
            assert str(jax.make_jaxpr(lambda: attention.blocked_causal_gqa(
                q, k, v, 0.2, 16, window))()) == str(plain)
        assert str(jax.make_jaxpr(lambda: attention.blocked_causal_gqa(
            q, k, v, 0.2, 16, 63))()) != str(plain)


QWEN3_NEXT_AT_PARENT = (
    "bb4965c46b792d66869f9b6eaae3d07984092b6e4392f43e53a1bf510443bb00")
