"""Laguna (models/laguna.py) against its plain reference
(benchmark/reference/laguna.py) at ``laguna_tiny``, on seeded weights made
by the benchmark's own rules (benchlib/weights.py): loss, every gradient
leaf and three SGD steps; what a layer's kind decides (head count, rotary
record, window); the per-head gate; sigmoid routing beside the softmax the
other models keep; the share cut of 32-way expert parallelism; the leaves'
names, the parameter count of the chip's share, the sub-scopes, the
counters, the benchmark's counts of the triangle and the band, and three
steps through the ``Trainer``.
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

from benchlib import (discover, kernels_lm, kernels_mixed_gqa,  # noqa: E402
                      weights)

from oktopk_tpu.config import TrainConfig  # noqa: E402
from oktopk_tpu.models import attention, layers, moe  # noqa: E402
from oktopk_tpu.models import create_model  # noqa: E402
from oktopk_tpu.models import laguna as la  # noqa: E402
from oktopk_tpu.models.registry import TOKEN_LMS  # noqa: E402
from oktopk_tpu.obs import anatomy  # noqa: E402
from oktopk_tpu.train.trainer import Trainer  # noqa: E402

REF = discover.load_module(
    os.path.join(ROOT, "benchmark", "reference", "laguna.py"))
CONFIG = os.path.join(ROOT, "benchmark", "configs", "laguna_xs2_ep32.json")

# float32 on the CPU: program and reference differ by the order of float32
# sums (2e-6 the worst gradient leaf read here, 1e-7 the loss); bfloat16
# compute reads 2e-2 at its best leaf and 4e-4 in the loss. About ten times
# the sound reading.
LOSS_TOL, GRAD_TOL = 2e-6, 3e-5
HELD = (1, 2, 5, 6, 9, 12)


def rope_spec(rope):
    """A ``Rope`` record as the published ``rope_parameters`` entry."""
    return dataclasses.asdict(rope)


def spec_of(cfg, held=None, block=24):
    """The reference's ``spec`` for a model configuration."""
    n = cfg.num_hidden_layers
    return dict(
        num_hidden_layers=n, layer_types=list(cfg.layer_types[:n]),
        mlp_layer_types=list(cfg.mlp_layer_types[:n]),
        num_attention_heads_per_layer=list(
            cfg.num_attention_heads_per_layer[:n]),
        num_key_value_heads=cfg.num_key_value_heads, head_dim=cfg.head_dim,
        sliding_window=cfg.sliding_window,
        rope_parameters={la.FULL: rope_spec(cfg.rope_full),
                         la.SLIDING: rope_spec(cfg.rope_sliding)},
        rms_norm_eps=cfg.rms_norm_eps, num_experts=cfg.num_experts,
        num_experts_per_tok=cfg.num_experts_per_tok,
        moe_routed_scaling_factor=cfg.moe_routed_scaling_factor,
        held_experts=list(cfg.held_experts if held is None else held),
        attn_block=block, mlp_block=32, head_block=32)


def reference_loss(params, batch, spec):
    return jax.jit(lambda p: REF.loss(p, batch, spec))(params)


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
    # layers 0-4 (full and dense, three sliding, full), 4 x 64 tokens, a
    # window of 16 in blocks of 16, 4 of 16 experts a token, 6 held
    model, example = create_model("laguna_tiny", held_experts=HELD)
    params = seeded(model, example)
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
        # embed, 5 x (2 norms, 5 projections), a dense layer's 3 kernels,
        # 4 x (router, 3 stacks, the shared expert's 3), norm, head
        assert len(gaps) == 1 + 5 * 7 + 3 + 4 * 7 + 2
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
        model, _ = create_model("laguna_tiny", held_experts=HELD,
                                dtype=jnp.bfloat16)
        (loss, _), grads = program_loss(model, batch)(params)
        gaps = leaf_gaps(grads, ref_grads)
        assert abs(loss - ref_loss) / abs(ref_loss) > LOSS_TOL
        assert min(gaps.values()) > GRAD_TOL

    def test_counters_equal_the_reference_routing(self, tiny):
        """``expert_rows``: the reference's own routing of each sparse
        layer's post-attention state, counted at the held experts."""
        model, params, batch, _, step = tiny
        rows = step(params)[0][1]
        cfg, spec = model.cfg, spec_of(model.cfg)

        @jax.jit
        def reference_rows(params):
            x = params["embed"]["embedding"][batch["tokens"]]
            want = []
            for i in range(cfg.num_hidden_layers):
                p = params[f"layers_{i}"]
                if cfg.mlp_layer_types[i] == la.SPARSE:
                    mid = x + jax.vmap(lambda s: REF.attention(
                        p["attn"], s, spec, i))(REF._norm(
                            x, p["attn_norm"]["scale"], cfg.rms_norm_eps))
                    h2 = REF._norm(mid, p["ffn_norm"]["scale"],
                                   cfg.rms_norm_eps)
                    w = REF.routing(h2.reshape(-1, h2.shape[-1]),
                                    p["moe"]["kernel"], spec)
                    want.append(jnp.sum(w > 0, axis=0)[jnp.asarray(HELD)])
                x = jax.vmap(lambda s: REF.layer(p, s, spec, i))(x)
            return jnp.stack(want)
        want = reference_rows(params)
        assert np.array_equal(np.asarray(rows), np.stack(want))
        # the dense layer counts nothing: four sparse layers' rows
        assert rows.shape == (4, len(HELD)) and int(rows.min()) > 0


def attention_of(kind, cfg=None, seed=3):
    """One attention block of ``kind`` at the tiny widths, its parameters
    and an input [1, 64, 128]."""
    cfg = cfg or la.LagunaConfig.tiny()
    full = kind == la.FULL
    attn = la.Attention(12 if full else 16, 2, 32,
                        cfg.rope_full if full else cfg.rope_sliding,
                        None if full else cfg.sliding_window, 16)
    h = jax.random.normal(jax.random.PRNGKey(seed), (1, 64, 128))
    params = attn.init(jax.random.PRNGKey(1), h)
    return attn, params, h


class TestTheLayersKind:
    def test_it_decides_the_head_count(self, tiny):
        """12 query heads and gates in a full layer, 16 in a sliding one,
        both over 2 key-value heads of 32 (groups of 6 and of 8)."""
        _, params, _, _, _ = tiny
        for i, heads in enumerate((12, 16, 16, 16, 12)):
            attn = params[f"layers_{i}"]["attn"]
            assert attn["q_proj"]["kernel"].shape == (128, heads * 32), i
            assert attn["g_proj"]["kernel"].shape == (128, heads), i
            assert attn["o_proj"]["kernel"].shape == (heads * 32, 128), i
            assert attn["k_proj"]["kernel"].shape == (128, 2 * 32), i

    @pytest.mark.parametrize("kind", [la.FULL, la.SLIDING])
    def test_it_decides_the_window(self, kind):
        """A key ``sliding_window`` or more back reaches the last query of
        a full layer and not of a sliding one; the window's first key
        reaches both."""
        attn, params, h = attention_of(kind)
        far = h.at[:, :48].set(0.0)             # keys 0..47: 16 or more back
        near = h.at[:, 48:49].set(0.0)          # key 48: the window's first
        a = attn.apply(params, h)[0, -1]
        moved = not np.allclose(a, attn.apply(params, far)[0, -1], atol=1e-6)
        assert moved == (kind == la.FULL)
        assert not np.allclose(a, attn.apply(params, near)[0, -1], atol=1e-5)

    def test_it_decides_the_rotary_record(self, tiny):
        """The two records swapped is another function, caught by the
        tolerances; the reference told of the swap follows it."""
        model, params, batch, ref, step = tiny
        cfg = model.cfg
        swapped = la.Laguna(dataclasses.replace(
            cfg, rope_full=cfg.rope_sliding, rope_sliding=cfg.rope_full))
        a = float(step(params)[0][0])
        b = float(program_loss(swapped, batch)(params)[0][0])
        assert abs(a - b) / a > 100 * LOSS_TOL
        assert abs(b - reference_loss(
            params, batch, spec_of(swapped.cfg))) / b < LOSS_TOL

    def test_yarn_turns_half_a_head_at_its_own_frequencies(self):
        """The published full-attention record: 32 frequencies over dims
        0-63, ``yarn_inv_freq`` of 64 dims, cos and sin times
        ``attention_factor``; dims 64-127 pass. And the reference's own
        YaRN (written apart) gives the same frequencies."""
        rope = la.LagunaConfig().rope_full
        cos, sin = attention.rotary_table(rope, 128, 48)
        freq = attention.yarn_inv_freq(64, 500000.0, 64.0, 4096, 64.0, 1.0)
        assert cos.shape == sin.shape == (48, 32)
        ang = np.arange(48, dtype=np.float32)[:, None] * freq[None]
        amp = 0.1 * math.log(64.0) + 1.0
        assert rope.attention_factor == pytest.approx(amp, rel=1e-12)
        np.testing.assert_allclose(cos, np.cos(ang) * amp, rtol=1e-6,
                                   atol=1e-6)
        np.testing.assert_allclose(sin, np.sin(ang) * amp, rtol=1e-6,
                                   atol=1e-6)
        np.testing.assert_allclose(REF.yarn_freq(64, rope_spec(rope)), freq,
                                   rtol=1e-6)
        # the fastest pairs keep their frequency, the slowest are cut by 64
        plain = 500000.0 ** (-np.arange(0, 64, 2) / 64)
        assert freq[0] == pytest.approx(plain[0]) and (
            freq[-1] == pytest.approx(plain[-1] / 64))
        x = jax.random.normal(jax.random.PRNGKey(2), (48, 3, 128))
        y = attention.rotate_half_partial(x, cos, sin)
        assert np.array_equal(y[..., 64:], x[..., 64:])
        assert float(jnp.min(jnp.abs(y[1:, :, :64] - x[1:, :, :64]))) > 0
        # the sliding record: every dim, plain frequencies, no factor
        cos, _ = attention.rotary_table(la.LagunaConfig().rope_sliding, 128,
                                        48)
        np.testing.assert_allclose(cos, np.cos(
            np.arange(48)[:, None] * 10000.0 ** (-np.arange(0, 128, 2) / 128)
        ), rtol=1e-5, atol=1e-5)

    def test_the_lists_are_read_layer_by_layer(self, tiny):
        """Lists that make every layer sliding with 16 heads are another
        model; the kinds come from the lists alone, and a list too short
        or of an unknown kind is refused."""
        model, params, batch, _, step = tiny
        assert model.cfg.layer_types[:5] == (la.PERIOD * 2)[:5]
        assert model.cfg.mlp_layer_types[:5] == (la.DENSE,) + (la.SPARSE,) * 4
        flat, example = create_model(
            "laguna_tiny", held_experts=HELD, layer_types=[la.SLIDING] * 5,
            num_attention_heads_per_layer=[16] * 5)
        p = seeded(flat, example)
        assert p["layers_0"]["attn"]["q_proj"]["kernel"].shape == (128, 512)
        b = program_loss(flat, batch)(p)[0][0]
        assert abs(b - reference_loss(
            p, batch, spec_of(flat.cfg))) / b < LOSS_TOL
        for bad in (dict(layer_types=[la.FULL]),
                    dict(layer_types=["global"] * 5),
                    dict(num_attention_heads_per_layer=[12, 15, 16, 16, 12])):
            with pytest.raises(ValueError):
                la.LagunaConfig.tiny(**bad)


class TestTheGate:
    def test_a_gate_at_zero_silences_its_head(self):
        """Head 5's gate forced shut (its column of W_g reads a constant
        input at -1e4: sigmoid gives 0.0): the block's output is what it is
        with that head's rows of W_o zeroed, and differs from the open
        one's."""
        attn, params, h = attention_of(la.FULL)
        h = h.at[..., 0].set(1.0)
        p = jax.tree.map(lambda a: a, params)["params"]
        w_g = p["g_proj"]["kernel"].at[:, 5].set(0.0).at[0, 5].set(-1e4)
        shut = dict(p, g_proj={"kernel": w_g})
        w_o = p["o_proj"]["kernel"].reshape(12, 32, 128).at[5].set(0.0)
        cut = dict(p, o_proj={"kernel": w_o.reshape(384, 128)})
        a = attn.apply({"params": shut}, h)
        np.testing.assert_allclose(a, attn.apply({"params": cut}, h),
                                   rtol=1e-5, atol=1e-6)
        assert float(jnp.max(jnp.abs(a - attn.apply({"params": p}, h)))) > (
            1e-3)

    def test_a_gate_left_out_is_caught(self, tiny):
        """Every gate at 1 (what a model without the gate computes) is
        another function of the same weights."""
        model, params, batch, ref, step = tiny
        open_ = jax.tree.map(lambda a: a, params)
        for i in range(5):
            g = open_[f"layers_{i}"]["attn"]["g_proj"]
            g["kernel"] = jnp.zeros_like(g["kernel"])     # sigmoid(0) = 1/2
            o = open_[f"layers_{i}"]["attn"]["o_proj"]
            o["kernel"] = 2.0 * o["kernel"]               # ... times 2
        a, b = ref(params)[0], ref(open_)[0]
        assert abs(a - b) / a > 100 * LOSS_TOL
        c = step(open_)[0][0]
        assert abs(c - b) / b < LOSS_TOL


def moe_params(d, f, e, seed=11, shared=0):
    stack = lambda s: {"experts": jax.ShapeDtypeStruct(s, jnp.float32)}
    kern = lambda s: {"kernel": jax.ShapeDtypeStruct(s, jnp.float32)}
    tree = {"kernel": jax.ShapeDtypeStruct((d, e), jnp.float32),
            "routed_gate": stack((e, d, f)), "routed_up": stack((e, d, f)),
            "routed_down": stack((e, f, d))}
    if shared:
        tree["shared_ffn"] = {"gate_proj": kern((d, shared)),
                              "up_proj": kern((d, shared)),
                              "down_proj": kern((shared, d))}
    return weights.make_params(tree, seed)


def share_of(full, ids):
    ids = np.asarray(list(ids))
    return {k: ({"experts": v["experts"][ids]} if k.startswith("routed")
                else v) for k, v in full.items()}


class TestSigmoidRouting:
    D, F, E, K = 128, 64, 16, 4

    def test_the_weights_of_a_tokens_k_sum_to_the_scaling_factor(self):
        h = jax.random.normal(jax.random.PRNGKey(4), (96, self.D))
        w_r = moe_params(self.D, 8, self.E)["kernel"]
        spec = {"num_experts_per_tok": self.K,
                "moe_routed_scaling_factor": 2.5}
        w = REF.routing(h, w_r, spec)
        assert np.array_equal(np.asarray(jnp.sum(w > 0, axis=1)),
                              np.full(96, self.K))
        np.testing.assert_allclose(jnp.sum(w, axis=1), 2.5, rtol=1e-6)
        # they are the k largest sigmoids, renormalised: not a softmax
        scores = jax.nn.sigmoid(jnp.dot(h, w_r, precision=layers.HIGHEST))
        top = jnp.where(w > 0, scores, 0.0)
        assert float(jnp.min(jnp.where(w > 0, scores, 1.0))) >= float(
            jnp.max(jnp.where(w > 0, 0.0, scores), axis=1).min())
        np.testing.assert_allclose(
            w, 2.5 * top / jnp.sum(top, axis=1, keepdims=True), rtol=1e-5)

    @pytest.mark.parametrize("scoring", ["sigmoid", "softmax"])
    def test_the_module_scores_as_it_is_told(self, scoring):
        """``scoring="sigmoid"`` is the reference's layer (routed experts
        and the shared one); the softmax it replaces is not."""
        full = moe_params(self.D, self.F, self.E, shared=self.F)
        h = jax.random.normal(jax.random.PRNGKey(5), (96, self.D))
        layer = moe.MoE(self.E, tuple(range(self.E)), self.K, self.F, 1, 2.5,
                        True, jnp.float32, scoring=scoring)
        y, rows = layer.apply({"params": full}, h)
        spec = {"num_experts_per_tok": self.K,
                "moe_routed_scaling_factor": 2.5,
                "held_experts": range(self.E)}
        want = REF.experts(full, h, REF.routing(h, full["kernel"], spec),
                           spec)
        gap = float(jnp.max(jnp.abs(y - want)) / jnp.max(jnp.abs(want)))
        assert (gap < 1e-5) if scoring == "sigmoid" else (gap > 1e-2), gap
        assert int(rows.sum()) == 96 * self.K

    def test_the_default_is_the_softmax_as_it_was(self):
        """Left at its default the field changes nothing: the module's
        output is, bit for bit, the softmax router's as it stood before the
        field (its scores, top-k, renormalisation and scaling by hand, then
        the same ``routed_experts``)."""
        full = moe_params(self.D, self.F, self.E)
        x = jax.random.normal(jax.random.PRNGKey(6), (96, self.D))
        held = (1, 2, 5, 6)
        share = share_of(full, held)
        default = moe.MoE(self.E, held, self.K, self.F, 0, 1.0, True,
                          jnp.float32)
        assert default.scoring == "softmax"
        y, rows = default.apply({"params": share}, x)
        scores = jax.nn.softmax(
            jnp.dot(x, full["kernel"], precision=layers.HIGHEST), axis=-1)
        top_w, top_i = jax.lax.top_k(scores, self.K)
        top_w = top_w / (jnp.sum(top_w, -1, keepdims=True) + 1e-20) * 1.0
        hit = top_i[..., None] == jnp.asarray(held, jnp.int32)
        want, counts = moe.routed_experts(
            x, jnp.sum(jnp.where(hit, top_w[..., None], 0.0), axis=1),
            jnp.any(hit, axis=1), *(share[n]["experts"] for n in (
                "routed_gate", "routed_up", "routed_down")),
            moe.expert_capacity(96, len(held), self.K, self.E), self.K)
        assert np.array_equal(y, want) and np.array_equal(rows, counts)
        with pytest.raises(KeyError):
            moe.MoE(self.E, held, self.K, self.F, 0, 1.0, True, jnp.float32,
                    scoring="tanh").apply({"params": share}, x)


class TestShare:
    def test_32_shares_with_what_every_chip_computes_once_make_the_uncut_layer(
            self, tiny):
        """Expert parallelism's cut (guide, section 4) at 64 experts: 32
        chips hold two each; their routed parts, with attention, the shared
        expert and the residual (what every chip computes alike) counted
        once, add up to the reference's uncut layer. The dense layer has no
        routed part: it is the same on every chip."""
        model, params, batch, _, step = tiny
        cfg = dataclasses.replace(model.cfg, num_experts=64,
                                  held_experts=None)
        e, f, d = cfg.num_experts, cfg.moe_intermediate_size, cfg.hidden_size
        p = dict(params["layers_2"])
        p["moe"] = moe_params(d, f, e, shared=f)
        x = params["embed"]["embedding"][batch["tokens"]][:2]
        spec = spec_of(cfg, held=range(e))
        uncut = jax.vmap(lambda s: REF.layer(p, s, spec, 2))(x)
        # what every chip computes alike: x' = x + attention, and Shared(h')
        eps = cfg.rms_norm_eps
        mid = x + jax.vmap(lambda s: REF.attention(p["attn"], s, spec, 2))(
            REF._norm(x, p["attn_norm"]["scale"], eps))
        h2 = REF._norm(mid, p["ffn_norm"]["scale"], eps)
        shared = REF._ffn(p["moe"]["shared_ffn"], h2)
        total, rows = mid + shared, 0
        for chip in range(32):
            held = (2 * chip, 2 * chip + 1)
            layer = moe.MoE(e, held, cfg.num_experts_per_tok, f, 1,
                            cfg.moe_routed_scaling_factor, True, jnp.float32,
                            scoring="sigmoid")
            y, counts = layer.apply({"params": share_of(p["moe"], held)}, h2)
            total = total + (y - shared)
            rows += int(counts.sum())
            if chip == 0:   # ... and the layer is x' + that module's output
                layer = la.DecoderLayer(dataclasses.replace(
                    cfg, held_experts=held), 2)
                out, _ = layer.apply(
                    {"params": dict(p, moe=share_of(p["moe"], held))}, x)
                np.testing.assert_allclose(out, mid + y, rtol=1e-5,
                                           atol=1e-5)
        tokens = x.shape[0] * x.shape[1]
        assert rows == tokens * cfg.num_experts_per_tok    # every pair, once
        assert float(jnp.max(jnp.abs(total - uncut))) < 2e-5 * float(
            jnp.max(jnp.abs(uncut)))
        # the dense layer: whatever is held
        p0, spec0 = params["layers_0"], spec_of(model.cfg)
        want = jax.vmap(lambda s: REF.layer(p0, s, spec0, 0))(x)
        for held in ((0, 1), (7,)):
            out, counts = la.DecoderLayer(dataclasses.replace(
                model.cfg, held_experts=held), 0).apply({"params": p0}, x)
            np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-5)
            assert not counts.any()

    def test_capacity_of_the_cells_share(self):
        # 16,384 tokens, 8 of 256 a token, 8 held: 4,096 pairs on average
        assert moe.expert_capacity(16384, 8, 8, 256) == 6144


def count(tree):
    return sum(math.prod(s.shape) for s in jax.tree.leaves(tree))


class TestRegistryAndScopes:
    def test_the_chips_share_of_the_published_model_is_390_million(self):
        with open(CONFIG) as f:
            config = json.load(f)
        model, example = create_model("laguna_xs2", **config["model_kwargs"])
        shapes = jax.eval_shape(lambda: model.init(
            jax.random.PRNGKey(0), example(2), train=False))["params"]
        assert count(shapes) == config["n_params"] == 389_634_048
        attn = lambda i: count(shapes[f"layers_{i}"]["attn"])
        assert attn(0) == attn(4) == 29_458_432       # 48 heads
        assert attn(1) == attn(2) == attn(3) == 37_879_808    # 64 heads
        assert count(shapes["layers_0"]["ffn"]) == 50_331_648
        assert count(shapes["layers_1"]["moe"]) == 524_288 + 9 * 3_145_728
        assert [count(shapes[f"layers_{i}"]) for i in range(5)] == [
            79_794_176, 66_719_744, 66_719_744, 66_719_744, 58_298_368]
        assert count(shapes["embed"]) + count(shapes["lm_head"]) + count(
            shapes["norm"]) == 51_382_272
        names = {str(p[-1].key) for p, _ in
                 jax.tree_util.tree_flatten_with_path(shapes)[0]}
        assert names == {"kernel", "embedding", "scale", "experts"}

    def test_the_configuration_keeps_every_published_width(self):
        with open(CONFIG) as f:
            config = json.load(f)
        cfg = la.LagunaConfig()
        same = [f.name for f in dataclasses.fields(cfg) if f.name in config
                and f.name not in config["reduced"]]
        assert len(same) >= 14
        for k in same:
            value = getattr(cfg, k)
            assert config[k] == (list(value) if isinstance(value, tuple)
                                 else value), k
        for k in config["reduced"]:
            assert config["published"][k] == getattr(cfg, k), k
        ropes = config["rope_parameters"]
        for kind, rope in ((la.FULL, cfg.rope_full),
                           (la.SLIDING, cfg.rope_sliding)):
            for k, v in ropes[kind].items():
                assert getattr(rope, k) == v, (kind, k)
        assert config["partial_rotary_factor"] == (
            cfg.rope_full.partial_rotary_factor)
        assert config["num_attention_heads"] == (
            cfg.num_attention_heads_per_layer[0])
        spec, layers = config["spec"], config["num_hidden_layers"]
        assert spec["num_experts"] == cfg.num_experts
        assert spec["rope_parameters"] == {
            k: ropes[k] for k in (la.FULL, la.SLIDING)}
        for k in ("layer_types", "mlp_layer_types",
                  "num_attention_heads_per_layer"):
            assert spec[k] == config[k][:layers], k
        assert spec["layer_types"] == [la.FULL] + [la.SLIDING] * 3 + [la.FULL]
        assert spec["num_attention_heads_per_layer"] == [48, 64, 64, 64, 48]

    def test_token_models_share_one_example_shape_rule(self):
        assert TOKEN_LMS["laguna_xs2"] == (16384, 100352)
        _, example = create_model("laguna_tiny")
        assert example(3).shape == (3, TOKEN_LMS["laguna_tiny"][0])

    @pytest.mark.parametrize("held", [(), (0, 0), (16,), (-1,)])
    def test_held_experts_have_to_exist(self, held):
        with pytest.raises(ValueError):
            la.LagunaConfig.tiny(held_experts=held)

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
                  for sub in ("attention", "full_scores", "window_attention",
                              "window_scores", "attn_gate", "router",
                              "experts", "shared", "mlp", "head")}
        for sub, mine in by_sub.items():
            assert mine, sub
            assert any("transpose" not in p for p in mine), sub  # forward
            assert any("transpose" in p for p in mine), sub      # backward
        # recomputed: the layer's remat runs the gate's projection again
        # (a forward operation's copy bears the forward one's name): two
        # products a layer give [4, 64, heads] in the gradient's program
        jaxpr = str(jax.make_jaxpr(jax.grad(loss))(params))
        assert jaxpr.count("f32[4,64,12] = dot_general") == 2 * 2
        assert jaxpr.count("f32[4,64,16] = dot_general") == 2 * 3
        # a full layer's scores lie inside attention, a sliding layer's
        # inside window_attention, the gate inside either
        assert all("/attention/" in p and "window" not in p
                   for p in by_sub["full_scores"])
        assert all("/window_attention/" in p for p in by_sub["window_scores"])
        gates = by_sub["attn_gate"]
        assert any("/attention/" in p for p in gates) and any(
            "/window_attention/" in p for p in gates)
        assert {re.search(r"layers_(\d)", p).group(1)
                for p in by_sub["full_scores"]} == {"0", "4"}
        assert {re.search(r"layers_(\d)", p).group(1)
                for p in by_sub["mlp"]} == {"0"}
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
        cfg = TrainConfig(dnn="laguna_tiny", dataset="ptb",
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
        # the share routed to 4 held experts of 16; four sparse layers
        assert 0 < c["expert_rows_max"] <= 4 * 128
        assert c["expert_rows_max"] <= c["expert_rows"] <= 4 * 4 * 128 * 4


class TestBenchmarkCounts:
    """benchmark/benchlib/kernels_mixed_gqa.py: pairs, operations and bytes
    by layer behind ``full_scores_roofline`` and
    ``sliding_scores_roofline``."""

    @pytest.mark.parametrize("t, w", [(64, 16), (64, 64), (64, 100), (5, 1),
                                      (16384, 512)])
    def test_the_bands_and_the_triangles_pairs(self, t, w):
        i = np.arange(t, dtype=np.int64)
        assert kernels_mixed_gqa.band_pairs(t, w) == int(
            np.minimum(i + 1, w).sum())
        assert kernels_mixed_gqa.triangle_pairs(t) == int((i + 1).sum())

    def test_counted_from_the_published_widths(self):
        with open(CONFIG) as f:
            config = json.load(f)
        k = kernels_mixed_gqa
        assert k.layers_of(config, k.FULL) == [(0, 48), (4, 48)]
        assert k.layers_of(config, k.SLIDING) == [(1, 64), (2, 64), (3, 64)]
        assert k.pairs_a_head(config, k.FULL) == 134_225_920
        assert k.pairs_a_head(config, k.SLIDING) == 8_257_792
        # pairs x the kind's heads x (q.k + p v) of 128; forward + backward
        # at twice a forward
        assert k.scores_flops_a_step(config, k.FULL, 1) == (
            134_225_920 * 96 * 4 * 128 * 3)
        assert k.scores_flops_a_step(config, k.SLIDING, 1) == (
            8_257_792 * 192 * 4 * 128 * 3)
        # q and the output of a layer's heads, k and v of 8; float32
        assert k.scores_bytes_a_step(config, k.FULL, 1) == (
            16384 * 2 * 128 * (2 * 56) * 4 * 3)
        assert k.scores_bytes_a_step(config, k.SLIDING, 1) == (
            16384 * 2 * 128 * (3 * 72) * 4 * 3)
        least, bound = k.scores_roofline_seconds(config, k.FULL, 1,
                                                 "TPU v5 lite")
        assert bound == "compute" and 100e-3 < least < 101e-3
        # the band is 6 % of a triangle: its bytes bound it, not its
        # operations (12.4 ms of products beside 13.3 ms of traffic)
        least, bound = k.scores_roofline_seconds(config, k.SLIDING, 1,
                                                 "TPU v5 lite")
        assert bound == "memory" and 13e-3 < least < 14e-3

    def test_the_readers_take_the_innermost_sub_scope(self):
        subs = anatomy.SUB_SCOPES["fwd_bwd"]
        base = "jit(shard_fn)/anat/fwd_bwd/"
        for path, want in [
            (base + "jvp(Laguna)/layers_0/anat/fwd_bwd/attention/attn/"
             "q_proj/dot_general", "attention"),
            (base + "transpose(jvp(Laguna))/layers_4/anat/fwd_bwd/attention/"
             "attn/anat/fwd_bwd/full_scores/checkpoint/dot_general",
             "full_scores"),
            (base + "jvp(Laguna)/layers_1/anat/fwd_bwd/window_attention/"
             "attn/anat/fwd_bwd/attn_gate/g_proj/dot_general", "attn_gate"),
            (base + "jvp(Laguna)/layers_1/anat/fwd_bwd/window_attention/"
             "attn/anat/fwd_bwd/window_scores/mul", "window_scores")]:
            assert kernels_lm.sub_of(path, subs) == want, path
