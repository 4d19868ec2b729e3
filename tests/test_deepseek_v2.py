"""DeepSeek-V2 (models/deepseek_v2.py) against its plain reference
(benchmark/reference/deepseek_v2.py) at ``deepseek_v2_tiny``, on seeded
weights made by the benchmark's own rules (benchlib/weights.py): loss and
every gradient leaf, the share cut of expert parallelism, no dropped token
at any imbalance, blocked attention, the YaRN rotary, the model's counters,
and three steps through the ``Trainer``.
"""

import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))

from benchlib import discover, weights  # noqa: E402

from oktopk_tpu.config import TrainConfig  # noqa: E402
from oktopk_tpu.models import attention, layers, moe  # noqa: E402
from oktopk_tpu.models import create_model  # noqa: E402
from oktopk_tpu.models import deepseek_v2 as ds  # noqa: E402
from oktopk_tpu.models.registry import TOKEN_LMS  # noqa: E402
from oktopk_tpu.obs import anatomy  # noqa: E402
from oktopk_tpu.train.trainer import Trainer  # noqa: E402

REF = discover.load_module(
    os.path.join(ROOT, "benchmark", "reference", "deepseek_v2.py"))

# float32 on the CPU: program and reference do the same products and
# differ by the order of float32 sums alone (1e-6 a leaf, 2e-6 the worst
# read here); bfloat16 compute reads 1e-2. Ten times the sound reading.
LOSS_TOL, GRAD_TOL = 2e-6, 2e-5
HELD = (1, 2, 5, 6)
ROPE1 = dict(theta=100.0, factor=1.0, original_max_position_embeddings=8,
             beta_fast=32, beta_slow=1, mscale=1.0, mscale_all_dim=1.0)


def spec_of(cfg, held=None):
    """The reference's ``spec`` for a model configuration."""
    return dict(
        num_hidden_layers=cfg.num_hidden_layers,
        first_k_dense_replace=cfg.first_k_dense_replace,
        num_attention_heads=cfg.num_attention_heads,
        qk_nope_head_dim=cfg.qk_nope_head_dim,
        qk_rope_head_dim=cfg.qk_rope_head_dim, v_head_dim=cfg.v_head_dim,
        kv_lora_rank=cfg.kv_lora_rank, rms_norm_eps=cfg.rms_norm_eps,
        num_experts_per_tok=cfg.num_experts_per_tok,
        held_experts=list(cfg.held_experts if held is None else held),
        rope=dict(theta=cfg.rope_theta, factor=cfg.rope_factor,
                  original_max_position_embeddings=(
                      cfg.rope_original_max_position),
                  beta_fast=cfg.rope_beta_fast, beta_slow=cfg.rope_beta_slow,
                  mscale=cfg.rope_mscale,
                  mscale_all_dim=cfg.rope_mscale_all_dim))


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


def gradient_jaxpr(model, params, batch):
    """The gradient's program as JAX hands it to XLA, as text."""
    return str(jax.make_jaxpr(
        lambda p: program_loss(model, batch).__wrapped__(p)[1])(params))


def forget_the_attention_output(monkeypatch):
    """The parent's form, for a comparison: every ``nn.remat`` that
    ``models/deepseek_v2.py`` makes from here on has no policy, so a layer
    keeps its input alone."""
    remat = ds.nn.remat
    monkeypatch.setattr(ds.nn, "remat", lambda cls, policy=None: remat(cls))


def leaf_gaps(prog, ref):
    flat = jax.tree_util.tree_flatten_with_path(prog)[0]
    return {jax.tree_util.keystr(path): float(
        jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-30))
        for (path, a), b in zip(flat, jax.tree.leaves(ref))}


@pytest.fixture(scope="module")
def tiny():
    # 256 tokens, 2 of 8 experts a token, 4 held: a buffer of 384 rows for
    # 256 pairs on average and 512 at most, so the grouped branch AND the
    # all-rows branch are both in the program
    model, example = create_model("deepseek_v2_tiny", held_experts=HELD)
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
        assert len(gaps) == 51 and max(gaps.values()) < GRAD_TOL, gaps

    def test_bfloat16_compute_fails_the_tolerances(self, tiny):
        _, params, batch, (ref_loss, ref_grads) = tiny
        model, _ = create_model("deepseek_v2_tiny", held_experts=HELD,
                                dtype=jnp.bfloat16)
        (loss, _), grads = program_loss(model, batch)(params)
        gaps = leaf_gaps(grads, ref_grads)
        assert abs(loss - ref_loss) / abs(ref_loss) > LOSS_TOL
        assert min(gaps.values()) > GRAD_TOL

    def test_what_the_backward_pass_computes_again(self, tiny):
        """The grouped products of an expert layer are in the gradient's
        program twelve times: three forward, three recomputed by the
        branch's own ``jax.checkpoint`` and six backward. The layer's
        recomputation (``nn.remat``) makes none: nothing in the layer's
        backward pass needs the experts' output. A change of what is
        recomputed moves this count, and with it what
        ``benchmark/configs/deepseek_v2_lite_ep8.json`` says under
        ``recompute``."""
        model, params, batch, _ = tiny
        text = gradient_jaxpr(model, params, batch)
        expert_layers = (model.cfg.num_hidden_layers
                         - model.cfg.first_k_dense_replace)
        assert text.count("ragged_dot_general[") == 12 * expert_layers

    @pytest.mark.parametrize("kept, passes", [(True, 2), (False, 3)])
    def test_passes_over_a_blocks_scores(self, tiny, monkeypatch, kept,
                                         passes):
        """A block's scores, mask and softmax are in the gradient's program
        twice a layer before their backward pass: the forward pass's and
        the block's own ``jax.checkpoint``. The layer's recomputation
        starts from the kept attention output (``attention.ATTN_OUT``) and runs
        none; with the layer's remat given no policy (built here, no
        option in the package) it runs a third. The experts' twelve
        grouped products a layer are the same either way."""
        model, params, batch, _ = tiny
        if not kept:
            forget_the_attention_output(monkeypatch)
        text = gradient_jaxpr(model, params, batch)
        cfg, t = model.cfg, batch["tokens"].shape[1]
        for end in range(cfg.attn_block, t + 1, cfg.attn_block):
            scores = (f"f32[{cfg.num_attention_heads},{cfg.attn_block},"
                      f"{end}] = exp ")
            assert text.count(scores) == passes * cfg.num_hidden_layers, end
        expert_layers = cfg.num_hidden_layers - cfg.first_k_dense_replace
        assert text.count("ragged_dot_general[") == 12 * expert_layers

    def test_the_kept_attention_output_changes_no_bit(self, tiny,
                                                      monkeypatch):
        """The kept array holds the values the recomputation would have
        made: loss and every gradient leaf are bit-equal to the same model
        with the layer's remat given no policy."""
        model, params, batch, _ = tiny
        (loss, rows), grads = program_loss(model, batch)(params)
        forget_the_attention_output(monkeypatch)
        (loss0, rows0), grads0 = program_loss(model, batch)(params)
        assert float(loss) == float(loss0)
        assert np.array_equal(np.asarray(rows), np.asarray(rows0))
        flat = jax.tree_util.tree_flatten_with_path(grads)[0]
        assert len(flat) == 51
        for (path, a), b in zip(flat, jax.tree.leaves(grads0)):
            assert np.array_equal(np.asarray(a), np.asarray(b)), path

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
            p = params[f"layers_{i}"]
            dense = i < cfg.first_k_dense_replace
            if not dense:
                # the layer's router input: after attention, normed
                h = jax.vmap(lambda s: REF._rms_norm(
                    s + REF._attention(p["attn"], REF._rms_norm(
                        s, p["attn_norm"]["scale"], cfg.rms_norm_eps), spec),
                    p["ffn_norm"]["scale"], cfg.rms_norm_eps))(x)
                w = REF.routing(h.reshape(-1, h.shape[-1]),
                                p["moe"]["kernel"], spec)
                want.append(np.asarray(jnp.sum(w > 0, axis=0))[list(HELD)])
            x = jax.vmap(lambda s: REF._layer(p, s, spec, dense))(x)
        assert np.array_equal(np.asarray(rows), np.stack(want))
        assert rows.shape == (2, len(HELD)) and int(rows.sum()) > 0


class TestShare:
    def test_eight_shares_and_the_shared_expert_once_make_the_uncut_layer(
            self):
        """Expert parallelism's cut (guide, section 4): eight chips hold one
        expert each; their routed parts, with the shared expert counted
        once, add up to the reference's uncut layer."""
        cfg = ds.DeepseekV2Config.tiny()
        d, f, e = cfg.hidden_size, cfg.moe_intermediate_size, 8
        key = jax.random.PRNGKey(3)
        h = jax.random.normal(key, (2, 48, d))
        full = weights.make_params({
            "kernel": jax.ShapeDtypeStruct((d, e), jnp.float32),
            "routed_gate": {"experts": jax.ShapeDtypeStruct((e, d, f),
                                                            jnp.float32)},
            "routed_up": {"experts": jax.ShapeDtypeStruct((e, d, f),
                                                          jnp.float32)},
            "routed_down": {"experts": jax.ShapeDtypeStruct((e, f, d),
                                                            jnp.float32)},
            "shared_ffn": {n: {"kernel": jax.ShapeDtypeStruct(s, jnp.float32)}
                           for n, s in (("gate_proj", (d, 2 * f)),
                                        ("up_proj", (d, 2 * f)),
                                        ("down_proj", (2 * f, d)))}}, 11)
        uncut = REF._experts(full, h.reshape(-1, d),
                             spec_of(cfg, held=range(e)))
        shared = layers.swiglu(h.reshape(-1, d), *(
            full["shared_ffn"][n]["kernel"]
            for n in ("gate_proj", "up_proj", "down_proj")))
        total, rows = shared, 0
        for chip in range(e):
            layer = moe.MoE(e, (chip,), cfg.num_experts_per_tok, f, 0, 1.0,
                            False, jnp.float32)
            share = {k: ({"experts": v["experts"][chip:chip + 1]}
                         if k.startswith("routed") else v)
                     for k, v in full.items() if k != "shared_ffn"}
            y, counts = layer.apply({"params": share}, h)
            total = total + y.reshape(-1, d)
            rows += int(counts.sum())
        assert rows == 2 * 48 * cfg.num_experts_per_tok  # every pair, once
        assert float(jnp.max(jnp.abs(total - uncut))) < 1e-5 * float(
            jnp.max(jnp.abs(uncut)))


class TestNoDroppedToken:
    @pytest.mark.parametrize("capacity,k,branch", [
        (128, 2, "all rows"), (256, 2, "grouped, full"),
        (384, 2, "grouped, 128 rows of padding"),
        (256, 1, "grouped, the only branch")])
    def test_every_token_to_one_held_expert(self, capacity, k, branch):
        """The router sends all 256 tokens to held expert 0 (and none to the
        other three): whatever the buffer, all 256 are computed."""
        t, d, f, held = 256, 32, 16, 4
        ks = jax.random.split(jax.random.PRNGKey(5), 5)
        x = jax.random.normal(ks[0], (t, d))
        wg, wu = (jax.random.normal(k, (held, d, f)) / math.sqrt(d)
                  for k in ks[1:3])
        wd = jax.random.normal(ks[3], (held, f, d)) / math.sqrt(f)
        routed = jnp.zeros((t, held), bool).at[:, 0].set(True)
        weights_ = jnp.where(routed, jax.random.uniform(ks[4], (t, held)), 0.)

        def run(x, wg, wu, wd):
            y, counts = moe.routed_experts(x, weights_, routed, wg, wu, wd,
                                           capacity, k)
            return jnp.sum(y * y), (y, counts)

        def plain(x, wg, wu, wd):
            y = layers.swiglu(x, wg[0], wu[0], wd[0]) * weights_[:, :1]
            return jnp.sum(y * y), y

        (_, (y, counts)), grads = jax.value_and_grad(
            run, argnums=(0, 1, 2, 3), has_aux=True)(x, wg, wu, wd)
        (_, want), want_grads = jax.value_and_grad(
            plain, argnums=(0, 1, 2, 3), has_aux=True)(x, wg, wu, wd)
        assert counts.tolist() == [t, 0, 0, 0]
        np.testing.assert_allclose(y, want, rtol=1e-5, atol=1e-6)
        for g, w in zip(grads, want_grads):
            np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-5)

    def test_capacity_from_the_mean_number_of_pairs(self):
        # 16,384 tokens, 6 of 64 a token, 8 held: 12,288 pairs on average
        assert moe.expert_capacity(16384, 8, 6, 64) == 18432
        assert moe.expert_capacity(256, 4, 2, 8) == 384
        # never more rows than pairs there can be (256 tokens x 2 a token)
        assert moe.expert_capacity(256, 8, 2, 8) == 512

    @pytest.mark.parametrize("share", [0.1, 0.5, 0.75])
    def test_grouped_products_cover_the_buffer_at_any_routing(
            self, share, monkeypatch):
        """The buffer's empty rows lie in the last expert's group: the
        groups of every product add up to the buffer, so a step's work does
        not follow the number of pairs its routing made."""
        t, d, f, held, rows = 256, 32, 16, 4, 384
        ks = jax.random.split(jax.random.PRNGKey(7), 2)
        x = jax.random.normal(ks[0], (t, d))
        routed = jax.random.uniform(ks[1], (t, held)) < share / 2
        counts = jnp.sum(routed, 0, dtype=jnp.int32)
        assert 0 < int(counts.sum()) < rows
        seen, plain = [], jax.lax.ragged_dot

        def spy(lhs, rhs, group_sizes, **kw):
            seen.append(group_sizes)
            return plain(lhs, rhs, group_sizes, **kw)

        monkeypatch.setattr(moe.lax, "ragged_dot", spy)
        moe._grouped_branch(rows, x, routed.astype(x.dtype), routed, counts,
                            jnp.ones((held, d, f)), jnp.ones((held, d, f)),
                            jnp.ones((held, f, d)))
        assert len(seen) == 3
        for groups in seen:
            assert int(groups.sum()) == rows
            assert groups[:-1].tolist() == counts[:-1].tolist()

    def test_which_branches_a_program_holds(self):
        """A ``cond`` only where a step can bring more pairs than the
        buffer holds."""
        t, d, f, held = 256, 32, 16, 4
        x, w = jnp.zeros((t, d)), jnp.zeros((t, held))
        wg = wu = jnp.zeros((held, d, f))
        wd = jnp.zeros((held, f, d))

        def conds(capacity, k):
            return str(jax.make_jaxpr(lambda: moe.routed_experts(
                x, w, w > 0, wg, wu, wd, capacity, k))()).count("cond[")
        assert conds(384, 2) == 1 and conds(512, 2) == 0

    @pytest.mark.parametrize("held", [(), (0, 0), (8,), (-1,)])
    def test_held_experts_have_to_exist(self, held):
        with pytest.raises(ValueError):
            ds.DeepseekV2Config.tiny(held_experts=held)


class TestAttentionAndRotary:
    @pytest.mark.parametrize("block", [16, 24, 64])
    def test_blocked_attention_is_the_full_causal_softmax(self, block):
        b, t, h, dn, dr, dv = 2, 64, 3, 8, 4, 8
        ks = jax.random.split(jax.random.PRNGKey(1), 5)
        qn, kn = (jax.random.normal(k, (b, t, h, dn)) for k in ks[:2])
        qp = jax.random.normal(ks[2], (b, t, h, dr))
        kp = jax.random.normal(ks[3], (b, t, dr))
        v = jax.random.normal(ks[4], (b, t, h, dv))
        got = attention.blocked_causal_attention(qn, qp, kn, kp, v, 0.3, block)
        q = jnp.concatenate([qn, qp], -1)
        k = jnp.concatenate(
            [kn, jnp.broadcast_to(kp[:, :, None], (b, t, h, dr))], -1)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * 0.3
        s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
        want = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)

    def test_yarn_frequencies_are_the_written_formula(self):
        dim, theta, factor, orig, fast, slow = 64, 10000.0, 40.0, 4096, 32, 1
        got = attention.yarn_inv_freq(dim, theta, factor, orig, fast, slow)

        def dim_of(rot):
            return dim * math.log(orig / (rot * 2 * math.pi)) / (
                2 * math.log(theta))
        low, high = math.floor(dim_of(fast)), math.ceil(dim_of(slow))
        assert (low, high) == (10, 23)      # DeepSeek-V2-Lite's own
        for i, f in enumerate(got):
            base = theta ** (-2 * i / dim)
            ramp = min(1.0, max(0.0, (i - low) / (high - low)))
            assert f == pytest.approx(base / factor * ramp
                                      + base * (1 - ramp), rel=1e-6)
        assert got[0] == pytest.approx(1.0) and got[-1] == pytest.approx(
            theta ** (-62 / 64) / 40, rel=1e-6)
        # cos/sin scale 1 (mscale == mscale_all_dim); softmax scale m^2
        assert attention.yarn_mscale(40, 0.707) == pytest.approx(
            0.1 * 0.707 * math.log(40) + 1)

    def test_rotation_turns_adjacent_pairs(self):
        t, dim = 5, 8
        x = jax.random.normal(jax.random.PRNGKey(2), (t, 1, dim))
        ang = (jnp.arange(t)[:, None] * jnp.asarray([1.0, .5, .25, .125]))
        got = attention.rotate_pairs(x, jnp.cos(ang), jnp.sin(ang))
        z = (x[..., 0::2] + 1j * x[..., 1::2]) * jnp.exp(1j * ang)[:, None]
        np.testing.assert_allclose(got[..., 0::2], z.real, atol=1e-6)
        np.testing.assert_allclose(got[..., 1::2], z.imag, atol=1e-6)
        # ... as the reference's own rotary does (no scaling: factor 1)
        np.testing.assert_allclose(
            attention.rotate_pairs(x, *(
                f(jnp.arange(t)[:, None] * REF._inv_freq(dim, ROPE1))
                for f in (jnp.cos, jnp.sin))),
            REF._rotary(x, ROPE1), atol=1e-6)


class TestRegistryAndScopes:
    def test_the_chips_share_of_the_published_model_is_535_million(self):
        model, example = create_model(
            "deepseek_v2_lite", num_hidden_layers=5, vocab_size=12800,
            held_experts=list(range(8)))
        shapes = jax.eval_shape(lambda: model.init(
            jax.random.PRNGKey(0), example(2), train=False))["params"]
        assert sum(math.prod(s.shape)
                   for s in jax.tree.leaves(shapes)) == 535_060_992
        names = {str(p[-1].key) for p, _ in
                 jax.tree_util.tree_flatten_with_path(shapes)[0]}
        assert names == {"kernel", "embedding", "scale", "experts"}

    def test_token_models_share_one_example_shape_rule(self):
        assert TOKEN_LMS["lstm"] == (35, 10000)
        assert TOKEN_LMS["lstm_tiny"] == (35, 1024)
        for dnn in ("lstm_tiny", "deepseek_v2_tiny"):
            _, example = create_model(dnn)
            assert example(3).shape == (3, TOKEN_LMS[dnn][0])

    def test_forward_and_backward_ops_carry_the_sub_scopes(self, tiny):
        model, params, batch, _ = tiny

        def loss(p):
            with anatomy.phase_scope("fwd_bwd"):
                return program_loss(model, batch).__wrapped__(p)[0][0]
        text = jax.jit(jax.grad(loss)).lower(params).compile().as_text()
        import re
        paths = set(re.findall(r'op_name="([^"]*)"', text))
        # the sub-scopes this model enters (``linear_attention`` and
        # ``delta_rule`` are models/qwen3_next.py's)
        entered = ("attention", "router", "experts", "shared", "mlp", "head")
        assert set(entered) <= set(anatomy.SUB_SCOPES["fwd_bwd"])
        for sub in entered:
            mine = [p for p in paths if f"anat/fwd_bwd/{sub}" in p]
            assert mine, sub
            assert any("transpose" in p for p in mine), sub  # backward too
        # no flax module is named like a sub-scope
        assert not any(k in anatomy.SUB_SCOPES["fwd_bwd"]
                       for k in jax.tree_util.tree_flatten_with_path(
                           params)[0][0][0])


def run_steps(trainer, steps, seed=0):
    workers = trainer.algo_cfg.num_workers
    losses, m = [], None
    for i in range(steps):
        b = batch_of(seqs=2 * workers, seed=seed)      # one batch, learnt
        m = trainer.train_step({k: jnp.asarray(v) for k, v in b.items()})
        losses.append(float(m["loss"]))
    return losses, m


class TestTrainer:
    @pytest.mark.parametrize("compressor", ["dense", "oktopk"])
    def test_three_steps_on_four_workers(self, mesh4, compressor):
        cfg = TrainConfig(dnn="deepseek_v2_tiny", dataset="ptb",
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
        # 4 workers x 2 sequences x 64 tokens x 2 experts a token, of which
        # the share routed to 4 held experts of 8; two expert layers
        assert 0 < c["expert_rows_max"] <= 4 * 128
        assert c["expert_rows_max"] <= c["expert_rows"] <= 4 * 2 * 128 * 2

    def test_lstm_reports_no_expert_rows(self, mesh4):
        cfg = TrainConfig(dnn="lstm_tiny", dataset="ptb", batch_size=2,
                          lr=1.0, compressor="dense")
        tr = Trainer(cfg, mesh=mesh4, warmup=False)
        b = batch_of(seqs=8, t=35, vocab=1024)
        m = tr.train_step({k: jnp.asarray(v) for k, v in b.items()})
        assert np.asarray(m["counters"])[-2:].tolist() == [0, 0]


class TestBenchmarkReaders:
    """benchmark/benchlib/kernels_lm.py: the scope reader and the counts
    behind the two matrix-peak shares."""

    def test_sub_scope_of_forward_recomputed_and_backward_paths(self):
        from benchlib import kernels_lm
        subs = anatomy.SUB_SCOPES["fwd_bwd"]
        base = "jit(shard_fn)/anat/fwd_bwd/"
        for path, want in [
            (base + "jvp(anat/fwd_bwd/experts)/sin", "experts"),
            (base + "transpose(jvp(DeepseekV2))/anat/fwd_bwd/jvp(DeepseekV2)"
             "/checkpoint/layers_4/moe/anat/fwd_bwd/router/dot_general",
             "router"),
            (base + "transpose(jvp(anat/fwd_bwd/attention))/mul",
             "attention"),
            (base + "jvp(DeepseekV2)/layers_1/moe/anat/fwd_bwd/shared/"
             "shared_ffn/dot_general", "shared"),
            (base + "jvp(DeepseekV2)/embed/gather", None),
            ("jit(shard_fn)/anat/optimizer/add", None),
            ("ragged-dot-none", None)]:
            assert kernels_lm.sub_of(path, subs) == want, path

    def test_counted_operations_of_the_published_widths(self):
        import json
        from benchlib import kernels_lm
        with open(os.path.join(ROOT, "benchmark", "configs",
                               "deepseek_v2_lite_ep8.json")) as f:
            config = json.load(f)
        # 49,152 pairs x 3 products x 2 x 2048 x 1408; forward + backward
        assert kernels_lm.expert_flops_a_step(config, 49152) == (
            49152 * 6 * 2048 * 1408 * 3)
        a = kernels_lm.attention_flops_a_step(config, 4)
        proj = 2 * (2048 * 3072 + 2048 * 576 + 512 * 4096 + 2048 * 2048)
        scores = 2 * 16 * (4096 * 4097 // 2) * 320
        assert a == 5 * 4 * (4096 * proj + scores) * 3
        assert 11e12 < a < 12e12      # 61 ms at the chip's matrix peak


class TestNewArgumentsAtTheirDefaults:
    """``MoE`` gained ``router_input`` and ``hidden_act`` for
    ``models/smallthinker.py``: at their defaults the model is the parent
    commit's, bit for bit."""

    def test_loss_and_gradients_hash_to_the_parents(self, tiny):
        """``deepseek_v2_tiny``'s loss, counters and every gradient leaf on
        seeded weights hash to what the parent commit's code gave
        (recorded from a checkout of commit d8fca06 by these same
        lines)."""
        import hashlib
        model, params, batch, _ = tiny
        (loss, rows), grads = program_loss(model, batch)(params)
        digest = hashlib.sha256()
        for x in [loss, rows] + jax.tree.leaves(grads):
            digest.update(np.asarray(x).tobytes())
        assert digest.hexdigest() == AT_PARENT

    def test_the_defaults_spelled_out_change_no_bit(self):
        cfg = ds.DeepseekV2Config.tiny()
        args = (cfg.n_routed_experts, (1, 2, 5), cfg.num_experts_per_tok,
                cfg.moe_intermediate_size, 2, 1.0, False)
        h = jax.random.normal(jax.random.PRNGKey(3), (2, 48, cfg.hidden_size))
        plain = moe.MoE(*args)
        params = plain.init(jax.random.PRNGKey(1), h)
        y, rows = plain.apply(params, h)
        spelled = moe.MoE(*args, hidden_act="silu")
        y2, rows2 = spelled.apply(params, h, router_input=h)
        assert np.array_equal(y, y2) and np.array_equal(rows, rows2)
        # ... and each of them is read: another gate, another input of the
        # router
        relu, _ = moe.MoE(*args, hidden_act="relu").apply(params, h)
        _, moved = plain.apply(params, h, router_input=2.0 * h - 1.0)
        assert not np.array_equal(y, relu) and not np.array_equal(rows, moved)


AT_PARENT = (
    "acc2ecf97eb274dabae8bbcd425e53ac751132a6385341bdd2efcb85192da3ce")
