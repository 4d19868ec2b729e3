"""Ouro (models/ouro.py) against its plain reference
(benchmark/reference/ouro.py) at ``ouro_tiny``, on seeded weights made by
the benchmark's own rules (benchlib/weights.py): loss, every gradient leaf
and three SGD steps; ONE set of parameters under R passes (the tree, the
published n, a shared weight's gradient as the sum over R unshared copies);
the exits (one pass is one plain cross-entropy, p sums to 1, the blocked
loss, the fed-back norm, beta); each broken path the chip's check has to
catch; the token-LM rung of the ``Trainer`` (a model with its own loss, the
four that hand back logits bit for bit, ``eval_step``); the counters, the
sub-scopes and the benchmark's counts.
"""

import contextlib
import dataclasses
import json
import os
import re
import sys

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))

from benchlib import discover, kernels_lm, kernels_loop, weights  # noqa: E402

from oktopk_tpu.collectives.state import COUNTERS, MODEL_COUNTERS  # noqa: E402
from oktopk_tpu.config import TrainConfig  # noqa: E402
from oktopk_tpu.models import attention, layers  # noqa: E402
from oktopk_tpu.models import create_model  # noqa: E402
from oktopk_tpu.models import ouro  # noqa: E402
from oktopk_tpu.models.registry import TOKEN_LMS  # noqa: E402
from oktopk_tpu.obs import anatomy  # noqa: E402
from oktopk_tpu.train import losses  # noqa: E402
from oktopk_tpu.train.trainer import Trainer  # noqa: E402

REF = discover.load_module(
    os.path.join(ROOT, "benchmark", "reference", "ouro.py"))
CONFIG = os.path.join(ROOT, "benchmark", "configs", "ouro_2_6b_l6.json")

# float32 on the CPU: program and reference differ by the order of float32
# sums (7e-6 the worst gradient leaf read here, the gate's bias, whose
# gradient is a sum over every token of terms of both signs; under 1e-7 the
# loss); bfloat16 compute reads 1e-2 at its best leaf and 1e-4 in the loss.
# About five times the sound reading.
LOSS_TOL, GRAD_TOL = 2e-6, 4e-5


def spec_of(cfg, **over):
    """The reference's ``spec`` for a model configuration."""
    return dict(
        num_hidden_layers=cfg.num_hidden_layers,
        total_ut_steps=cfg.total_ut_steps,
        num_attention_heads=cfg.num_attention_heads,
        num_key_value_heads=cfg.num_key_value_heads, head_dim=cfg.head_dim,
        rope_theta=cfg.rope_theta, rms_norm_eps=cfg.rms_norm_eps,
        entropy_beta=cfg.entropy_beta, attn_block=24, mlp_block=32,
        head_block=40, **over)


def shapes_of(model, example):
    tokens = example(2)
    return jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), tokens, tokens, train=False))["params"]


def seeded(model, example, seed=7):
    return weights.make_params(shapes_of(model, example), seed)


def batch_of(seqs=4, t=64, vocab=512, seed=0):
    toks = np.random.default_rng(seed).integers(
        0, vocab, size=(seqs, t + 1)).astype(np.int32)
    return {"tokens": toks[:, :-1], "targets": toks[:, 1:]}


def program_loss(model, batch):
    return jax.jit(jax.value_and_grad(lambda p: model.apply(
        {"params": p}, batch["tokens"], batch["targets"])[0]))


def reference_loss(model, batch, **over):
    return jax.jit(jax.value_and_grad(
        lambda p: REF.loss(p, batch, spec_of(model.cfg, **over))))


def leaf_gaps(prog, ref):
    flat = jax.tree_util.tree_flatten_with_path(prog)[0]
    return {jax.tree_util.keystr(path): float(
        jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-30))
        for (path, a), b in zip(flat, jax.tree.leaves(ref))}


def count(tree):
    return sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree))


@pytest.fixture(scope="module")
def tiny():
    # four passes over three layers, 4 x 64 = 256 rows in head blocks of 48
    model, example = create_model("ouro_tiny")
    params = seeded(model, example)
    batch = batch_of()
    return (model, params, batch, reference_loss(model, batch),
            program_loss(model, batch))


class TestAgainstReference:
    def test_loss_and_every_gradient_leaf(self, tiny):
        model, params, batch, ref, step = tiny
        ref_loss, ref_grads = ref(params)
        loss, grads = step(params)
        assert abs(loss - ref_loss) / abs(ref_loss) < LOSS_TOL
        gaps = leaf_gaps(grads, ref_grads)
        # embed, 3 x (4 norms, 4 projections, 3 of the SwiGLU), norm, head,
        # the gate's kernel and bias
        assert len(gaps) == 1 + 3 * 11 + 4
        assert max(gaps.values()) < GRAD_TOL, gaps

    def test_three_sgd_steps(self, tiny):
        """Plain SGD at lr 0.1, each side by its own gradients from the
        same start: the losses and the parameters stay together."""
        model, params, batch, ref, step = tiny
        p, r = params, params
        for _ in range(3):
            loss, g = step(p)
            ref_loss, ref_g = ref(r)
            assert abs(loss - ref_loss) / abs(ref_loss) < 5 * LOSS_TOL
            p = jax.tree.map(lambda a, b: a - 0.1 * b, p, g)
            r = jax.tree.map(lambda a, b: a - 0.1 * b, r, ref_g)
        moved = leaf_gaps(jax.tree.map(jnp.subtract, p, params),
                          jax.tree.map(jnp.subtract, r, params))
        assert max(moved.values()) < 10 * GRAD_TOL, moved
        assert float(loss) < float(step(params)[0])

    def test_bfloat16_compute_fails_the_tolerances(self, tiny):
        _, params, batch, ref, _ = tiny
        ref_loss, ref_grads = ref(params)
        model, _ = create_model("ouro_tiny", dtype=jnp.bfloat16)
        loss, grads = program_loss(model, batch)(params)
        gaps = leaf_gaps(grads, ref_grads)
        assert abs(loss - ref_loss) / abs(ref_loss) > LOSS_TOL
        assert min(gaps.values()) > GRAD_TOL

    def test_what_the_model_counts(self, tiny):
        """``exit_step_milli_max`` is the tokens' mean of sum_t t p_t by
        the reference's own distribution."""
        model, params, batch, _, _ = tiny
        _, extra = jax.jit(lambda p: model.apply(
            {"params": p}, batch["tokens"], batch["targets"]))(params)
        _, gate = jax.jit(lambda p: REF.exits(
            p, batch["tokens"], batch["targets"], spec_of(model.cfg)))(params)
        p = REF.exit_distribution(gate)
        np.testing.assert_allclose(extra["exit_p"], p, rtol=1e-4, atol=1e-6)
        want = 1e3 * float(jnp.mean(jnp.tensordot(
            jnp.arange(1.0, 5.0), p, axes=1)))
        assert abs(float(extra["exit_step_milli_max"]) - want) <= 1.0
        assert 1000 < want < 4000


class TestOneSetOfParametersRPasses:
    def test_the_tree_holds_l_layers_whatever_r(self, tiny):
        model, params, _, _, _ = tiny
        assert sorted(params) == ["embed", "gate", "layers_0", "layers_1",
                                  "layers_2", "lm_head", "norm"]
        once, example = create_model("ouro_tiny", total_ut_steps=1)
        assert (jax.tree.map(lambda x: x.shape, shapes_of(once, example))
                == jax.tree.map(lambda x: x.shape, params))
        names = {str(p[-1].key) for p, _ in
                 jax.tree_util.tree_flatten_with_path(params)[0]}
        assert names == {"kernel", "embedding", "scale", "bias"}

    def test_the_published_n_at_the_published_widths(self):
        """By shape arithmetic, no allocation: 48 layers and the cell's 6."""
        layer = 4 * 2048 * 2048 + 3 * 2048 * 5632 + 4 * 2048
        rest = 2 * 49152 * 2048 + 2048 + 2049
        assert layer == 51_388_416
        with open(CONFIG) as f:
            config = json.load(f)
        for kwargs, want in (({}, 48 * layer + rest),
                             (config["model_kwargs"], 6 * layer + rest)):
            model, example = create_model("ouro_2_6b", **kwargs)
            shapes = shapes_of(model, example)
            assert count(shapes) == want
            assert count(shapes["layers_0"]) == layer
        assert want == config["n_params"] == 509_661_185
        assert len(jax.tree.leaves(shapes)) == 6 * 11 + 5
        assert config["published"]["num_hidden_layers"] == 48

    def test_a_shared_weights_gradient_is_the_sum_over_r_unshared_copies(
            self, tiny):
        """The reference with a copy of the stack a pass (the same values)
        gives each copy its own gradient; the shared weight's, in the
        program and in the reference, is their sum."""
        model, params, batch, ref, step = tiny
        layers = {k: v for k, v in params.items() if k.startswith("layers_")}
        rest = {k: v for k, v in params.items() if k not in layers}
        copies = {**rest, **{f"pass_{t}": layers for t in range(4)}}
        loss, grads = reference_loss(model, batch, unshared_passes=True)(
            copies)
        summed = jax.tree.map(lambda *g: sum(g),
                              *(grads[f"pass_{t}"] for t in range(4)))
        # the copies' gradients differ: a sum, not four times one of them
        first = leaf_gaps(grads["pass_0"], grads["pass_3"])
        assert min(first.values()) > 0.1
        ref_loss, ref_grads = ref(params)
        assert abs(loss - ref_loss) / abs(ref_loss) < LOSS_TOL
        _, prog_grads = step(params)
        for got in (ref_grads, prog_grads):
            gaps = leaf_gaps({k: got[k] for k in layers}, summed)
            assert max(gaps.values()) < GRAD_TOL, gaps
            gaps = leaf_gaps({k: got[k] for k in rest},
                             {k: grads[k] for k in rest})
            assert max(gaps.values()) < GRAD_TOL, gaps

    def test_the_compiled_gradient_holds_one_body_of_l_layers(self, tiny):
        """One ``while`` over the passes: each layer's q projection appears
        once forward in the program's text, not once a pass."""
        model, params, batch, _, _ = tiny
        text = str(jax.make_jaxpr(lambda p: model.apply(
            {"params": p}, batch["tokens"], batch["targets"])[0])(params))
        # 3 layers x (q, k, v, o) products of [4, 64, 128] x [128, 128]
        assert text.count("f32[4,64,128] = dot_general") == 3 * 4
        assert "length=4" in text


class TestTheExits:
    def test_one_pass_is_one_plain_cross_entropy(self):
        """R = 1: p = 1, H = 0, the loss is ``lm_cross_entropy`` of the
        one exit's logits, and the gate gets no gradient."""
        model, example = create_model("ouro_tiny", total_ut_steps=1)
        params, batch = seeded(model, example), batch_of()
        loss, grads = program_loss(model, batch)(params)
        spec = spec_of(model.cfg)
        x = params["embed"]["embedding"][batch["tokens"]]
        for p in REF.stack_of(params, spec, 0):
            x = jax.vmap(lambda s: REF.layer(p, s, spec))(x)
        logits = REF._norm(x, params["norm"]["scale"], 1e-6) @ params[
            "lm_head"]["kernel"]
        want = losses.lm_cross_entropy(logits, batch["targets"])
        assert abs(loss - want) / want < LOSS_TOL
        assert float(jnp.abs(grads["gate"]["kernel"]).max()) == 0.0
        assert float(jnp.abs(grads["lm_head"]["kernel"]).max()) > 0.0

    @pytest.mark.parametrize("r", [1, 2, 4, 7])
    def test_p_sums_to_one_and_the_last_exit_takes_the_remainder(self, r):
        gate = 3.0 * jax.random.normal(jax.random.PRNGKey(r), (r, 5, 11))
        nll = jax.random.uniform(jax.random.PRNGKey(1), (r, 5, 11)) + 1.0
        loss, p = ouro.exit_mixture(nll, gate, 0.1)
        np.testing.assert_allclose(p.sum(0), 1.0, rtol=1e-6)
        lam = jax.nn.sigmoid(gate)
        np.testing.assert_allclose(
            p[-1], jnp.prod(1.0 - lam[:-1], axis=0), rtol=1e-4)
        np.testing.assert_allclose(p, REF.exit_distribution(gate),
                                   rtol=1e-4, atol=1e-7)
        want = jnp.mean(jnp.sum(p * nll, 0) + 0.1 * jnp.sum(
            p * jnp.log(p), 0))
        np.testing.assert_allclose(loss, want, rtol=1e-6)
        # the last pass's gate is not read
        g = jax.grad(lambda g: ouro.exit_mixture(nll, g, 0.1)[0])(gate)
        assert float(jnp.abs(g[-1]).max()) == 0.0
        assert r == 1 or float(jnp.abs(g[:-1]).min()) > 0.0

    def test_the_log_space_mixture_holds_where_the_plain_one_underflows(
            self):
        gate = jnp.asarray([[120.0], [-120.0], [0.0]])
        loss, p = ouro.exit_mixture(jnp.ones((3, 1)), gate, 0.1)
        assert np.isfinite(float(loss)) and abs(float(loss) - 1.0) < 1e-6

    @pytest.mark.parametrize("block", [48, 100, 256, 1000])
    def test_the_blocked_loss_is_the_unblocked_one(self, tiny, block):
        """256 rows in blocks of 48 (the preset's: five whole and 16 rows
        of a sixth), 100, one block, and a block larger than the rows:
        value and every gradient leaf."""
        model, params, batch, _, step = tiny
        other, _ = create_model("ouro_tiny", head_block=block)
        whole, _ = create_model("ouro_tiny", head_block=256)
        want_loss, want = program_loss(whole, batch)(params)
        loss, grads = (step(params) if block == 48
                       else program_loss(other, batch)(params))
        assert abs(loss - want_loss) / want_loss < LOSS_TOL
        assert max(leaf_gaps(grads, want).values()) < GRAD_TOL

    def test_no_whole_vocabulary_tensor_outlives_its_block(self, tiny):
        model, params, batch, _, _ = tiny
        text = str(jax.make_jaxpr(jax.grad(lambda p: model.apply(
            {"params": p}, batch["tokens"], batch["targets"])[0]))(params))
        assert "f32[48,512]" in text            # a block's logits
        for rows in ("256,512", "4,64,512", "6,48,512", "288,512"):
            assert f"f32[{rows}]" not in text, rows

    def test_beta_zero_drops_the_entropy_and_the_gate_still_learns(
            self, tiny):
        _, params, batch, _, _ = tiny
        model, _ = create_model("ouro_tiny", entropy_beta=0.0)
        loss, grads = program_loss(model, batch)(params)
        nll, gate = jax.jit(lambda p: REF.exits(
            p, batch["tokens"], batch["targets"], spec_of(model.cfg)))(params)
        want = jnp.mean(jnp.sum(REF.exit_distribution(gate) * nll, axis=0))
        assert abs(loss - want) / want < LOSS_TOL
        assert float(jnp.linalg.norm(grads["gate"]["kernel"])) > 1e-4
        ref_loss, _ = reference_loss(model, batch)(params)
        assert abs(loss - ref_loss) / ref_loss < LOSS_TOL


# ---- the broken paths the chip's check has to catch ------------------------
# Each is a patch of models/ouro.py made here (nothing of the tree is
# edited); the builder's chip script enters the same patch round
# benchmark/run.py.

def _swap(name, new):
    @contextlib.contextmanager
    def patch():
        old = getattr(ouro, name)
        setattr(ouro, name, new)
        try:
            yield
        finally:
            setattr(ouro, name, old)
    return patch


def _exit_block_h_fed_back(mdl, carry, rows):
    carry, (_, nll, gate) = _EXIT_BLOCK(mdl, carry, rows)
    return carry, (rows[0], nll, gate)


class _NoSandwich(nn.Module):
    """A block with no norm on a branch's output (the parameters exist)."""
    cfg: ouro.OuroConfig

    @nn.compact
    def __call__(self, x):
        c = self.cfg
        norm = lambda name: layers.RMSNorm(c.rms_norm_eps, c.dtype, name=name)
        a = ouro.Attention(c.num_attention_heads, c.num_key_value_heads,
                           c.head_dim, c.rope_theta, c.attn_block, c.dtype,
                           name="attn")(norm("attn_norm")(x))
        norm("attn_out_norm")(a)
        x = x + a
        m = layers.SwiGLU(c.intermediate_size, c.dtype, True, name="ffn")(
            norm("ffn_norm")(x))
        norm("ffn_out_norm")(m)
        return x + m


def _mixture_without_remainder(nll, gate, beta):
    """``exit_mixture`` whose last exit keeps its own gate: p_R = lam_R
    prod_{s<R}(1 - lam_s), so p no longer sums to 1."""
    stay = jnp.cumsum(jax.nn.log_sigmoid(-gate), axis=0)
    log_p = jnp.concatenate([jnp.zeros_like(stay[:1]), stay[:-1]],
                            axis=0) + jax.nn.log_sigmoid(gate)
    p = jnp.exp(log_p)
    return jnp.mean(jnp.sum(p * nll, axis=0)
                    + beta * jnp.sum(p * log_p, axis=0)), p


_EXIT_BLOCK, _MIXTURE = ouro._exit_block, ouro.exit_mixture
BROKEN = {
    # h and not x_t = N_f(h) goes to the next pass
    "norm_not_fed_back": _swap("_exit_block", _exit_block_h_fed_back),
    "sandwich_left_out": _swap("DecoderLayer", _NoSandwich),
    # loss = L_R alone
    "last_exit_only": _swap("exit_mixture", lambda nll, gate, beta: (
        jnp.mean(nll[-1]), _MIXTURE(nll, gate, beta)[1])),
    "entropy_left_out": _swap("exit_mixture", lambda nll, gate, beta:
                              _MIXTURE(nll, gate, 0.0)),
    # p_R = lam_R prod(1 - lam_s): the last exit forgets the remainder
    "no_remainder": _swap("exit_mixture", _mixture_without_remainder),
}


def broken_model(variant, name="ouro_tiny", **kwargs):
    """(model, the patch to trace it under). ``one_pass`` is R = 1."""
    if variant == "one_pass":
        return (create_model(name, total_ut_steps=1, **kwargs)[0],
                contextlib.nullcontext)
    return create_model(name, **kwargs)[0], BROKEN[variant]


class TestBrokenPathsAreCaught:
    @pytest.mark.parametrize("variant", ["one_pass"] + sorted(BROKEN))
    def test_a_broken_path_fails_the_tolerances(self, tiny, variant):
        _, params, batch, ref, _ = tiny
        ref_loss, ref_grads = ref(params)
        model, patch = broken_model(variant)
        with patch():
            loss, grads = program_loss(model, batch)(params)
        gaps = leaf_gaps(grads, ref_grads)
        assert (abs(loss - ref_loss) / abs(ref_loss) > 10 * LOSS_TOL
                or max(gaps.values()) > 10 * GRAD_TOL), (loss, ref_loss)
        assert max(gaps.values()) > 10 * GRAD_TOL

    def test_the_patches_leave_the_module_as_it_was(self, tiny):
        model, params, batch, _, step = tiny
        assert ouro._exit_block is _EXIT_BLOCK
        assert ouro.exit_mixture is _MIXTURE
        assert ouro.DecoderLayer is not _NoSandwich


class TestAttentionAtAGroupOfOneHead:
    def test_rotary_turns_all_of_a_heads_dims(self):
        cos, sin = attention.rotary_table(attention.Rope(1e4), 32, 64)
        assert cos.shape == (64, 16)
        x = jax.random.normal(jax.random.PRNGKey(0), (1, 64, 4, 32))
        y = attention.rotate_half_partial(x, cos, sin)
        np.testing.assert_allclose(y[0], REF._rotary(x[0], 1e4), rtol=1e-5,
                                   atol=1e-6)
        assert float(jnp.abs(y[0, 1:] - x[0, 1:]).min(axis=(0, 1)).max()) > 0

    def test_the_interpreted_kernels_against_the_xla_form(self, monkeypatch):
        """16 query heads over 16 key-value heads: a tile serves one head."""
        ks = jax.random.split(jax.random.PRNGKey(3), 4)
        q, k, v, w = (jax.random.normal(key, (2, 64, 4, 32)) for key in ks)

        def through(q, k, v):
            return jnp.sum(attention.blocked_causal_gqa(
                q, k, v, 32 ** -0.5, 16) * w)
        want = jax.jit(jax.value_and_grad(through, (0, 1, 2)))(q, k, v)

        def attend(start, end):
            return lambda *seq: attention._attend_block_gqa(
                *seq, start, end, 32 ** -0.5)
        # the walk over a sequence's blocks, a group of one head spelled out
        plain = jax.vmap(lambda q, k, v: attention._blocked_xla(
            attend, (q.reshape(64, 4, 1, 32),), (k, v), 16).reshape(
                64, 4, 32))(q, k, v)
        np.testing.assert_allclose(jnp.sum(plain * w), want[0], rtol=1e-5)
        monkeypatch.setenv("OKTOPK_PALLAS_INTERPRET", "1")
        got = jax.jit(jax.value_and_grad(through, (0, 1, 2)))(q, k, v)
        np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
        for a, e in zip(got[1], want[1]):
            np.testing.assert_allclose(a, e, rtol=2e-4, atol=2e-5)

    def test_the_model_through_the_interpreted_kernels(self, tiny,
                                                       monkeypatch):
        model, params, batch, _, step = tiny
        want_loss, want = step(params)
        monkeypatch.setenv("OKTOPK_PALLAS_INTERPRET", "1")
        loss, grads = program_loss(model, batch)(params)
        assert abs(loss - want_loss) / want_loss < 1e-5
        assert max(leaf_gaps(grads, want).values()) < 1e-3
        # one body of three layers: a forward kernel a layer, not a pass
        text = str(jax.make_jaxpr(jax.grad(lambda p: model.apply(
            {"params": p}, batch["tokens"], batch["targets"])[0]))(params))
        for kernel in ("fwd", "dq", "dkv"):
            assert len(re.findall(
                rf"name=oktopk_flash_gqa_{kernel}\b", text)) == 3, kernel


def run_steps(trainer, steps, seed=0):
    workers = trainer.algo_cfg.num_workers
    out, m = [], None
    for _ in range(steps):
        b = batch_of(seqs=2 * workers, seed=seed)      # one batch, learnt
        m = trainer.train_step({k: jnp.asarray(v) for k, v in b.items()})
        out.append(float(m["loss"]))
    return out, m


OLDER = {"lstm_tiny": ({}, 35, 1024),
         "deepseek_v2_tiny": ({"held_experts": [0, 1, 2, 3]}, 64, 512),
         "qwen3_next_tiny": ({"held_experts": [0, 1, 2, 3]}, 64, 512),
         "smallthinker_tiny": ({"held_experts": [0, 1, 2, 3]}, 64, 512),
         "laguna_tiny": ({"held_experts": [0, 1, 2, 3]}, 64, 512)}


class TestTrainer:
    @pytest.mark.parametrize("compressor", ["dense", "oktopk"])
    def test_three_steps_on_four_workers(self, mesh4, compressor):
        cfg = TrainConfig(dnn="ouro_tiny", dataset="ptb", batch_size=2,
                          lr=0.05, momentum=0.9, weight_decay=0.0,
                          compressor=compressor, density=0.05, grad_clip=1.0)
        tr = Trainer(cfg, mesh=mesh4, warmup=False)
        out, m = run_steps(tr, 3)
        assert all(np.isfinite(out)) and out[-1] < out[0], out
        for leaf in jax.tree.leaves(tr.state.params):
            shards = [np.asarray(s.data) for s in leaf.addressable_shards]
            assert all(np.array_equal(s, shards[0]) for s in shards[1:])
        c = dict(zip(COUNTERS, np.asarray(m["counters"]).tolist()))
        # the gate's mass between the first exit and the last
        assert 1000 < c["exit_step_milli_max"] < 4000
        assert c["expert_rows"] == c["expert_rows_max"] == 0

    def test_the_step_loss_is_the_models_own(self, mesh4):
        """The rung hands the model its targets and takes its loss: the
        first step's loss is the reference's on the trainer's own
        parameters, the mean over the four workers' shards."""
        cfg = TrainConfig(dnn="ouro_tiny", dataset="ptb", batch_size=2,
                          lr=0.05, compressor="dense")
        tr = Trainer(cfg, mesh=mesh4, warmup=False)
        params = jax.device_get(tr.state.params)
        b = batch_of(seqs=8)
        m = tr.train_step({k: jnp.asarray(v) for k, v in b.items()})
        ref = jax.jit(lambda p, shard: REF.loss(p, shard,
                                                spec_of(tr.model.cfg)))
        want = np.mean([float(ref(
            params, {k: v[2 * w:2 * w + 2] for k, v in b.items()}))
            for w in range(4)])
        assert abs(float(m["loss"]) - want) / want < 10 * LOSS_TOL

    def test_eval_step_gives_the_last_exits_perplexity(self, mesh4):
        cfg = TrainConfig(dnn="ouro_tiny", dataset="ptb", batch_size=2,
                          lr=0.05, compressor="dense")
        tr = Trainer(cfg, mesh=mesh4, warmup=False)
        b = {k: jnp.asarray(v) for k, v in batch_of(seqs=4).items()}
        got = tr.eval_step(b)
        nll, _ = jax.jit(lambda p: REF.exits(
            p, b["tokens"], b["targets"], spec_of(tr.model.cfg)))(
                tr.state.params)
        want = float(jnp.mean(nll[-1]))
        assert abs(float(got["loss"]) - want) / want < 10 * LOSS_TOL
        assert abs(float(got["ppl"]) - np.exp(want)) / np.exp(want) < 1e-4
        # not the mixture, and not the first exit
        assert abs(float(jnp.mean(nll[0])) - want) / want > 1e-4

    @pytest.mark.parametrize("dnn", sorted(OLDER))
    def test_the_older_token_models_loss_is_unchanged_bit_for_bit(
            self, mesh4, dnn):
        """``_loss_fn`` of a model that hands back logits: the loss and the
        whole gradient are, bit for bit, those of ``lm_cross_entropy`` of
        ``apply``'s logits, as the rung computed them before it took
        models with a loss of their own (the counters' places:
        ``TestCountersAndDocstrings``)."""
        kwargs, t, vocab = OLDER[dnn]
        cfg = TrainConfig(dnn=dnn, dataset="ptb", batch_size=2, lr=0.05,
                          compressor="dense")
        tr = Trainer(cfg, mesh=mesh4, warmup=False, model_kwargs=kwargs)
        assert not getattr(tr.model, "computes_loss", False)
        b = {k: jnp.asarray(v) for k, v in
             batch_of(seqs=2, t=t, vocab=vocab).items()}
        rng = jax.random.PRNGKey(5)
        state = tr.state.model_state

        def before():
            def loss(p):
                (logits, extra), _ = tr.model.apply(
                    {"params": p, **state}, b["tokens"], train=True,
                    mutable=list(state), rngs={"dropout": rng})
                return losses.lm_cross_entropy(logits, b["targets"])
            return loss

        def now():
            def loss(p):
                return tr._loss_fn(p, state, b, rng)[0]
            return loss
        # one program: the lowered text of loss and gradient is the same,
        # so every bit of both is
        want, got = (jax.jit(jax.value_and_grad(make())).lower(
            tr.state.params).as_text() for make in (before, now))
        assert got == want and "dot_general" in got
        aux = jax.eval_shape(
            lambda p: tr._loss_fn(p, state, b, rng)[1][1], tr.state.params)
        if dnn == "lstm_tiny":
            assert aux == {}
        else:
            assert aux["counters"].shape == (len(MODEL_COUNTERS),)


class TestCountersAndDocstrings:
    def test_a_model_without_experts_raises_no_key_error(self):
        got = losses.model_counters({"exit_step_milli_max": 2187.0,
                                     "exit_p": jnp.ones((4, 2, 3))})
        assert got["counters"].dtype == jnp.int32
        assert got["counters"].tolist() == [0, 0, 2187]
        assert losses.model_counters({})["counters"].tolist() == [0, 0, 0]
        assert losses.model_counters((jnp.zeros(3),)) == {}
        rows = jnp.asarray([[3, 0], [5, 1]])
        assert losses.model_counters({"expert_rows": rows})[
            "counters"].tolist() == [9, 5, 0]
        assert MODEL_COUNTERS == ("expert_rows", "expert_rows_max",
                                  "exit_step_milli_max")
        assert COUNTERS[-3:] == MODEL_COUNTERS

    def test_a_model_with_its_own_loss_never_calls_lm_cross_entropy(
            self, mesh4, monkeypatch):
        """``lm_cross_entropy`` is the loss of the models that hand back
        logits: the rung's training loss and ``eval_step`` of a
        ``computes_loss`` model trace without it."""
        logits = jax.random.normal(jax.random.PRNGKey(0), (2, 5, 7))
        targets = jnp.zeros((2, 5), jnp.int32)
        want = jnp.mean(jax.nn.logsumexp(logits, -1) - logits[..., 0])
        np.testing.assert_allclose(losses.lm_cross_entropy(logits, targets),
                                   want, rtol=1e-6)
        cfg = TrainConfig(dnn="ouro_tiny", dataset="ptb", batch_size=2,
                          lr=0.05, compressor="dense")
        tr = Trainer(cfg, mesh=mesh4, warmup=False)
        b = {k: jnp.asarray(v) for k, v in batch_of(seqs=2).items()}

        def never(*_):
            raise AssertionError("lm_cross_entropy was called")
        monkeypatch.setattr(losses, "lm_cross_entropy", never)
        loss, (_, aux) = jax.eval_shape(
            lambda p: tr._loss_fn(p, tr.state.model_state, b,
                                  jax.random.PRNGKey(5)), tr.state.params)
        assert loss.shape == () and loss.dtype == jnp.float32
        assert aux["counters"].shape == (len(MODEL_COUNTERS),)
        assert set(tr.eval_step(b)) == {"loss", "ppl"}

    @pytest.mark.parametrize("bias, want", [(30.0, 1000), (-30.0, 4000)])
    def test_the_exit_step_counter_follows_the_gate(self, tiny, bias, want):
        """A gate that always leaves at the first exit reads 1,000 and one
        that never leaves before the last 1,000 R: the two ends at which a
        collapsed gate shows."""
        model, params, batch, _, _ = tiny
        forced = {**params, "gate": {
            "kernel": jnp.zeros_like(params["gate"]["kernel"]),
            "bias": jnp.full_like(params["gate"]["bias"], bias)}}
        _, extra = jax.jit(lambda p: model.apply(
            {"params": p}, batch["tokens"], batch["targets"]))(forced)
        assert int(extra["exit_step_milli_max"]) == want
        exit_of = {1000: 0, 4000: 3}[want]
        np.testing.assert_allclose(extra["exit_p"][exit_of], 1.0, atol=1e-6)


class TestRegistryAndScopes:
    def test_token_models_share_one_example_shape_rule(self):
        assert TOKEN_LMS["ouro_2_6b"] == (4096, 49152)
        model, example = create_model("ouro_tiny")
        assert example(3).shape == (3, TOKEN_LMS["ouro_tiny"][0])
        assert model.computes_loss and model.jit_init

    def test_the_configuration_keeps_every_published_width(self):
        with open(CONFIG) as f:
            config = json.load(f)
        cfg = ouro.OuroConfig()
        same = [f.name for f in dataclasses.fields(cfg) if f.name in config
                and f.name not in config["reduced"]]
        assert len(same) >= 11
        for k in same:
            assert config[k] == getattr(cfg, k), k
        assert config["reduced"] == ["num_hidden_layers"]
        assert config["published"]["num_hidden_layers"] == (
            cfg.num_hidden_layers) == 48
        assert config["num_hidden_layers"] == config["model_kwargs"][
            "num_hidden_layers"] == config["spec"]["num_hidden_layers"] == 6
        assert config["hidden_act"] == "silu" and not config[
            "tie_word_embeddings"]
        assert config["sliding_window"] is None and not config[
            "use_sliding_window"]
        assert set(config["layer_types"]) == {"full_attention"}
        for k, v in config["spec"].items():   # but the depth and the blocks
            if hasattr(cfg, k) and k != "num_hidden_layers" and (
                    not k.endswith("_block")):
                assert v == getattr(cfg, k), k
        assert config["input"] == {"kind": "tokens", "vocab": cfg.vocab_size,
                                   "seq_len": 4096}
        # the catalog's row, where this sandbox has it: every number of
        # its ``config`` under the same key, but the depth
        catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
        if os.path.exists(catalog):
            with open(catalog) as f:
                row = next(r for r in map(json.loads, f)
                           if r["name"] == "Ouro-2.6B")
            assert config["source"].startswith(row["source_url"])
            for k, v in row["config"].items():
                assert config[k] == (6 if k == "num_hidden_layers" else v), k

    @pytest.mark.parametrize("kwargs", [
        {"num_key_value_heads": 3}, {"total_ut_steps": 0},
        {"num_hidden_layers": 0}])
    def test_a_shape_that_cannot_be_is_refused(self, kwargs):
        with pytest.raises(ValueError):
            ouro.OuroConfig.tiny(**kwargs)

    def test_forward_recomputed_and_backward_ops_carry_the_sub_scopes(
            self, tiny):
        """The model lives in a loop's body: the body's operations carry
        their sub-scope, forward and backward, every layer."""
        model, params, batch, _, _ = tiny

        def loss(p):
            with anatomy.phase_scope("fwd_bwd"):
                return model.apply({"params": p}, batch["tokens"],
                                   batch["targets"])[0]
        text = jax.jit(jax.grad(loss)).lower(params).compile().as_text()
        paths = set(re.findall(r'op_name="([^"]*)"', text))
        subs = anatomy.SUB_SCOPES["fwd_bwd"]
        by_sub = {sub: [p for p in paths if kernels_lm.sub_of(p, subs) == sub]
                  for sub in ("attention", "full_scores", "mlp", "head",
                              "exit_gate")}
        for sub, mine in by_sub.items():
            assert mine, sub
            assert any("transpose" not in p for p in mine), sub  # forward
            assert any("transpose" in p for p in mine), sub      # backward
            assert any("while" in p for p in mine), sub      # the loop's body
        assert all("/attention/" in p for p in by_sub["full_scores"])
        for sub in ("attention", "full_scores", "mlp"):
            assert {re.search(r"layers_(\d)", p).group(1)
                    for p in by_sub[sub] if "layers_" in p} == {"0", "1", "2"}
        # the mixing after the loop is the gate's too, outside any while
        assert any("while" not in p for p in by_sub["exit_gate"])
        # no flax module is named like a sub-scope
        for path, _ in jax.tree_util.tree_flatten_with_path(params)[0]:
            assert not any(str(k.key) in subs for k in path[:-1]), path


class TestBenchmarkCounts:
    """benchmark/benchlib/kernels_loop.py behind ``loop_scores_roofline``,
    ``exit_head_mxu_share`` and ``mlp_mxu_share`` (benchmark/tests holds
    each count against hand arithmetic)."""

    def test_counted_from_the_published_widths(self):
        with open(CONFIG) as f:
            config = json.load(f)
        k = kernels_loop
        assert k.applications(config) == 24
        assert k.triangle_pairs(4096) == 8_390_656
        assert k.scores_flops_a_step(config, 2) == (
            24 * 2 * 8_390_656 * 16 * 4 * 128 * 3)
        assert k.head_flops_a_step(config, 2) == (
            4 * 2 * 8192 * 2048 * 49152 * 3)
        assert k.mlp_flops_a_step(config, 2) == (
            24 * 3 * 2 * 8192 * 2048 * 5632 * 3)
        least, bound = k.scores_roofline_seconds(config, 2, "TPU v5 lite")
        assert bound == "compute" and 50e-3 < least < 51e-3

    def test_the_readers_take_the_innermost_sub_scope(self):
        subs = anatomy.SUB_SCOPES["fwd_bwd"]
        base = "jit(shard_fn)/anat/fwd_bwd/"
        for path, want in [
            (base + "jvp(Ouro)/while/body/layers_0/anat/fwd_bwd/attention/"
             "attn/q_proj/dot_general", "attention"),
            (base + "transpose(jvp(Ouro))/while/body/layers_2/anat/fwd_bwd/"
             "attention/attn/anat/fwd_bwd/full_scores/checkpoint/"
             "dot_general", "full_scores"),
            (base + "jvp(Ouro)/while/body/while/body/anat/fwd_bwd/head/"
             "lm_head/dot_general", "head"),
            (base + "jvp(Ouro)/anat/fwd_bwd/exit_gate/cumsum", "exit_gate"),
            (base + "jvp(Ouro)/while/body/layers_1/anat/fwd_bwd/mlp/ffn/"
             "dot_general", "mlp")]:
            assert kernels_lm.sub_of(path, subs) == want, path
