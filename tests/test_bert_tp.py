"""Tensor-parallel BERT (Megatron-style head/FFN sharding over a model
mesh axis) vs the single-module oracle. TP is absent from the reference
(SURVEY.md §2.3) — this is the extension completing dp/pp/sp/tp."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from oktopk_tpu.config import OkTopkConfig
from oktopk_tpu.models.bert import BertConfig, BertForPreTraining
from oktopk_tpu.optim.sgd import sgd
from oktopk_tpu.parallel.bert_tp import (build_tp_loss,
                                         build_tp_sparse_train_step,
                                         build_tp_train_step,
                                         init_tp_opt_states,
                                         init_tp_sparse_states,
                                         make_tp_mesh, merge_tp, split_tp)
from oktopk_tpu.train import losses

B, T = 4, 16


@pytest.fixture(scope="module")
def cfg():
    return BertConfig.tiny()


@pytest.fixture(scope="module")
def params(cfg):
    ex = jnp.zeros((2, T), jnp.int32)
    rng = jax.random.PRNGKey(0)
    return BertForPreTraining(cfg).init(
        {"params": rng, "dropout": rng}, ex, ex, jnp.ones_like(ex),
        train=False)["params"]


def make_batch(rng, vocab):
    ids = rng.randint(0, vocab, size=(B, T)).astype(np.int32)
    mlm = np.full((B, T), -1, np.int32)
    pos = rng.rand(B, T) < 0.2
    mlm[pos] = ids[pos]
    amask = np.ones((B, T), np.int32)
    amask[:, -3:] = 0
    return {"input_ids": jnp.asarray(ids),
            "token_type_ids": jnp.zeros((B, T), jnp.int32),
            "attention_mask": jnp.asarray(amask),
            "mlm_labels": jnp.asarray(mlm),
            "nsp_labels": jnp.asarray(
                rng.randint(0, 2, size=(B,)).astype(np.int32))}


def oracle_loss(cfg, params, batch):
    mlm, nsp = BertForPreTraining(cfg).apply(
        {"params": params}, batch["input_ids"], batch["token_type_ids"],
        batch["attention_mask"], train=False)
    loss, _ = losses.bert_pretrain_loss(mlm, nsp, batch["mlm_labels"],
                                        batch["nsp_labels"])
    return loss


class TestBertTensorParallel:
    def test_split_merge_roundtrip(self, cfg, params):
        tp, shared = split_tp(params, 2)
        merged = merge_tp(tp, shared)
        for (pa, a), (pb, b) in zip(
                jax.tree_util.tree_leaves_with_path(params),
                jax.tree_util.tree_leaves_with_path(merged)):
            assert pa == pb
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_loss_matches_single_module(self, cfg, params):
        batch = make_batch(np.random.RandomState(1), cfg.vocab_size)
        want = float(oracle_loss(cfg, params, batch))
        tp, shared = split_tp(params, 2)   # tiny has 2 heads -> TP=2 max
        loss_fn = build_tp_loss(cfg, make_tp_mesh(2))
        got = float(loss_fn(tp, shared, batch))
        np.testing.assert_allclose(got, want, rtol=2e-5)

    def test_gradients_match_single_module(self, cfg, params):
        batch = make_batch(np.random.RandomState(2), cfg.vocab_size)
        g_ref = jax.grad(lambda p: oracle_loss(cfg, p, batch))(params)
        tp, shared = split_tp(params, 2)
        loss_fn = build_tp_loss(cfg, make_tp_mesh(2))
        g_tp, g_sh = jax.grad(
            lambda t, s: loss_fn(t, s, batch), argnums=(0, 1))(tp, shared)
        g_merged = merge_tp(g_tp, g_sh)
        for (pa, a), (_, b) in zip(
                jax.tree_util.tree_leaves_with_path(g_ref),
                jax.tree_util.tree_leaves_with_path(g_merged)):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=5e-5,
                err_msg=jax.tree_util.keystr(pa))

    def test_train_step_matches_single_module(self, cfg, params):
        """Two SGD-momentum steps through the TP step == two oracle steps
        on the merged module (elementwise optimizer: sharded moments are
        the merged moments re-split)."""
        opt = sgd(0.05, momentum=0.9)
        mesh = make_tp_mesh(2)
        step = build_tp_train_step(cfg, mesh, opt)
        tp, shared = split_tp(params, 2)
        # the step donates its inputs and split_tp's `shared` tree aliases
        # the fixture's arrays — give the step fresh buffers
        tp, shared = jax.tree.map(jnp.array, (tp, shared))
        opt_tp, opt_sh = init_tp_opt_states(opt, tp, shared)

        ref_p, ref_o = params, opt.init(params)
        for i in range(2):
            batch = make_batch(np.random.RandomState(10 + i),
                               cfg.vocab_size)
            tp, shared, opt_tp, opt_sh, loss = step(tp, shared, opt_tp,
                                                    opt_sh, batch)
            g = jax.grad(lambda p: oracle_loss(cfg, p, batch))(ref_p)
            upd, ref_o = opt.update(g, ref_o, ref_p)
            ref_p = jax.tree.map(jnp.add, ref_p, upd)
            ref_loss = float(oracle_loss(cfg, ref_p, batch))
        merged = merge_tp(tp, shared)
        for (pa, a), (_, b) in zip(
                jax.tree_util.tree_leaves_with_path(ref_p),
                jax.tree_util.tree_leaves_with_path(merged)):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=2e-5,
                err_msg=jax.tree_util.keystr(pa))
        assert np.isfinite(float(loss)) and np.isfinite(ref_loss)

    def test_sparse_dp_tp_full_density_matches_dense_oracle(self, cfg,
                                                            params,
                                                            devices):
        """The data x model cell of the composition matrix: at density 1.0
        with a float32 wire the sparse collective returns exactly the
        dense data-mean (pinned by TestOkTopk::test_full_density_equals
        _dense), so one composed dp(2) x tp(2) step must equal the oracle:
        mean of the per-data-half gradients, one SGD step on the merged
        module. Also pins the divergence hazard the split-vector design
        exists for: shared params stay identical across model ranks."""
        dp, tpn = 2, 2
        mesh = make_tp_mesh(tpn, devices, data_size=dp)
        opt = sgd(0.05, momentum=0.9)
        acfg = OkTopkConfig(density=1.0, wire_dtype="float32",
                            warmup_steps=0, num_workers=dp)
        step = build_tp_sparse_train_step(cfg, mesh, opt, acfg,
                                          compressor="oktopk",
                                          warmup=False)
        tp, shared = split_tp(params, tpn)
        stack = lambda t, lead: jax.tree.map(
            lambda x: jnp.broadcast_to(x, lead + x.shape), t)
        tp_r, sh_r = stack(tp, (dp,)), stack(shared, (dp,))
        ss = init_tp_sparse_states(tp, shared, acfg, dp)
        opt_tp, opt_sh = init_tp_opt_states(opt, tp, shared)
        opts = (stack(opt_tp, (dp,)), stack(opt_sh, (dp,)))

        batch = make_batch(np.random.RandomState(3), cfg.vocab_size)
        (tp_r, sh_r), ss, opts, metrics = step((tp_r, sh_r), ss, opts,
                                               batch)

        # oracle: mean of per-half grads (each half normalises its own
        # mask count, exactly what the composed step averages)
        half = lambda t, i: jax.tree.map(
            lambda x: x[i * (B // dp):(i + 1) * (B // dp)], t)
        gs = [jax.grad(lambda p: oracle_loss(cfg, p, half(batch, i)))(
            params) for i in range(dp)]
        g = jax.tree.map(lambda a, b: (a + b) / dp, *gs)
        upd, _ = opt.update(g, opt.init(params), params)
        ref_p = jax.tree.map(jnp.add, params, upd)

        # replicas identical across data ranks; shared across model ranks
        # is structural (single [dp, ...] array sharded over data only)
        for x in jax.tree.leaves((tp_r, sh_r)):
            np.testing.assert_array_equal(np.asarray(x[0]),
                                          np.asarray(x[1]))
        merged = merge_tp(jax.tree.map(lambda x: x[0], tp_r),
                          jax.tree.map(lambda x: x[0], sh_r))
        for (pa, a), (_, b) in zip(
                jax.tree_util.tree_leaves_with_path(ref_p),
                jax.tree_util.tree_leaves_with_path(merged)):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=5e-5,
                err_msg=jax.tree_util.keystr(pa))
        assert float(metrics["comm_volume"]) > 0
