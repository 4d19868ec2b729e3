"""Sequence-parallel BERT (ring attention over a seq mesh axis) vs the
single-module oracle — long-context support the reference lacks
(SURVEY.md §5.7)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from oktopk_tpu.models.bert import BertConfig, BertForPreTraining
from oktopk_tpu.parallel.bert_seq import build_seq_loss, make_seq_mesh
from oktopk_tpu.train import losses

B, T = 4, 32


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
    amask[:, -5:] = 0                      # padding tail crosses shards
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


class TestBertSeqParallel:
    @pytest.mark.parametrize("shards", [2, 4, 8])
    def test_loss_matches_single_module(self, cfg, params, shards):
        batch = make_batch(np.random.RandomState(1), cfg.vocab_size)
        want = float(oracle_loss(cfg, params, batch))
        mesh = make_seq_mesh(shards)
        loss_fn = build_seq_loss(cfg, mesh)
        got = float(loss_fn(params, batch))
        np.testing.assert_allclose(got, want, rtol=2e-5)

    def test_composed_data_x_seq_mesh(self, cfg, params):
        """dp x sp composition: batch over 'data', tokens over 'seq' —
        loss still equals the single-module global loss."""
        batch = make_batch(np.random.RandomState(3), cfg.vocab_size)
        want = float(oracle_loss(cfg, params, batch))
        mesh = make_seq_mesh(4, data_size=2)
        loss_fn = build_seq_loss(cfg, mesh)
        got = float(loss_fn(params, batch))
        np.testing.assert_allclose(got, want, rtol=2e-5)

    def test_gradients_match_single_module(self, cfg, params):
        batch = make_batch(np.random.RandomState(2), cfg.vocab_size)
        g_ref = jax.grad(
            lambda p: oracle_loss(cfg, p, batch))(params)
        mesh = make_seq_mesh(4)
        loss_fn = build_seq_loss(cfg, mesh)
        g_seq = jax.grad(lambda p: loss_fn(p, batch))(params)
        for (pa, a), (_, b) in zip(
                jax.tree_util.tree_leaves_with_path(g_ref),
                jax.tree_util.tree_leaves_with_path(g_seq)):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=5e-5,
                err_msg=jax.tree_util.keystr(pa))

    def test_activation_memory_scales_with_seq_shards(self, cfg, params):
        """The long-context property (docs/PERF.md, scripts/memory_scaling
        .py): per-chip temp allocation of the compiled training program
        falls near-linearly with seq shards — no [T, T] materialisation,
        positionwise tensors sharded on the token axis."""
        batch = make_batch(np.random.RandomState(9), cfg.vocab_size)
        temps = {}
        for sp in (1, 4):
            mesh = make_seq_mesh(sp)
            loss_fn = build_seq_loss(cfg, mesh)
            grad_fn = jax.jit(jax.grad(lambda p: loss_fn(p, batch)))
            stats = grad_fn.lower(params).compile().memory_analysis()
            temps[sp] = stats.temp_size_in_bytes
        # measured ~0.26x at sp=4 with this file's T=32 tiny config;
        # 0.6 fails if anything re-materialises the full sequence
        assert temps[4] < 0.6 * temps[1], temps


class TestSeqSparseComposition:
    """Sparse data parallelism composed with sequence parallelism on a
    (data, seq) mesh — the reference's whole framework (sparse allreduce
    DP) riding under long context it never had."""

    def _setup(self, cfg, params, compressor, warmup=False):
        from oktopk_tpu.collectives.state import init_state
        from oktopk_tpu.config import OkTopkConfig
        from oktopk_tpu.optim.sgd import sgd
        from oktopk_tpu.parallel.bert_seq import build_seq_sparse_train_step

        dp, sp = 2, 4
        mesh = make_seq_mesh(sp, data_size=dp)
        n = sum(x.size for x in jax.tree.leaves(params))
        acfg = OkTopkConfig(n=n, num_workers=dp, density=0.05,
                            warmup_steps=0, use_pallas=False)
        opt = sgd(lr=0.1)
        step = build_seq_sparse_train_step(cfg, mesh, opt, acfg,
                                           compressor=compressor,
                                           warmup=warmup)
        from oktopk_tpu.parallel.bert_seq import stack_replicas
        sstate = stack_replicas(init_state(acfg), dp)
        return step, sstate, opt, acfg, dp

    def test_dense_composition_matches_per_row_oracle(self, cfg, params):
        """compressor='dense': the composed step must equal mean-of-
        per-data-row gradients (each row = the single-module loss on its
        sub-batch) applied by the same optimizer."""
        from oktopk_tpu.optim.sgd import sgd

        from oktopk_tpu.parallel.bert_seq import stack_replicas
        step, sstate, opt, acfg, dp = self._setup(cfg, params, "dense")
        batch = make_batch(np.random.RandomState(11), cfg.vocab_size)
        pstack = stack_replicas(params, dp)
        ostack = stack_replicas(opt.init(params), dp)
        p2s, _, _, loss = step(pstack, sstate, ostack, batch)
        # every data rank holds the identical replica
        p2 = jax.tree.map(lambda x: x[0], p2s)
        for leaf in jax.tree.leaves(p2s):
            np.testing.assert_array_equal(np.asarray(leaf[0]),
                                          np.asarray(leaf[1]))

        rows = [jax.tree.map(lambda x, r=r: x[r * (B // dp):(r + 1)
                             * (B // dp)], batch) for r in range(dp)]
        gs = [jax.grad(lambda p, rb=rb: oracle_loss(cfg, p, rb))(params)
              for rb in rows]
        gmean = jax.tree.map(lambda a, b: (a + b) / dp, *gs)
        updates, _ = opt.update(gmean, opt.init(params), params)
        want = jax.tree.map(jnp.add, params, updates)
        for (pa, a), (_, b) in zip(
                jax.tree_util.tree_leaves_with_path(want),
                jax.tree_util.tree_leaves_with_path(p2)):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=5e-5,
                err_msg=jax.tree_util.keystr(pa))

    def test_oktopk_composition_trains(self, cfg, params):
        """oktopk over data x ring attention over seq: state advances,
        volume is sparse, params move and stay finite."""
        from oktopk_tpu.parallel.bert_seq import stack_replicas
        step, sstate, opt, acfg, dp = self._setup(cfg, params, "oktopk")
        batch = make_batch(np.random.RandomState(12), cfg.vocab_size)
        p = stack_replicas(params, dp)
        opt_state = stack_replicas(opt.init(params), dp)
        for i in range(3):
            p, sstate, opt_state, loss = step(p, sstate, opt_state, batch)
            assert np.isfinite(float(loss))
        assert int(sstate.step[0]) == 3
        vol = float(sstate.last_volume[0])
        assert 0 < vol < 2.0 * acfg.n, vol
        moved = sum(float(jnp.sum((a[0] - b) ** 2)) for a, b in zip(
            jax.tree.leaves(p), jax.tree.leaves(params)))
        assert moved > 0

    def test_accumulation_matches_large_batch_dense(self, cfg, params):
        """accum_steps=2 on half-batches == one step on the full batch
        (dense compressor; per-row weighted means make the halves equal-
        weight when mask counts match, so use uniform masking)."""
        from oktopk_tpu.collectives.state import init_state
        from oktopk_tpu.config import OkTopkConfig
        from oktopk_tpu.optim.sgd import sgd
        from oktopk_tpu.parallel.bert_seq import (
            build_seq_sparse_train_step, stack_replicas)

        dp, sp = 2, 4
        mesh = make_seq_mesh(sp, data_size=dp)
        n = sum(x.size for x in jax.tree.leaves(params))
        acfg = OkTopkConfig(n=n, num_workers=dp, density=0.05,
                            warmup_steps=0, use_pallas=False)
        opt = sgd(lr=0.1)
        rng = np.random.RandomState(17)
        batch = make_batch(rng, cfg.vocab_size)
        # uniform per-example mask count so half-batch means average
        # exactly to the full-batch mean
        mlm = np.full((B, T), -1, np.int32)
        ids = np.asarray(batch["input_ids"])
        for b in range(B):
            cols = rng.choice(T, size=3, replace=False)
            mlm[b, cols] = ids[b, cols]
        batch["mlm_labels"] = jnp.asarray(mlm)

        outs = {}
        for acc in (1, 2):
            step = build_seq_sparse_train_step(
                cfg, mesh, opt, acfg, compressor="dense", warmup=False,
                accum_steps=acc)
            p2, _, _, loss = step(stack_replicas(params, dp),
                                  stack_replicas(init_state(acfg), dp),
                                  stack_replicas(opt.init(params), dp),
                                  batch)
            outs[acc] = (p2, float(loss))
        np.testing.assert_allclose(outs[1][1], outs[2][1], rtol=1e-6)
        for (pa, a), (_, b) in zip(
                jax.tree_util.tree_leaves_with_path(outs[1][0]),
                jax.tree_util.tree_leaves_with_path(outs[2][0])):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=1e-6,
                err_msg=jax.tree_util.keystr(pa))
