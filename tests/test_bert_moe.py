"""Expert-parallel MoE BERT (GShard top-1 dispatch over an expert mesh
axis) vs the single-module oracle. EP is absent from the reference
(SURVEY.md §2.3) — this is the extension completing dp/pp/sp/tp/ep.

The equivalence lever: ``experts_from_dense`` tiles the dense FFN into E
identical experts, so with no capacity overflow ANY routing reproduces
the dense forward exactly; and a P=1 mesh (all experts local) must match
a P=4 mesh (experts + batch sharded, two all_to_all hops) — the dispatch
correctness test.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from oktopk_tpu.models.bert import BertConfig, BertForPreTraining
from oktopk_tpu.parallel.bert_moe import (MoEConfig, build_moe_loss,
                                          experts_from_dense, make_moe_mesh)
from oktopk_tpu.train import losses

B, T = 8, 16
E = 4


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
    return {"input_ids": jnp.asarray(ids),
            "token_type_ids": jnp.zeros((B, T), jnp.int32),
            "attention_mask": jnp.ones((B, T), jnp.int32),
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


def perturb(moe, scale=0.05):
    """Make the experts (and implicitly the routing consequences) differ."""
    leaves, treedef = jax.tree.flatten(moe)
    rng = np.random.RandomState(3)
    out = [jnp.asarray(np.asarray(x)
                       * (1.0 + scale * rng.randn(x.shape[0])
                          .astype(np.float32).reshape((-1,) + (1,) *
                                                      (x.ndim - 1))))
           for x in leaves]
    return jax.tree.unflatten(treedef, out)


class TestBertExpertParallel:
    def test_identical_experts_match_dense_oracle(self, cfg, params):
        """Identical experts + full capacity: the MoE forward must equal
        the single-module BERT (gate zero -> uniform probs -> the top-1
        scale is exactly 1/E... no: argmax prob = 1/E, so the combine is
        scaled; cancel it by scaling wo/bo by E)."""
        moe, shared = experts_from_dense(params, E)
        # gate is zero -> probs uniform -> g = 1/E; identical experts mean
        # output = dense_ffn(x)/E. Pre-scale the expert output params by E
        # so the MoE layer reproduces the dense FFN exactly.
        moe = {k: {**v, "wo": v["wo"] * E, "bo": v["bo"] * E}
               for k, v in moe.items()}
        mcfg = MoEConfig(num_experts=E, capacity_factor=float(E),
                         aux_weight=0.0)
        mesh = make_moe_mesh(4)
        loss_fn = build_moe_loss(cfg, mcfg, mesh)
        batch = make_batch(np.random.RandomState(1), cfg.vocab_size)
        got = float(loss_fn(moe, shared, batch))
        want = float(oracle_loss(cfg, params, batch))
        np.testing.assert_allclose(got, want, rtol=2e-4)

    def test_ep4_matches_ep1_dispatch(self, cfg, params):
        """Sharded experts + two all_to_all hops must reproduce the
        all-local computation, with DIFFERENT experts and a real gate."""
        moe, shared = experts_from_dense(params, E)
        moe = perturb(moe)
        rng = np.random.RandomState(5)
        for name in shared["layers"]:
            g = shared["layers"][name]["gate"]
            shared["layers"][name]["gate"] = jnp.asarray(
                0.5 * rng.randn(*g.shape).astype(np.float32))
        mcfg = MoEConfig(num_experts=E, capacity_factor=float(E))
        batch = make_batch(np.random.RandomState(2), cfg.vocab_size)
        losses_got = {}
        for pshards in (1, 4):
            mesh = make_moe_mesh(pshards)
            loss_fn = build_moe_loss(cfg, mcfg, mesh)
            losses_got[pshards] = float(loss_fn(moe, shared, batch))
        np.testing.assert_allclose(losses_got[4], losses_got[1], rtol=1e-5)

    def test_composed_data_x_expert_matches_ep1(self, cfg, params):
        """dp=2 x ep=4 (batch over both axes, experts replicated over
        data, dispatch within each data row) == all-local single device."""
        moe, shared = experts_from_dense(params, E, gate_scale=0.5, seed=9)
        moe = perturb(moe)
        mcfg = MoEConfig(num_experts=E, capacity_factor=float(E))
        batch = make_batch(np.random.RandomState(7), cfg.vocab_size)
        ref_fn = build_moe_loss(cfg, mcfg, make_moe_mesh(1))
        want = float(ref_fn(moe, shared, batch))
        mesh = make_moe_mesh(4, data_size=2)
        assert mesh.axis_names == ("data", "expert")
        got = float(build_moe_loss(cfg, mcfg, mesh)(moe, shared, batch))
        # psum reduction order differs across mesh layouts
        np.testing.assert_allclose(got, want, rtol=5e-5)

    def test_gradients_flow_to_experts_and_gate(self, cfg, params):
        moe, shared = experts_from_dense(params, E)
        moe = perturb(moe)
        mcfg = MoEConfig(num_experts=E, capacity_factor=2.0)
        mesh = make_moe_mesh(4)
        loss_fn = build_moe_loss(cfg, mcfg, mesh)
        batch = make_batch(np.random.RandomState(4), cfg.vocab_size)

        grads = jax.jit(jax.grad(
            lambda m, s: loss_fn(m, s, batch), argnums=(0, 1)))(moe, shared)
        gm, gs = grads
        flat = [np.asarray(x) for x in jax.tree.leaves(gm)]
        assert all(np.all(np.isfinite(x)) for x in flat)
        assert any(np.any(x != 0) for x in flat), "no grad reached experts"
        ggate = np.asarray(gs["layers"]["layer_0"]["gate"])
        assert np.all(np.isfinite(ggate)) and np.any(ggate != 0)

    def test_capacity_overflow_drops_but_stays_finite(self, cfg, params):
        """Tiny capacity: most tokens drop (pass through the residual);
        the loss must stay finite and the forward deterministic."""
        moe, shared = experts_from_dense(params, E)
        mcfg = MoEConfig(num_experts=E, capacity_factor=0.1)
        mesh = make_moe_mesh(4)
        loss_fn = build_moe_loss(cfg, mcfg, mesh)
        batch = make_batch(np.random.RandomState(6), cfg.vocab_size)
        l1 = float(loss_fn(moe, shared, batch))
        l2 = float(loss_fn(moe, shared, batch))
        assert np.isfinite(l1) and l1 == l2


def make_equal_mask_batch(rng, vocab, masked_per_example=3):
    ids = rng.randint(0, vocab, size=(B, T)).astype(np.int32)
    mlm = np.full((B, T), -1, np.int32)
    for b in range(B):
        cols = rng.choice(T, size=masked_per_example, replace=False)
        mlm[b, cols] = ids[b, cols]
    return {"input_ids": jnp.asarray(ids),
            "token_type_ids": jnp.zeros((B, T), jnp.int32),
            "attention_mask": jnp.ones((B, T), jnp.int32),
            "mlm_labels": jnp.asarray(mlm),
            "nsp_labels": jnp.asarray(
                rng.randint(0, 2, size=(B,)).astype(np.int32))}


class TestMoESparseComposition:
    """Sparse DP x expert parallelism — completes sparse x {seq, pipe,
    expert}."""

    def _setup(self, cfg, params, compressor):
        from oktopk_tpu.config import OkTopkConfig
        from oktopk_tpu.optim.sgd import sgd
        from oktopk_tpu.parallel.bert_moe import (
            build_moe_sparse_train_step, init_moe_sparse_opt,
            init_moe_sparse_states)
        from oktopk_tpu.parallel.bert_seq import stack_replicas

        dp, ep = 2, 4
        moe, shared = experts_from_dense(params, E, gate_scale=0.5, seed=3)
        moe = perturb(moe)
        mcfg = MoEConfig(num_experts=E, capacity_factor=float(E))
        mesh = make_moe_mesh(ep, data_size=dp)
        acfg = OkTopkConfig(density=0.05, warmup_steps=0,
                            use_pallas=False)
        opt = sgd(lr=0.1)
        step = build_moe_sparse_train_step(
            cfg, mcfg, mesh, opt, acfg, compressor=compressor,
            warmup=False)
        sstates = init_moe_sparse_states(moe, shared, acfg, dp, ep)
        opts = init_moe_sparse_opt(opt, moe, shared, dp)
        pstack = (stack_replicas(moe, dp), stack_replicas(shared, dp))
        return step, pstack, sstates, opts, (moe, shared), mcfg, opt

    def test_dense_composition_matches_expert_only_step(self, cfg, params):
        """Equal per-row mask counts: mean-of-row gradients == global
        gradient, so the composed dense step must land on the same params
        as the expert-only build_moe_train_step."""
        from oktopk_tpu.parallel.bert_moe import build_moe_train_step

        (step, pstack, sstates, opts, (moe, shared), mcfg,
         opt) = self._setup(cfg, params, "dense")
        batch = make_equal_mask_batch(np.random.RandomState(31),
                                      cfg.vocab_size)
        (p_moe, p_sh), _, _, m = step(pstack, sstates, opts, batch)
        assert np.isfinite(float(m["loss"]))

        ref_step = build_moe_train_step(cfg, mcfg, make_moe_mesh(4), opt)
        (r_moe, r_sh), _, _ = ref_step((moe, shared),
                                       opt.init((moe, shared)), batch)
        for (pa, a), (_, b) in zip(
                jax.tree_util.tree_leaves_with_path(r_moe),
                jax.tree_util.tree_leaves_with_path(
                    jax.tree.map(lambda x: x[0], p_moe))):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=2e-6,
                err_msg=jax.tree_util.keystr(pa))
        for (pa, a), (_, b) in zip(
                jax.tree_util.tree_leaves_with_path(r_sh),
                jax.tree_util.tree_leaves_with_path(
                    jax.tree.map(lambda x: x[0], p_sh))):
            # tight: with the aux f/p stats global over data, the dense
            # composition equals the expert-only step to float noise
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=2e-6,
                err_msg=jax.tree_util.keystr(pa))

    def test_oktopk_composition_trains(self, cfg, params):
        (step, p, ss, opts, (moe, shared), mcfg, opt) = self._setup(
            cfg, params, "oktopk")
        batch = make_batch(np.random.RandomState(32), cfg.vocab_size)
        n_total = sum(x.size for x in jax.tree.leaves((moe, shared)))
        for i in range(3):
            p, ss, opts, m = step(p, ss, opts, batch)
            assert np.isfinite(float(m["loss"]))
        moe_ss, _ = ss
        assert int(np.asarray(moe_ss.step)[0, 0]) == 3
        vol = float(m["comm_volume"])
        assert 0 < vol < 2.0 * n_total, vol
        for leaf in jax.tree.leaves(p):
            np.testing.assert_array_equal(np.asarray(leaf[0]),
                                          np.asarray(leaf[1]))
