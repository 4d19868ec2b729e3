"""A dense bucket is reduced a leaf at a time (optim/distributed.py).

Where a bucket's collective is the registry's ``dense_allreduce`` itself
and no option of the step reads the bucket's flat vector, the step builds
none: the bucket's leaves go to ONE ``pmean`` as a tuple, and the
accounting is ``dense_allreduce``'s own line. These tests hold the
leaf-wise step to the flat one (the same ``dense_allreduce`` behind a
wrapper, which the step cannot tell from any other algorithm and hands a
flat vector), read the lowered and the compiled program for the
concatenate that went and the all-reduces that stayed, and check what
``snapshot()["capacities"]`` says of each bucket.
"""

import re

import jax
import numpy as np
import pytest

from oktopk_tpu.autotune.policy import BucketPlan
from oktopk_tpu.collectives import registry
from oktopk_tpu.collectives.dense import dense_allreduce
from oktopk_tpu.comm import get_mesh
from oktopk_tpu.config import TrainConfig
from oktopk_tpu.data.synthetic import synthetic_iterator
from oktopk_tpu.optim.distributed import bucket_partition, bucket_sizes
from oktopk_tpu.resilience.faults import FaultPlan, FaultSpec
from oktopk_tpu.train.trainer import Trainer
from oktopk_tpu.utils import profiling

BATCH = 8
PLAIN_SGD = dict(momentum=0.0, weight_decay=0.0)
STEP_METRICS = ("loss", "grad_norm", "grad_nonfinite", "comm_volume",
                "wire_bytes", "local_k", "global_k", "counters")


@pytest.fixture(scope="module")
def meshes(devices, mesh4):
    return {1: get_mesh((1,), ("data",), devices=devices[:1]), 4: mesh4}


def make(mesh, num_buckets=1, compressor="dense", warmup=False,
         algo=None, **kw):
    cfg_kw = {k: kw.pop(k) for k in list(kw)
              if k in TrainConfig.__dataclass_fields__}
    cfg = TrainConfig(dnn="mnistnet", dataset="mnist", batch_size=BATCH,
                      lr=0.05, compressor=compressor, density=0.05,
                      num_buckets=num_buckets, **cfg_kw)
    tr = Trainer(cfg, mesh=mesh, warmup=warmup, **kw)
    if algo:
        tr.algo_cfg = tr.algo_cfg.replace(**algo)
        tr.step_fn = tr._build_step()
    return tr


def make_flat(mesh, num_buckets=1, **kw):
    """The flat form, called directly: the registry hands out the same
    ``dense_allreduce`` behind a wrapper, so the step sees an algorithm
    that is not the dense all-reduce itself and flattens the bucket."""
    def flat_dense(grad, state, cfg, axis_name="data"):
        assert grad.ndim == 1
        return dense_allreduce(grad, state, cfg, axis_name)

    with pytest.MonkeyPatch.context() as mp:
        mp.setitem(registry.ALGORITHMS, "dense", flat_dense)
        return make(mesh, num_buckets, **kw)


def batches(n, seed=0):
    it = synthetic_iterator("mnistnet", BATCH, seed)
    return [next(it) for _ in range(n)]


def leafwise(tr):
    return [c["leafwise"] for c in tr.capacities()]


def sizes_of(tr, min_leaves=1):
    """The buckets' element counts; ``min_leaves=2`` leaves out a bucket
    of one leaf, which is a reshape and no concatenate in either form."""
    params = tr.state.params
    buckets = [b for b in bucket_partition(params, tr.cfg.num_buckets)
               if len(b) >= min_leaves]
    return bucket_sizes(params, buckets)


def lowered(tr):
    return tr.step_fn.lower(tr.state, batches(1)[0], jax.random.PRNGKey(0))


def concat_sizes(tr, text=None):
    """The result sizes of every 1-d float32 concatenate in the lowered
    step."""
    return {int(m) for m in re.findall(
        r"stablehlo\.concatenate.*-> tensor<(\d+)xf32>",
        text or lowered(tr).as_text())}


def all_reduces(compiled_text):
    """The operand lists of the compiled program's all-reduces."""
    return [m.split(", ") for m in re.findall(
        r" all-reduce(?:-start)?\(([^)]*)\)", compiled_text)]


def assert_trees_bit_equal(a, b, what):
    la, ta = jax.tree.flatten(a)
    lb, tb = jax.tree.flatten(b)
    assert ta == tb, what
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                      err_msg=what)


def assert_metrics_bit_equal(ml, mf):
    assert set(ml) == set(mf) >= set(STEP_METRICS)
    for key in ml:
        np.testing.assert_array_equal(np.asarray(ml[key]),
                                      np.asarray(mf[key]), err_msg=key)


@pytest.mark.parametrize("num_buckets", [1, 3])
@pytest.mark.parametrize("workers", [1, 4])
def test_leafwise_steps_equal_flat_steps_bit_for_bit(meshes, workers,
                                                     num_buckets):
    """Three steps of plain SGD: every metric of every step, the new
    parameters and the buckets' ``SparseState`` are those of the flat
    path. (With momentum and weight decay XLA:CPU fuses the optimizer's
    multiply-adds differently in the two programs: the test below.)"""
    mesh = meshes[workers]
    leaf = make(mesh, num_buckets, **PLAIN_SGD)
    flat = make_flat(mesh, num_buckets, **PLAIN_SGD)
    assert leafwise(leaf) == [True] * num_buckets
    assert leafwise(flat) == [False] * num_buckets
    for batch in batches(3):
        ml, mf = leaf.train_step(batch), flat.train_step(batch)
        assert_metrics_bit_equal(ml, mf)
    for field in ("params", "model_state", "opt_state", "sparse_state"):
        assert_trees_bit_equal(getattr(leaf.state, field),
                               getattr(flat.state, field), field)
    n = leaf.algo_cfg.n
    assert float(ml["comm_volume"]) == 2.0 * n
    assert int(ml["local_k"]) == n and int(ml["global_k"]) == n
    sps = ([leaf.state.sparse_state] if num_buckets == 1
           else list(leaf.state.sparse_state))
    for sp, n_b in zip(sps, sizes_of(leaf)):
        assert int(sp.step[0]) == 3
        assert float(sp.last_volume[0]) == 2.0 * n_b
        assert int(sp.last_local_count[0]) == n_b
        assert not np.asarray(sp.last_counters).any()


@pytest.mark.parametrize("num_buckets", [1, 3])
def test_with_momentum_and_weight_decay_only_a_rounding_differs(
        mesh4, num_buckets):
    """At the default momentum and weight decay the reduced gradient and
    every metric of a step are still bit-equal. The optimizer's
    ``g + wd * p`` is one fused multiply-add in one program and a product
    and a sum in the other (XLA:CPU's choice, a fusion each), so the
    momentum buffer may differ in the last bit."""
    leaf, flat = make(mesh4, num_buckets), make_flat(mesh4, num_buckets)
    batch = batches(1)[0]
    assert_metrics_bit_equal(leaf.train_step(batch), flat.train_step(batch))
    assert_trees_bit_equal(leaf.state.sparse_state, flat.state.sparse_state,
                           "sparse_state")
    for x, y in zip(jax.tree.leaves(leaf.state.opt_state),
                    jax.tree.leaves(flat.state.opt_state)):
        x, y = np.asarray(x), np.asarray(y)
        np.testing.assert_allclose(x, y, rtol=0,
                                   atol=np.spacing(np.abs(y).max()))


@pytest.mark.parametrize("num_buckets", [1, 3])
def test_no_bucket_length_concatenate_and_no_more_all_reduces(mesh4,
                                                              num_buckets):
    """Several leaves a bucket (mnistnet's eight in one bucket, or two
    buckets of several and one of a single leaf). The lowered leaf-wise
    step holds no concatenate of a bucket's length, the flat one holds one
    a bucket. A ``pmean`` over a tuple lowers to an ``all_reduce`` a leaf; compiled for four workers,
    XLA's combiner leaves as many all-reduces as the flat step has, and
    ONE of them carries every gradient leaf (XLA:CPU merges all of a
    step's buckets, in either form)."""
    leaf, flat = make(mesh4, num_buckets), make_flat(mesh4, num_buckets)
    nbs = set(sizes_of(leaf, 2))
    assert len(nbs) == min(num_buckets, 2)
    low_leaf, low_flat = lowered(leaf), lowered(flat)
    text_leaf, text_flat = low_leaf.as_text(), low_flat.as_text()
    assert not nbs & concat_sizes(leaf, text_leaf)
    assert nbs <= concat_sizes(flat, text_flat)
    n_leaves = len(jax.tree.leaves(leaf.state.params))
    assert (text_leaf.count("stablehlo.all_reduce")
            - text_flat.count("stablehlo.all_reduce")
            == n_leaves - num_buckets)
    ar_leaf = all_reduces(low_leaf.compile().as_text())
    ar_flat = all_reduces(low_flat.compile().as_text())
    assert len(ar_leaf) == len(ar_flat)
    # the gradients and the loss
    assert max(map(len, ar_leaf)) == n_leaves + 1
    assert max(map(len, ar_flat)) == num_buckets + 1


def test_an_oktopk_step_with_warmup_still_builds_its_flat_vector(mesh4):
    """As ``lstm_ptb_oktopk_x1`` runs it: ``with_warmup``'s ``lax.cond``
    between the dense all-reduce and the sparse one needs one shape, so
    the bucket is flattened in the warm-up steps too."""
    tr = make(mesh4, 1, compressor="oktopk", warmup=True,
              algo=dict(warmup_steps=3))
    assert profiling.snapshot()["capacities"][-1]["leafwise"] is False
    assert leafwise(tr) == [False]
    assert set(sizes_of(tr)) <= concat_sizes(tr)
    for batch in batches(4):
        m = tr.train_step(batch)
    assert np.isfinite(float(m["loss"]))
    assert float(m["comm_volume"]) < 2.0 * tr.algo_cfg.n


def test_a_mixed_plan_takes_each_bucket_by_its_own_algorithm(mesh4):
    """The autotuner's and the supervisor's per-bucket plans: a dense
    bucket among sparse ones is reduced a leaf at a time, the others are
    flattened."""
    tr = make(mesh4, 2, compressor="oktopk")
    nbs = sizes_of(tr)
    tr._plans = [BucketPlan(b, n_b, algo, dens, 0.0, 0.0)
                 for b, (n_b, algo, dens) in enumerate(
                     zip(nbs, ["dense", "oktopk"], [1.0, 0.05]))]
    tr.step_fn = tr._build_step()
    assert [c["leafwise"] for c in
            profiling.snapshot()["capacities"][-2:]] == [True, False]
    got = concat_sizes(tr)
    assert nbs[0] not in got and nbs[1] in got
    for batch in batches(2):
        m = tr.train_step(batch)
    assert np.isfinite(float(m["loss"]))
    dense_sp, sparse_sp = tr.state.sparse_state
    assert int(dense_sp.step[0]) == 2 and int(sparse_sp.step[0]) == 2
    assert float(dense_sp.last_volume[0]) == 2.0 * nbs[0]
    assert float(sparse_sp.last_volume[0]) < 2.0 * nbs[1]


def _guard_saw_a_clean_step(tr, m):
    assert int(m["step_skipped"]) == 0
    assert np.isfinite(float(m["reduced_absmax"]))


def _fault_poisoned_the_step(tr, m):
    assert int(m["grad_nonfinite"]) > 0


def _quality_ring_took_a_row(tr, m):
    for qb in tr.state.quality:
        assert (np.asarray(qb.cursor) == 1).all()


def _momentum_buffer_is_flat_and_filled(tr, m):
    moms = tr.state.local_momentum
    for mom, n_b in zip(moms, sizes_of(tr)):
        assert mom.shape[-1] == n_b
        assert float(np.abs(np.asarray(mom)).max()) > 0.0


def _eps_of_dense_is_zero(tr, m):
    assert float(m["eps_vs_dense"]) == 0.0


READERS = {
    "guard": (dict(resilience=True), _guard_saw_a_clean_step),
    "fault_plan": (dict(fault_plan=FaultPlan((FaultSpec("nan_grad", 0),))),
                   _fault_poisoned_the_step),
    "quality": (dict(obs=True, obs_quality=True), _quality_ring_took_a_row),
    "momentum_correction": (dict(momentum_correction=True),
                            _momentum_buffer_is_flat_and_filled),
    "profile_norm": (dict(profile_norm=True), _eps_of_dense_is_zero),
}


@pytest.mark.parametrize("reader", sorted(READERS))
def test_a_reader_of_the_flat_vector_keeps_a_dense_bucket_flat(mesh4,
                                                               reader):
    """Each of the five options reads ``flat``: with ``compressor="dense"``
    its step falls back to the flat vector and gives what it gave."""
    kw, gave = READERS[reader]
    tr = make(mesh4, 2, **kw)
    assert leafwise(tr) == [False, False]
    assert set(sizes_of(tr, 2)) <= concat_sizes(tr)
    m = tr.train_step(batches(1)[0])
    assert float(m["comm_volume"]) == 2.0 * tr.algo_cfg.n
    gave(tr, m)
