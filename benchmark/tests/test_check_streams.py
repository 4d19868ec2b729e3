"""``benchlib/check.py`` streams: what ``follow`` and ``compare_exchange``
hold on the device beside a model that fills the chip, and that every
number is what it was when they worked on whole trees.

The whole-tree functions of the parent (commit b747489) are kept here as
the oracle, not in ``benchlib``."""

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchlib import check

from test_correct import (CELLS, X4, interpreted_kernels,  # noqa: F401
                          settled, short_settling)


# ---- the oracle: the parent's whole-tree functions ----------------------

def old_worker_grads(ref, spec, opt, precision):
    clip = opt.get("grad_clip")

    def one(params, batch, extra):
        l, g = jax.value_and_grad(ref.loss)(params, batch, spec, extra)
        if clip is not None:
            norm = jnp.sqrt(sum(jnp.sum(x ** 2) for x in jax.tree.leaves(g)))
            scale = jnp.minimum(1.0, clip / (norm + 1e-12))
            g = jax.tree.map(lambda x: x * scale, g)
        return l, g

    jitted = jax.jit(one)

    def all_workers(params, batch, workers, skey):
        rows = len(next(iter(batch.values()))) // workers
        out = []
        with jax.default_matmul_precision(precision or "default"):
            for w in range(workers):
                shard = {k: jnp.asarray(v[w * rows:(w + 1) * rows])
                         for k, v in batch.items()}
                extra = ref.extras(spec, shard, check.worker_key(skey, w))
                out.append(jitted(params, shard, extra))
        return out

    return all_workers


def old_follow(ref, spec, opt, batches, workers, key0, snaps, precision):
    lr, m, wd = float(opt["lr"]), float(opt["momentum"]), float(
        opt["weight_decay"])
    grads = old_worker_grads(ref, spec, opt, precision)
    losses, d1, change = [], None, None
    for t, (batch, skey) in enumerate(
            zip(batches, check.step_keys(key0, len(batches))), start=1):
        p, buf = jax.tree.map(jnp.asarray, snaps.states[t - 1])
        per = grads(p, batch, workers, skey)
        losses.append(sum(float(l) for l, _ in per) / workers)
        gsum = per[0][1]
        for _, g in per[1:]:
            gsum = jax.tree.map(jnp.add, gsum, g)
        ghat = jax.tree.map(lambda x: x / workers, gsum)
        d = jax.tree.map(lambda g, x: g + wd * x, ghat, p)
        step = jax.tree.map(lambda b, x: m * b + x, buf, d) if m else d
        if t == 1:
            d1 = jax.device_get(d)
        delta = jax.tree.map(lambda b: -lr * b, step)
        change = delta if change is None else jax.tree.map(
            jnp.add, change, delta)
    return losses, d1, jax.device_get(change)


def old_compare_exchange(ref, spec, opt, workers, key0, snap, precision):
    from benchlib import exchange
    skey = check.step_keys(key0, snap.step_index + 1)[-1]
    per = old_worker_grads(ref, spec, opt, precision)(
        jax.tree.map(jnp.asarray, snap.before[0]), snap.batch, workers, skey)
    grads = [jax.tree.leaves(g) for _, g in per]
    theirs = jax.tree.leaves(
        check.recovered_ghat(snap.before, snap.after, opt))
    numbers, over = {}, 0
    for b in snap.buckets:
        flat = lambda leaves: jnp.concatenate(
            [jnp.asarray(leaves[i], jnp.float32).ravel() for i in b.leaves])
        acc = jnp.stack([flat(g) for g in grads]) + jnp.asarray(
            b.residual_before)
        lt = (np.asarray(b.local_threshold, np.float32)
              * np.asarray(b.drift, np.float32))
        gt = (np.asarray(b.global_threshold, np.float32)
              * np.asarray(b.drift, np.float32))
        got, o = exchange.compare(
            flat(theirs), b.residual_after, acc, lt, gt, b.boundaries,
            snap.wire_dtype, b.cap_pair, b.cap_gather)
        over += o
        for k, v in got.items():
            numbers[k] = max(v, numbers.get(k, 0.0))
    return numbers, over


# ---- (i) the footprint ---------------------------------------------------

LEAVES, LEAF = 32, 8192          # a tree of 32 equal leaves, 1 MiB in all
TREE_BYTES = 4 * LEAVES * LEAF
ROWS = 8                         # a global batch: noise beside a leaf


def live_trees() -> float:
    """Bytes of every live array on any device, in parameter trees."""
    return sum(a.nbytes for a in jax.live_arrays()) / TREE_BYTES


def fake_reference(seen):
    """A model of ``LEAVES`` leaves whose ``extras`` (called as each
    gradient call starts) notes what is live on the device."""
    def extras(spec, batch, key):
        seen.append(live_trees())
        return None

    def loss(params, batch, spec, extra):
        x = jnp.mean(batch["x"])
        return sum(jnp.sum((v - x) ** 2) * (i + 1)
                   for i, v in enumerate(jax.tree.leaves(params))) / LEAF

    return types.SimpleNamespace(loss=loss, extras=extras)


def fake_case(momentum, workers, seed=3):
    rng = np.random.default_rng(seed)
    tree = lambda: {f"l{i:02d}": rng.standard_normal(LEAF, np.float32)
                    for i in range(LEAVES)}
    states = [(tree(), tree() if momentum else None) for _ in range(4)]
    snaps = check.Snapshots(states=states, losses=[1.0, 1.0, 1.0])
    batches = [{"x": rng.standard_normal((ROWS, 4), np.float32)}
               for _ in range(3)]
    opt = {"lr": 0.1, "momentum": momentum, "weight_decay": 5e-4,
           "grad_clip": None}
    return opt, batches, workers, jax.random.PRNGKey(seed), snaps


@pytest.mark.parametrize("workers", [1, 4])
@pytest.mark.parametrize("momentum", [0.9, 0.0])
def test_follow_holds_two_trees_at_a_gradient_call_and_three_at_most(
        monkeypatch, momentum, workers):
    opt, batches, workers, key0, snaps = fake_case(momentum, workers)
    at_calls, ever = [], []
    base = live_trees()     # what other tests of the process left behind

    # every place where follow adds a gradient in or takes a leaf home
    real_acc, real_host = check._accumulate, check._host
    monkeypatch.setattr(check, "_accumulate", lambda t, n: (
        ever.append(live_trees()), real_acc(t, n))[1])
    monkeypatch.setattr(check, "_host", lambda x: (
        ever.append(live_trees()), real_host(x))[1])
    new = check.follow(fake_reference(at_calls), None, opt, batches,
                       workers, key0, snaps, None)
    assert len(at_calls) == 3 * workers and len(ever) >= 3 * LEAVES
    # the parameters, and the running sum once a worker has been added;
    # the slack is a leaf's terms (at most six leaves of 32) and the batch
    assert max(at_calls) - base < (2 if workers > 1 else 1) + 0.25, at_calls
    assert max(ever) - base < 3 + 0.25, max(ever)

    # the parent's: every name it bound was alive into the next step
    old_calls = []
    old = old_follow(fake_reference(old_calls), None, opt, batches, workers,
                     key0, snaps, None)
    assert max(old_calls) - base >= (8 if momentum else 6), old_calls
    assert_same(new, old)


def test_compare_exchange_holds_the_parameters_alone_at_a_gradient_call():
    opt, batches, workers, key0, snaps = fake_case(0.9, 4)
    n = LEAVES * LEAF
    rng = np.random.default_rng(5)
    bucket = check.Bucket(
        leaves=list(range(LEAVES)),
        residual_before=rng.standard_normal((workers, n), np.float32),
        residual_after=rng.standard_normal((workers, n), np.float32),
        local_threshold=np.full(workers, 2.0, np.float32),
        global_threshold=np.full(workers, 4.0, np.float32),
        drift=np.ones(workers, np.float32),
        boundaries=np.arange(workers + 1) * (n // workers),
        cap_pair=n, cap_gather=n)
    snap = check.ExchangeSnapshot(
        step_index=5, batch=batches[0], before=snaps.states[0],
        after=snaps.states[1], buckets=[bucket], wire_dtype="bfloat16")
    base, seen, old_seen = live_trees(), [], []
    new = check.compare_exchange(fake_reference(seen), None, opt, workers,
                                 key0, snap, None)
    old = old_compare_exchange(fake_reference(old_seen), None, opt, workers,
                               key0, snap, None)
    assert new == old
    assert len(seen) == workers and max(seen) - base < 1.25, seen
    assert max(old_seen) - base >= workers, old_seen


# ---- (ii) every number is what it was -----------------------------------

def assert_same(new, old):
    """Bit for bit: the losses, and every leaf of the two trees."""
    assert new[0] == old[0]
    for a, b in zip(new[1:], old[1:]):
        assert jax.tree.structure(a) == jax.tree.structure(b)
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
            x, y = np.asarray(x), np.asarray(y)
            assert x.dtype == y.dtype == np.float32
            assert np.array_equal(x.view(np.uint32), y.view(np.uint32))


@pytest.mark.parametrize("cell", CELLS + [X4], ids=lambda c: str(
    c if isinstance(c, str) else c["name"]))
def test_numbers_are_bit_identical_to_the_whole_tree_check(monkeypatch, cell):
    bench, config, h = settled(cell, 2 ** 31 + 9)
    ref = bench.reference(config["reference"])
    tr = h.trainer
    opt = {"lr": tr.cfg.lr, "momentum": tr.cfg.momentum,
           "weight_decay": tr.cfg.weight_decay, "grad_clip": tr.cfg.grad_clip}
    args = (ref, config["spec"], opt, h.first_batches, h.workers, h.key0,
            h.snaps, config.get("reference_precision"))
    assert_same(check.follow(*args), old_follow(*args))
    new = check.compare(*args)
    monkeypatch.setattr(check, "follow", old_follow)
    assert check.compare(*args) == new
    if h.train_cfg.compressor != "dense":
        snap = h.exchange_step()
        args = (ref, config["spec"], opt, h.workers, h.key0, snap,
                config.get("reference_precision"))
        assert check.compare_exchange(*args) == old_compare_exchange(*args)


# ---- (iv) the fifth seeding rule ----------------------------------------

def test_a_stack_of_expert_kernels_is_seeded_by_each_experts_fan_in():
    from benchlib import weights
    shapes = {"moe": {"experts": jax.ShapeDtypeStruct((8, 256, 64),
                                                      jnp.float32),
                      "kernel": jax.ShapeDtypeStruct((256, 8), jnp.float32)},
              "norm": {"scale": jax.ShapeDtypeStruct((256,), jnp.float32)}}
    p = weights.make_params(shapes, 7)
    e = np.asarray(p["moe"]["experts"])
    assert e.shape == (8, 256, 64)
    # variance 1/256, not the 1/(8 * 256) a ``kernel`` of that shape draws
    assert abs(e.std() * 16.0 - 1.0) < 0.02
    assert abs(e[3].std() * 16.0 - 1.0) < 0.05 and abs(e.mean()) < 1e-3
    assert abs(np.asarray(p["moe"]["kernel"]).std() * 16.0 - 1.0) < 0.05
    again = weights.make_params(shapes, 7)
    assert np.array_equal(e, np.asarray(again["moe"]["experts"]))
    with pytest.raises(ValueError):
        weights.make_params({"w": jax.ShapeDtypeStruct((4, 4), jnp.float32)},
                            7)
