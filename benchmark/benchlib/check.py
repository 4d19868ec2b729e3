"""What decides ``correct``, number by number, each against a limit of its
own. Two parts, so that each can fail alone.

**The model's math and the optimizer**: the program's first three steps
against the plain reference's. The program side is a ``Snapshots`` record
that the harness fills while it drives the compiled step through its first
three steps (host copies, so the device's peak stays the program's). The
three are warm-up steps, dense in every cell (the traffic files of the
sparse cells set ``warmup_steps`` to 3 for this). The reference side takes
the same three global batches and the same benchmark-made weights, and
follows the program step by step: each of its steps starts from the state
the program had before that step (see ``follow``).

    per worker i:  loss_i, g_i = d loss / d params  on its rows
                   (clipped to ``grad_clip`` by global norm where the
                    configuration clips)
    ghat = mean_i g_i
    SGD:           d = ghat + wd * p ; buf = m * buf + d ; p -= lr * buf

- ``loss_gap``: the largest |program - reference| / |reference| over the
  three steps' losses.
- ``grad1_gap``: the first gradient as the optimizer gets it (the momentum
  buffer after one step, d above), by the worst leaf: the gap between the
  two norms over the reference's norm of that leaf or of the median leaf,
  whichever is larger.
- ``dparam3_gap``: the same for the change of the parameters over the three
  steps.
- ``grad1_diff_q1``, ``dparam3_diff_q1``: of the same two trees, the norm
  of (program - reference) a leaf, over the same denominator, at the first
  quartile of the leaves (``first_quartile_diff`` says why).

**The exchange** (sparse cells): one predicted-threshold step of the
regime the window ran in, taken right after the window, against
``benchlib/exchange.py``: ``delivered_gap``, ``residual_gap``,
``support_mismatch`` (defined there), with each worker's gradient from the
plain model reference. And over the window's steps:

- ``delivered_share`` and ``delivered_share_min``: the mean count of
  delivered coordinates a step over k = density * n, held from both sides.
- ``volume_share``: the mean ``comm_volume`` over the paper's 6k budget (a
  steady-state budget: the step that recomputes the thresholds exactly, one
  in 32, may send more: ``volume_step_max_share`` holds the largest single
  step at about three times what sound runs read).
- ``replica_gap`` (several chips): parameters differ between chips: exact.
- ``nonfinite_steps``, ``window_compiles``: steps of the window with a
  non-finite loss, and compilations inside it: exact, both 0.
"""

from __future__ import annotations

import dataclasses
import statistics
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass
class Snapshots:
    """Host copies taken around the program's first three steps."""
    # (params, momentum buffers or None) before step 1 and after each step
    states: List[Any] = dataclasses.field(default_factory=list)
    losses: List[float] = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class Bucket:
    """One bucket of the sparse state round the compared step. ``leaves``
    are the indices of the parameter leaves it holds, in its order."""
    leaves: List[int]
    residual_before: np.ndarray      # [P, n_b]
    residual_after: np.ndarray       # [P, n_b]
    local_threshold: np.ndarray      # [P], before the step
    global_threshold: np.ndarray     # [P]
    drift: np.ndarray                # [P]
    boundaries: np.ndarray           # [P + 1]
    cap_pair: int
    cap_gather: int


@dataclasses.dataclass
class ExchangeSnapshot:
    """Host copies round one predicted-threshold step."""
    step_index: int                  # steps the trainer had taken before it
    batch: Any
    before: Any                      # (params, momentum buffers or None)
    after: Any
    buckets: List[Bucket]
    wire_dtype: str


def _leaf_norms(tree) -> List[float]:
    return [float(np.linalg.norm(np.asarray(x, np.float64).ravel()))
            for x in jax.tree.leaves(tree)]


def leaf_gaps(prog, ref) -> List[float]:
    """A leaf's gap: |program's norm - reference's norm| over the
    reference's norm of that leaf or of the median leaf, whichever is
    larger (some gradients are all but zero)."""
    a, b = _leaf_norms(prog), _leaf_norms(ref)
    floor = statistics.median(b)
    return [abs(x - y) / max(y, floor, 1e-30) for x, y in zip(a, b)]


def leaf_diffs(prog, ref) -> List[float]:
    """A leaf's difference: the norm of (program - reference) over the
    reference's norm of that leaf or of the median leaf."""
    b = _leaf_norms(ref)
    floor = statistics.median(b)
    d = _leaf_norms(_sub(prog, ref))
    return [x / max(y, floor, 1e-30) for x, y in zip(d, b)]


def worst_leaf_gap(prog, ref) -> float:
    return max(leaf_gaps(prog, ref))


def first_quartile_diff(prog, ref) -> float:
    """The leaf a quarter of the way up the sorted ``leaf_diffs``. In a deep
    batch-normalised network at seeded weights the backward pass amplifies
    rounding some thousandfold in most leaves, in sound runs and in
    lower-precision ones alike; the best-conditioned quarter (the head, the
    last layers) is where the two part, and this reads steadily there."""
    d = sorted(leaf_diffs(prog, ref))
    return d[len(d) // 4]


def step_keys(key0, steps: int):
    """The key each step hands the model, as ``Trainer.train_step`` splits
    it: ``state, key = split(state)``."""
    keys, state = [], key0
    for _ in range(steps):
        state, k = jax.random.split(state)
        keys.append(k)
    return keys


def worker_key(step_key, worker: int):
    """... and as the step derives a worker's: fold in the worker's index,
    split once, take the second."""
    return jax.random.split(jax.random.fold_in(step_key, worker))[1]


def _sub(a, b):
    return jax.tree.map(lambda x, y: np.asarray(x) - np.asarray(y), a, b)


def worker_grads(ref, spec, opt, precision: Optional[str]):
    """A function (params, global batch, workers, step key, worker) -> that
    worker's loss and gradient on its own rows, clipped where the
    configuration clips, as the optimizer's collective gets it. One worker
    a call, so that the caller holds no more gradients than it needs."""
    clip = opt.get("grad_clip")

    def one(params, batch, extra):
        l, g = jax.value_and_grad(ref.loss)(params, batch, spec, extra)
        if clip is not None:
            norm = jnp.sqrt(sum(jnp.sum(x ** 2) for x in jax.tree.leaves(g)))
            scale = jnp.minimum(1.0, clip / (norm + 1e-12))
            g = jax.tree.map(lambda x: x * scale, g)
        return l, g

    jitted = jax.jit(one)

    def one_worker(params, batch, workers: int, skey, w: int):
        rows = len(next(iter(batch.values()))) // workers
        with jax.default_matmul_precision(precision or "default"):
            shard = {k: jnp.asarray(v[w * rows:(w + 1) * rows])
                     for k, v in batch.items()}
            extra = ref.extras(spec, shard, worker_key(skey, w))
            return jitted(params, shard, extra)

    return one_worker


def _host(x) -> np.ndarray:
    return np.asarray(jax.device_get(x))


def _accumulate(total: List[Any], new: List[Any]) -> None:
    """``total[i] += new[i]``, a leaf at a time; ``new`` is emptied as it
    is read, so that a leaf's two terms and its sum are all that is held
    beside the two lists."""
    for i in range(len(total)):
        total[i] = jnp.add(total[i], new[i])
        new[i] = None


def follow(ref, spec, opt, batches, workers: int, key0, snaps: Snapshots,
           precision: Optional[str]):
    """The reference's three steps, each from the state the program had
    before it (the job's first steps at its stated learning rate are
    chaotic: a copy that runs free parts from the program by rounding
    alone, and then measures the chaos). Returns the losses, what the
    optimizer got at step 1, and the sum of the three changes of the
    parameters.

    What it holds on the device (the reference runs beside the program's
    own state, and a model that fills the chip leaves little room): the
    parameters of the step in hand and the running sum of the workers'
    gradients, two parameter-sized trees at every gradient call, a third
    (the call's own output) until it is added in. The optimizer's algebra
    then runs a leaf at a time, each result taken to the host as it is
    made and each term freed as it is read: float32 on the device, the
    same operations in the same order as on whole trees."""
    lr, m, wd = float(opt["lr"]), float(opt["momentum"]), float(
        opt["weight_decay"])
    one_worker = worker_grads(ref, spec, opt, precision)
    losses, d1, change = [], None, None
    treedef = jax.tree.structure(snaps.states[0][0])
    for t, (batch, skey) in enumerate(
            zip(batches, step_keys(key0, len(batches))), start=1):
        p_host, buf_host = snaps.states[t - 1]
        p = jax.tree.map(jnp.asarray, p_host)
        loss, gsum = 0, None
        for w in range(workers):
            l, g = one_worker(p, batch, workers, skey, w)
            loss += float(l)
            if gsum is None:
                gsum = jax.tree.leaves(g)
            else:
                _accumulate(gsum, jax.tree.leaves(g))
            del g
        losses.append(loss / workers)
        p = jax.tree.leaves(p)
        buf = jax.tree.leaves(buf_host) if m else None
        d_host, delta_host = [], []
        for i in range(len(p)):
            d = gsum[i] / workers + wd * p[i]
            gsum[i] = p[i] = None
            if t == 1:
                d_host.append(_host(d))
            delta = -lr * (m * jnp.asarray(buf[i]) + d if m else d)
            if change is not None:
                delta = jnp.add(jnp.asarray(change[i]), delta)
            delta_host.append(_host(delta))
        del d, delta    # a leaf each: the next gradient call need not find them
        change = delta_host
        if t == 1:
            d1 = jax.tree.unflatten(treedef, d_host)
    return losses, d1, jax.tree.unflatten(treedef, change)


def compare(ref, spec, opt, batches, workers: int, key0, snaps: Snapshots,
            precision: Optional[str],
            detail: Optional[Dict[str, Any]] = None) -> Dict[str, float]:
    """The numbers of the three-step comparison (no limits applied).
    ``detail``, if given, is filled with the per-step and per-leaf
    readings behind them."""
    lr, m = float(opt["lr"]), float(opt["momentum"])
    losses, d1, dp3 = follow(ref, spec, opt, batches, workers, key0, snaps,
                             precision)
    (p0, _), (p1, buf1) = snaps.states[0], snaps.states[1]
    d1_prog = buf1 if m else jax.tree.map(lambda x: x / lr, _sub(p0, p1))
    dp3_prog = _sub(snaps.states[-1][0], p0)
    out = {
        "loss_gap": max(abs(a - b) / abs(b)
                        for a, b in zip(snaps.losses, losses)),
        "grad1_gap": worst_leaf_gap(d1_prog, d1),
        "dparam3_gap": worst_leaf_gap(dp3_prog, dp3),
        "grad1_diff_q1": first_quartile_diff(d1_prog, d1),
        "dparam3_diff_q1": first_quartile_diff(dp3_prog, dp3),
    }
    if detail is not None:
        detail["losses"] = [snaps.losses, losses]
        detail["grad1_leaf_gaps"] = leaf_gaps(d1_prog, d1)
        detail["dparam3_leaf_gaps"] = leaf_gaps(dp3_prog, dp3)
        detail["grad1_leaf_diffs"] = leaf_diffs(d1_prog, d1)
        detail["dparam3_leaf_diffs"] = leaf_diffs(dp3_prog, dp3)
        detail["grad1_norms"] = [_leaf_norms(d1_prog), _leaf_norms(d1)]
        detail["dparam3_norms"] = [_leaf_norms(dp3_prog), _leaf_norms(dp3)]
    if not all(np.isfinite(snaps.losses)):
        out["loss_gap"] = float("inf")
    return out


def recovered_ghat(before, after, opt):
    """What the optimizer was handed, leaf by leaf, from the parameters
    and the momentum round one step of SGD."""
    lr, m, wd = (float(opt[k]) for k in ("lr", "momentum", "weight_decay"))
    (pa, bufa), (pb, bufb) = before, after
    if m:
        return jax.tree.map(
            lambda b1, b0, p: np.asarray(b1) - m * np.asarray(b0)
            - wd * np.asarray(p), bufb, bufa, pa)
    return jax.tree.map(
        lambda p0, p1: (np.asarray(p0) - np.asarray(p1)) / lr
        - wd * np.asarray(p0), pa, pb)


def compare_exchange(ref, spec, opt, workers: int, key0,
                     snap: ExchangeSnapshot, precision: Optional[str]):
    """``benchlib/exchange.py``'s numbers for the step of ``snap``, the
    worst over its buckets, and the count of capacities passed. Each
    worker's gradient goes to the host as it is made (the parameters are
    all that the gradient calls find on the device), and comes back a
    bucket at a time."""
    from benchlib import exchange
    skey = step_keys(key0, snap.step_index + 1)[-1]
    one_worker = worker_grads(ref, spec, opt, precision)
    p = jax.tree.map(jnp.asarray, snap.before[0])
    grads = []
    for w in range(workers):
        g = jax.tree.leaves(one_worker(p, snap.batch, workers, skey, w)[1])
        grads.append([_host(g.pop(0)) for _ in range(len(g))])
    del p
    theirs = jax.tree.leaves(recovered_ghat(snap.before, snap.after, opt))
    numbers: Dict[str, float] = {}
    over = 0
    for b in snap.buckets:
        flat = lambda leaves: np.concatenate(
            [np.asarray(leaves[i], np.float32).ravel() for i in b.leaves])
        acc = jnp.stack([jnp.asarray(flat(g)) + jnp.asarray(r)
                         for g, r in zip(grads, b.residual_before)])
        lt = (np.asarray(b.local_threshold, np.float32)
              * np.asarray(b.drift, np.float32))
        gt = (np.asarray(b.global_threshold, np.float32)
              * np.asarray(b.drift, np.float32))
        got, o = exchange.compare(
            flat(theirs), b.residual_after, acc, lt, gt, b.boundaries,
            snap.wire_dtype, b.cap_pair, b.cap_gather)
        del acc
        over += o
        for k, v in got.items():
            numbers[k] = max(v, numbers.get(k, 0.0))
    return numbers, over


def judge(numbers: Dict[str, float], limits: Dict[str, Any]):
    """Each number beside its limit. A limit is an upper one unless its
    name ends in ``_min``. A number without a limit, or a limit whose
    number is missing, fails: nothing is compared in silence."""
    lines, ok = [], True
    for name in sorted(set(numbers) | set(limits)):
        value, limit = numbers.get(name), limits.get(name)
        if value is None or limit is None:
            good = False
        elif name.endswith("_min"):
            good = value >= limit and np.isfinite(value)
        else:
            good = value <= limit and np.isfinite(value)
        ok = ok and good
        lines.append(f"check {name}: {value!r} against limit {limit!r}"
                     f" -> {'ok' if good else 'NOT ok'}")
    return ok, lines
