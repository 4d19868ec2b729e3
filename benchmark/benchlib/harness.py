"""Drives the program from outside: the objects a user's job drives.

``TrainConfig`` + ``OkTopkConfig`` -> ``Trainer(cfg, mesh, algo_cfg)`` ->
``Trainer.train_step(batch)``: the construction of
``oktopk_tpu/train/main_trainer.py::main`` (lines 199-275 at commit
669e046), with autotune, resilience, obs and checkpoints off as they are by
default. ``main`` itself runs a fixed number of iterations, not a window.

This is the only module of the benchmark that imports the program.
"""

from __future__ import annotations

import dataclasses
import math
import time
from typing import Any, Dict, List, NamedTuple, Optional

import jax
import numpy as np

from benchlib import check, traffic, weights
from benchlib import window as window_lib

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
FIRST_STEPS = 3   # the steps the plain reference follows
EXCHANGE_TRIES = 3  # steps compared until one stays inside its capacities


class CompileCounter:
    """Counts back-end compilations through jax's own monitoring events
    (the pattern of ``chip_smoke.Counters``)."""

    def __init__(self):
        import jax.monitoring as mon
        self.count = 0
        mon.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, **_):
        if event == COMPILE_EVENT:
            self.count += 1


class Cadence(NamedTuple):
    """A window of whole periods, in steps: it starts on a multiple of
    ``align``, is cut every ``period`` and holds ``most`` periods at the
    most (0: as many as the clock lets end)."""
    align: int
    period: int
    most: int


@dataclasses.dataclass
class Window:
    t0: float
    stamps: List[float]
    losses: np.ndarray
    volumes: np.ndarray
    delivered: np.ndarray
    compiles: int
    spans: List[tuple]      # (name, start, end) of the host, traced runs only
    first_step: int = 0     # steps the job had done before the window's first


def _dataclass_kwargs(cls, *layers: Dict[str, Any]) -> Dict[str, Any]:
    """Keys of the data files that are fields of ``cls``; an unknown key is
    an error, so that a typing slip is not a silent default."""
    fields = {f.name: f for f in dataclasses.fields(cls)}
    out: Dict[str, Any] = {}
    for layer in layers:
        for k, v in layer.items():
            if k not in fields:
                raise KeyError(f"{cls.__name__} has no field {k!r}")
            out[k] = tuple(v) if isinstance(v, list) else v
    return out


class Harness:
    def __init__(self, cell, config, traffic_spec, seed: int,
                 rehearsal: bool = False):
        from oktopk_tpu.comm.mesh import get_mesh
        from oktopk_tpu.config import OkTopkConfig, TrainConfig
        from oktopk_tpu.train.trainer import Trainer

        self.cell, self.config, self.traffic = cell, config, traffic_spec
        self.rehearsal = rehearsal
        self.chips = int(cell["chips"])
        devices = jax.devices()[:self.chips]
        mesh_spec = traffic_spec.get("mesh", {})
        self.mesh = get_mesh(
            tuple(mesh_spec.get("shape", (self.chips,))),
            tuple(mesh_spec.get("axis_names", ("data",))), devices=devices)
        self.devices = devices
        train = _dataclass_kwargs(
            TrainConfig, config["train"], traffic_spec.get("train", {}),
            {"seed": weights.seed32(seed), "num_workers": self.chips})
        algo = _dataclass_kwargs(
            OkTopkConfig, config.get("algo", {}), traffic_spec.get("algo", {}))
        self.train_cfg = TrainConfig(**train)
        self.trainer = Trainer(
            self.train_cfg, mesh=self.mesh, algo_cfg=OkTopkConfig(**algo),
            model_kwargs=config.get("model_kwargs"))
        self.workers = self.trainer.algo_cfg.num_workers
        self.n = self.trainer.algo_cfg.n
        self.global_batch = self.train_cfg.batch_size * self.workers
        self.compiles = CompileCounter()
        self.snaps: Optional[check.Snapshots] = None
        self.feed: Optional[traffic.Feed] = None
        self.key0 = None
        self.cadence = self._cadence()

    def _cadence(self) -> Optional[Cadence]:
        """Where the traffic file asks for a window of whole periods
        (``window.whole_periods_of``: fields of the trainer's own
        ``OkTopkConfig``, read from it and written nowhere else): the
        window starts where all of them fall together (their least common
        multiple), is cut by the shortest and holds ``window.periods`` of
        it at the most. None for a traffic file that names none: the
        clock's window."""
        spec = self.traffic.get("window", {})
        names = spec.get("whole_periods_of")
        if not names:
            return None
        every = [int(getattr(self.trainer.algo_cfg, n)) for n in names]
        return Cadence(math.lcm(*every), min(every),
                       int(spec.get("periods", 0)))

    # ---- state from the seed ------------------------------------------

    def seed_state(self, seed: int) -> None:
        """Benchmark-made weights (``benchlib/weights.py``: one jitted call
        on the device) put in the place of those the trainer drew for
        itself, and the traffic. Everything else of the state, momentum,
        residuals and thresholds, is the trainer's own construction, as
        it made it: nothing has stepped yet. The plain reference takes no
        weights that the program made; that is what this is for."""
        from jax.sharding import NamedSharding, PartitionSpec
        tr = self.trainer
        shapes = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tr.state.params)
        # the trainer's own parameters are freed before the new are made:
        # two copies at once would be the harness's peak, not the program's
        state, tr.state = tr.state.replace(params=None), None
        tr.state = state.replace(params=weights.make_params(
            shapes, seed, NamedSharding(self.mesh, PartitionSpec())))
        # the key the trainer's steps start from, as its constructor
        # derives it from the configuration's seed
        self.key0 = jax.random.PRNGKey(self.train_cfg.seed + 1)
        self.steps_done = 0
        self.feed = traffic.Feed(self.config["input"],
                                 self.traffic.get("stream", {}),
                                 self.global_batch, seed)
        self.snaps = None

    def step(self, batch):
        """The one call every step of a run goes through."""
        self.steps_done += 1
        return self.trainer.train_step(batch)

    # ---- the first steps, copied to the host for the check ------------

    def _host_state(self):
        st = self.trainer.state
        return jax.device_get((st.params, st.opt_state.momentum_buf))

    def first_steps(self) -> None:
        """Steps 1-3 through the window's own call and feed, the state
        copied to the host after each. All three are warm-up steps, dense
        in every cell (a sparse cell's traffic file sets ``warmup_steps``
        to 3 or more; ``numbers`` refuses less)."""
        snaps = check.Snapshots(states=[self._host_state()])
        self.first_batches = []
        for _ in range(FIRST_STEPS):
            batch = next(self.feed)
            self.first_batches.append(batch)
            m = self.step(batch)
            snaps.losses.append(float(np.asarray(m["loss"])))
            snaps.states.append(self._host_state())
        self.snaps = snaps

    def settle(self) -> None:
        """Past the first predicted-threshold steps, so that the window is
        in the regime a job spends its life in; and, where the window is
        whole periods, on to the job step where the periods start, so that
        every run of the cell times the same steps of the job."""
        m = None
        for _ in range(int(self.traffic.get("settle_steps", 4))):
            m = self.step(next(self.feed))
        while self.cadence and self.steps_done % self.cadence.align:
            m = self.step(next(self.feed))
        if m is not None:
            jax.block_until_ready(m["loss"])

    # ---- the window ---------------------------------------------------

    def window(self, seconds: float, max_steps: Optional[int] = None,
               annotate: bool = False) -> Window:
        """Queue one step deep, as ``Trainer.train``: dispatch step i, then
        wait for step i-1 and stamp its completion. The clock ends it at
        the first completion past ``seconds``. Where the traffic file asks
        for whole periods it ends with a period: the one the file counts
        to, or an earlier one where the periods before it say that the
        next would end past ``seconds`` (never before the first).
        ``max_steps`` overrides both."""
        step, feed = self.step, self.feed
        cadence = self.cadence if max_steps is None else None
        first_step = self.steps_done
        note = (jax.profiler.TraceAnnotation if annotate else None)
        metrics, stamps, spans = [], [], []
        clock = time.perf_counter
        compiled_before = self.compiles.count
        prev = None
        t0 = clock()
        while True:
            if annotate:
                a = clock()
                with note("bench/data"):
                    batch = next(feed)
                b = clock()
                with note("bench/dispatch"):
                    m = step(batch)
                c = clock()
                spans += [("data", a, b), ("dispatch", b, c)]
            else:
                m = step(next(feed))
            metrics.append(m)
            if prev is not None:
                if annotate:
                    with note("bench/block"):
                        jax.block_until_ready(prev["loss"])
                    spans.append(("block", c, clock()))
                else:
                    jax.block_until_ready(prev["loss"])
                stamps.append(clock())
            prev = m
            if max_steps is not None and len(metrics) >= max_steps:
                break
            if cadence:
                if stamps and len(metrics) % cadence.period == 0 and (
                        len(metrics) == cadence.most * cadence.period
                        or window_lib.last_period(
                            stamps[-1] - t0, len(stamps), len(metrics),
                            cadence.period, seconds)):
                    break
            elif max_steps is None and stamps and stamps[-1] - t0 >= seconds:
                break
        jax.block_until_ready(prev["loss"])
        stamps.append(clock())
        if annotate:
            spans.append(("block", c, stamps[-1]))
        host = jax.device_get([(m["loss"], m["comm_volume"], m["global_k"])
                               for m in metrics])
        cols = np.asarray(host, np.float64).reshape(len(metrics), 3)
        return Window(t0, stamps, cols[:, 0], cols[:, 1], cols[:, 2],
                      self.compiles.count - compiled_before, spans,
                      first_step)

    # ---- one step of the exchange, copied to the host for the check ----

    def _sparse_states(self):
        st = self.trainer.state.sparse_state
        return list(st) if isinstance(st, tuple) else [st]

    def _next_step_predicts(self) -> bool:
        """Will the next step use the thresholds it was handed (neither an
        exact recompute nor a repartition, nor the first sparse step)?"""
        cfg = self.trainer.algo_cfg
        s = int(np.asarray(self._sparse_states()[0].step).ravel()[0])
        every = (cfg.local_recompute_every, cfg.global_recompute_every,
                 cfg.repartition_every)
        return s > cfg.warmup_steps and all(s % e for e in every)

    def exchange_step(self) -> check.ExchangeSnapshot:
        """One more step of the run, a predicted-threshold one, through the
        same call and feed, with the state round it copied to the host."""
        from oktopk_tpu.optim.distributed import bucket_partition
        tr = self.trainer
        while not self._next_step_predicts():
            self.step(next(self.feed))
        before = self._host_state()
        pre = jax.device_get(self._sparse_states())
        index, batch = self.steps_done, next(self.feed)
        jax.block_until_ready(self.step(batch)["loss"])
        post = jax.device_get([sp.residual for sp in self._sparse_states()])
        parts = bucket_partition(tr.state.params, len(pre))
        sizes = [x.size for x in jax.tree.leaves(tr.state.params)]
        buckets = []
        for leaves, sp, after in zip(parts, pre, post):
            cfg = tr.algo_cfg.replace(n=sum(sizes[i] for i in leaves))
            buckets.append(check.Bucket(
                leaves=list(leaves), residual_before=sp.residual,
                residual_after=after, local_threshold=sp.local_threshold,
                global_threshold=sp.global_threshold, drift=sp.drift,
                boundaries=np.asarray(sp.boundaries)[0],
                cap_pair=cfg.cap_pair, cap_gather=cfg.cap_gather))
        return check.ExchangeSnapshot(
            step_index=index, batch=batch, before=before,
            after=self._host_state(), buckets=buckets,
            wire_dtype=tr.algo_cfg.wire_dtype)

    # ---- the check ---------------------------------------------------

    def replica_gap(self) -> float:
        """Largest difference of any parameter between chips."""
        worst = 0.0
        for leaf in jax.tree.leaves(self.trainer.state.params):
            shards = [np.asarray(s.data) for s in leaf.addressable_shards]
            for s in shards[1:]:
                worst = max(worst, float(np.max(np.abs(s - shards[0]))))
        return worst

    def numbers(self, reference, win: Optional[Window],
                precision: Optional[str],
                detail: Optional[Dict[str, Any]] = None) -> Dict[str, float]:
        """Every number ``correct`` is decided by, program against
        reference. Call after the window: the reference runs on the same
        device, so the program's peak has to be read before; and the
        exchange is compared on the step that follows the window."""
        from benchlib import volume
        tr = self.trainer
        sparse = self.train_cfg.compressor != "dense"
        if sparse and tr.algo_cfg.warmup_steps < FIRST_STEPS:
            raise ValueError(
                f"warmup_steps={tr.algo_cfg.warmup_steps}: the first "
                f"{FIRST_STEPS} steps are compared as dense steps")
        opt = {"lr": tr.cfg.lr, "momentum": tr.cfg.momentum,
               "weight_decay": tr.cfg.weight_decay,
               "grad_clip": tr.cfg.grad_clip}
        out = check.compare(reference, self.config["spec"], opt,
                            self.first_batches, self.workers, self.key0,
                            self.snaps, precision, detail)
        if self.workers > 1:
            out["replica_gap"] = self.replica_gap()
        if sparse:
            for _ in range(EXCHANGE_TRIES):
                got, over = check.compare_exchange(
                    reference, self.config["spec"], opt, self.workers,
                    self.key0, self.exchange_step(), precision)
                if not over:
                    break
                print(f"exchange: {over} capacities passed on step "
                      f"{self.steps_done}; comparing another", flush=True)
            out.update(got)
        if win is not None:
            out["nonfinite_steps"] = float(np.sum(~np.isfinite(win.losses)))
            out["window_compiles"] = float(win.compiles)
            if sparse:
                budget = volume.budget_scalars(
                    self.train_cfg.compressor, self.n, tr.cfg.density)
                out["volume_share"] = float(np.mean(win.volumes) / budget)
                out["volume_step_max_share"] = float(
                    np.max(win.volumes) / budget)
                share = float(np.mean(win.delivered)
                              / (tr.cfg.density * self.n))
                out["delivered_share"] = out["delivered_share_min"] = share
        return out

    def memory_peak_bytes(self) -> int:
        """The fullest chip's peak: the allocator's ``peak_bytes_in_use``
        (arrays) plus its ``peak_bytes_reserved``, which on this back end is
        where a loaded program's temporaries live (PR 23 read 328 MB in use
        beside 1,660 MB reserved for a step program whose
        ``memory_analysis`` gives 1,672 MB of temporaries)."""
        peaks = []
        for d in self.devices:
            s = d.memory_stats() or {}
            peaks.append(s.get("peak_bytes_in_use", 0)
                         + s.get("peak_bytes_reserved", 0))
        return int(max(peaks))
