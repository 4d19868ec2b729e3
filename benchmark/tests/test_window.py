"""Percentile and window arithmetic on synthetic stamps; the window of
whole controller periods replayed on a recorded run; the feed."""

import json
import os
import types

import numpy as np
import pytest

from benchlib import harness, traffic, window


def test_rate_is_all_steps_over_all_time():
    stamps = [1.0 + 0.1 * i for i in range(1, 101)]      # 100 steps, 10 s
    assert window.rate(1.0, stamps, 256) == pytest.approx(100 * 256 / 10.0)


def test_every_step_but_the_first_gives_one_reading():
    stamps = [0.1 * i for i in range(1, 32)]             # 0.1 s steps
    times = window.step_times(stamps)
    assert len(times) == 30
    assert all(t == pytest.approx(0.1) for t in times)
    assert window.summary(0.0, stamps) == (31, pytest.approx(3.1), 30)


def test_one_slow_step_shows_as_itself():
    stamps, t = [], 0.0
    for i in range(40):
        t += 0.3 if i in (10, 20, 30) else 0.1
        stamps.append(t)
    times = window.step_times(stamps)
    assert max(times) == pytest.approx(0.3)
    assert window.percentile(times, 50) == pytest.approx(0.1)
    # three slow steps of 39 are the 95th percentile's business
    assert window.percentile(times, 95) > 0.25


def test_no_step_no_reading():
    assert window.step_times([]) == [] and window.step_times([5.0]) == []


@pytest.mark.parametrize("q,want", [(0, 1.0), (50, 3.0), (95, 4.8), (100, 5.0)])
def test_percentile_is_linear_between_order_statistics(q, want):
    assert window.percentile([5.0, 1.0, 3.0, 2.0, 4.0], q) == pytest.approx(want)


def test_percentile_of_nothing_raises():
    with pytest.raises(ValueError):
        window.percentile([], 50)


# ---- the window of whole controller periods (harness.Harness.window) ----

RECORDED = os.path.join(os.path.dirname(__file__), "data",
                        "lstm_ptb_oktopk_x1_steps.json")


def recorded_gaps():
    """Seconds of every step of one recorded run on the chip from job step 3
    on (``tools/seed_band.py``, one seed of PR 37's Step 1)."""
    with open(RECORDED) as f:
        rec = json.load(f)
    return rec["first_job_step"], [ms / 1e3 for ms in rec["ms"]]


class Replay:
    """A Harness whose steps take what a recorded run's took: the clock
    moves when a step's result is waited for, by that step's gap."""

    def __init__(self, monkeypatch, traffic_spec, cadences=(32, 32, 64)):
        first, gaps = recorded_gaps()
        self.h = h = object.__new__(harness.Harness)
        h.traffic = traffic_spec
        h.trainer = types.SimpleNamespace(algo_cfg=types.SimpleNamespace(
            local_recompute_every=cadences[0],
            global_recompute_every=cadences[1],
            repartition_every=cadences[2]))
        h.cadence = h._cadence()
        h.steps_done = first
        h.feed = iter(lambda: {}, None)
        h.compiles = types.SimpleNamespace(count=0)
        self.now, self.gaps, self.first = 100.0, gaps, first
        self.waited = set()
        h.step = self.step
        h.trainer.train_step = None
        monkeypatch.setattr(harness.time, "perf_counter", lambda: self.now)
        monkeypatch.setattr(harness.jax, "block_until_ready", self.wait)

    def step(self, batch):
        i = self.h.steps_done
        self.h.steps_done += 1
        return {"loss": np.float32(i), "comm_volume": np.float32(0),
                "global_k": np.float32(0)}

    def wait(self, loss):
        i = int(loss)
        if i not in self.waited:     # a step completes once
            self.waited.add(i)
            self.now += self.gaps[i - self.first]


NAMES = ["local_recompute_every", "global_recompute_every",
         "repartition_every"]
PERIODS = {"settle_steps": 61, "window": {"whole_periods_of": NAMES}}
TWO = {"settle_steps": 61,
       "window": {"whole_periods_of": NAMES, "periods": 2}}


def test_whole_period_window_on_a_recorded_run(monkeypatch):
    r = Replay(monkeypatch, PERIODS)
    r.h.settle()
    assert r.h.steps_done == 64          # 3 + 61, a multiple of lcm(32, 32, 64)
    win = r.h.window(30.0)
    assert win.first_step == 64 and win.first_step % 64 == 0
    assert len(win.stamps) % 32 == 0 and len(win.stamps) == 96
    assert win.stamps[-1] - win.t0 <= 30.0
    # one more period would have ended past the clock
    _, gaps = recorded_gaps()
    assert sum(gaps[61:61 + 128]) > 30.0
    # the steps timed are the job's steps 64..159, each with its own gap
    assert list(win.losses) == list(range(64, 160))
    assert window.step_times(win.stamps) == pytest.approx(gaps[62:61 + 96])


def test_the_traffic_file_counts_the_periods(monkeypatch):
    """``periods: 2``: job steps 64..127 whatever the clock would allow,
    and fewer only where two would end past it."""
    r = Replay(monkeypatch, TWO)
    r.h.settle()
    win = r.h.window(30.0)
    assert win.first_step == 64 and list(win.losses) == list(range(64, 128))
    _, gaps = recorded_gaps()
    assert win.stamps[-1] - win.t0 == pytest.approx(sum(gaps[61:61 + 64]))
    assert win.stamps[-1] - win.t0 <= 30.0
    r = Replay(monkeypatch, TWO)
    r.h.settle()
    win = r.h.window(12.0)               # two periods take 18 s
    assert len(win.stamps) == 32 and win.stamps[-1] - win.t0 <= 12.0


def test_settling_goes_on_to_where_the_periods_start(monkeypatch):
    r = Replay(monkeypatch, dict(PERIODS, settle_steps=62))
    r.h.settle()
    assert r.h.steps_done == 128
    r = Replay(monkeypatch, dict(PERIODS, settle_steps=10), (16, 16, 16))
    r.h.settle()
    assert r.h.steps_done == 16
    assert len(r.h.window(30.0).stamps) % 16 == 0


def test_a_window_too_short_for_a_period_still_holds_one(monkeypatch):
    r = Replay(monkeypatch, TWO)
    r.h.settle()
    assert len(r.h.window(2.0).stamps) == 32


def test_the_clocks_window_is_unchanged_where_no_period_is_named(monkeypatch):
    """A dense traffic file: ``settle_steps`` steps, then the window ends
    at the first completion past ``seconds``, one step being in flight."""
    r = Replay(monkeypatch, {"settle_steps": 4})
    assert r.h.cadence is None
    r.h.settle()
    assert r.h.steps_done == 7
    win = r.h.window(10.0)
    _, gaps = recorded_gaps()
    cum = np.cumsum(gaps[4:])
    past = int(np.argmax(cum >= 10.0))     # index of the first stamp past 10 s
    assert len(win.stamps) == past + 2     # and the step in flight behind it
    assert win.stamps[-2] - win.t0 >= 10.0 > win.stamps[-3] - win.t0
    assert win.first_step == 7


def test_max_steps_overrides_the_periods(monkeypatch):
    r = Replay(monkeypatch, TWO)
    r.h.settle()
    assert len(r.h.window(0.0, max_steps=14).stamps) == 14


def test_the_accepted_traffic_files():
    """oktopk: two periods of the trainer's three cadences from job step
    64; dense names no period."""
    from benchlib import discover
    bench = discover.Bench()
    sparse = bench.traffic("oktopk")
    assert sparse["window"] == {"whole_periods_of": NAMES, "periods": 2}
    assert 3 + sparse["settle_steps"] == 64
    assert "window" not in bench.traffic("dense")


@pytest.mark.parametrize("elapsed,stamped,sent,seconds,last", [
    (8.8, 31, 32, 30.0, False),     # 9.08 s a period: a second fits
    (18.0, 63, 64, 30.0, False),    # 18.3 s for two: a third ends at 27.4
    (27.0, 95, 96, 30.0, True),     # 27.3 s for three: a fourth at 36.4
    (7.4, 31, 32, 15.0, True),      # 7.64 s a period: two end at 15.3
    (7.2, 31, 32, 15.0, False)])    # 7.43: two end at 14.9
def test_last_period(elapsed, stamped, sent, seconds, last):
    assert window.last_period(elapsed, stamped, sent, 32, seconds) is last
    with pytest.raises(ValueError):
        window.last_period(elapsed, stamped, sent + 1, 32, seconds)


# ---- a stream that does not repeat inside a run (traffic.Feed) ----------

TOKENS = {"kind": "tokens", "vocab": 1000, "seq_len": 35}


def digest(batch):
    return tuple(sorted((k, v.tobytes()) for k, v in batch.items()))


def test_feed_hands_out_every_batch_before_any_repeats():
    feed = traffic.Feed(TOKENS, {"distinct_batches": 256}, 8, 2147483999)
    first = [digest(next(feed)) for _ in range(256)]
    assert len(set(first)) == 256
    again = [digest(next(feed)) for _ in range(256)]
    assert set(again) == set(first) and again != first   # the seed's order


def test_feed_is_the_seeds():
    one = traffic.Feed(TOKENS, {"distinct_batches": 256}, 8, 2147483999)
    two = traffic.Feed(TOKENS, {"distinct_batches": 256}, 8, 2147483999)
    other = traffic.Feed(TOKENS, {"distinct_batches": 256}, 8, 2147483998)
    a, b, c = ([digest(next(f)) for _ in range(300)] for f in (one, two, other))
    assert a == b and not set(a) & set(c)


# ---- the step at k delivered values (metrics/step_at_k_ms.py) ------------

def at_k(first, count, traffic_spec=TWO, density=0.02):
    """The reader on steps [first, first + count) of the recorded run."""
    from benchlib import discover
    with open(RECORDED) as f:
        rec = json.load(f)
    lo = first - rec["first_job_step"]
    stamps = list(np.cumsum(rec["ms"][lo:lo + count]) / 1e3)
    ctx = discover.Context(
        traffic=traffic_spec, n=66_022_000,
        window=types.SimpleNamespace(
            stamps=stamps, first_step=first,
            delivered=np.asarray(rec["delivered"][lo:lo + count], float)),
        algo_cfg=types.SimpleNamespace(
            density=density, local_recompute_every=32,
            global_recompute_every=32, repartition_every=64))
    read = discover.load_module(os.path.join(
        discover.HERE, "metrics", "step_at_k_ms.py")).read
    return read(ctx)


def test_step_at_k_on_a_recorded_run():
    """Seed 2147370101 on the chip: the window's steps 64..127 read a median
    of 279.6 ms at a median count of 1.49 M; at k = 1.32 M the line reads
    270.2, and a traced window's fourteen steps from 68 within 4 ms of it."""
    whole = at_k(64, 64)
    assert whole == pytest.approx(270.2, abs=0.3)
    assert at_k(68, 14) == pytest.approx(whole, abs=4.0)
    # an exact step inside the window (96: 421 ms) is left out, not fitted
    assert at_k(90, 14) == pytest.approx(whole, abs=4.0)
    # more values delivered take longer
    assert at_k(64, 64, density=0.025) > whole + 10.0


def test_step_at_k_has_nothing_to_read():
    assert at_k(64, 64, {"settle_steps": 4}) is None      # no cadence named
    assert at_k(68, 4) is None                            # too few steps
    assert window.line_at([1.0] * 9, list(range(9)), 1.0) is None


def test_line_at_does_not_follow_a_late_stamp():
    xs = [1.0 + 0.05 * i for i in range(12)]
    ys = [200.0 + 60.0 * x for x in xs]
    assert window.line_at(xs, ys, 1.32) == pytest.approx(279.2)
    ys[5] += 60.0       # the stamp came late ...
    ys[6] -= 60.0       # ... so the next gap is short by as much
    assert window.line_at(xs, ys, 1.32) == pytest.approx(279.2, abs=1.0)
