"""Parity tests for the Pallas stream-compaction fast path
(ops/compaction.py) against the portable ops/select.py implementation.

Runs the kernel in interpret mode on CPU (the compiled path is exercised by
tests/test_tpu_hw.py and chip_smoke.py on hardware); the contract is identical:
(values[cap], indices[cap], count), ascending index order, sentinel n,
overflow dropped lowest-index-first (plus the documented per-block CAPB
bound)."""

import numpy as np
import pytest

import jax.numpy as jnp

from oktopk_tpu.ops.compaction import BLK, select_by_threshold_pallas
from oktopk_tpu.ops.select import select_by_threshold

# `pytest -m kernels` runs the Pallas parity suites standalone during
# kernel iteration (pytest.ini)
pytestmark = pytest.mark.kernels


def run_both(x, thresh, cap):
    got = select_by_threshold_pallas(jnp.asarray(x), thresh, cap,
                                     interpret=True)[:3]
    want = select_by_threshold(jnp.asarray(x), thresh, cap)
    return [np.asarray(g) for g in got], [np.asarray(w) for w in want]


class TestCompactionParity:
    @pytest.mark.parametrize("n", [BLK, 3 * BLK, 4 * BLK + 777])
    def test_matches_portable_select(self, n):
        rng = np.random.RandomState(0)
        x = rng.randn(n).astype(np.float32)
        t = 2.0                      # ~2.3% of N(0,1) passes
        cap = max(64, int(0.05 * n))
        (gv, gi, gc), (wv, wi, wc) = run_both(x, t, cap)
        assert gc == wc
        np.testing.assert_array_equal(gi, wi)
        np.testing.assert_array_equal(gv, wv)

    def test_bit_exact_values(self):
        rng = np.random.RandomState(1)
        # adversarial float bit patterns: subnormals excluded (threshold),
        # but mixed signs/exponents must come back bit-exact through the
        # staging offsets + value gather
        x = (rng.randn(2 * BLK) * 10.0 ** rng.randint(-6, 6, 2 * BLK))
        x = x.astype(np.float32)
        t = float(np.quantile(np.abs(x), 0.97))
        (gv, gi, gc), (wv, wi, wc) = run_both(x, t, 4096)
        assert gc == wc
        np.testing.assert_array_equal(gv.view(np.int32), wv.view(np.int32))

    def test_cap_overflow_drops_tail(self):
        rng = np.random.RandomState(2)
        x = rng.randn(4 * BLK).astype(np.float32)
        t = 0.5                      # ~62% pass -> far over cap
        cap = 256
        (gv, gi, gc), (wv, wi, wc) = run_both(x, t, cap)
        assert gc == wc == cap
        # lowest-index-first retention identical to the portable path
        np.testing.assert_array_equal(gi, wi)
        np.testing.assert_array_equal(gv, wv)

    def test_empty_selection(self):
        x = np.zeros(2 * BLK, np.float32)
        gv, gi, gc, _ = [np.asarray(a) for a in
                         select_by_threshold_pallas(jnp.asarray(x), 1.0, 128,
                                                    interpret=True)]
        assert gc == 0
        assert (gi == x.size).all()
        assert (gv == 0).all()

    def test_fully_dense_block(self):
        """cap >= BLK: a fully dense block is retained whole."""
        x = np.ones(2 * BLK, np.float32)
        x[BLK:] = 0.0
        (gv, gi, gc), (wv, wi, wc) = run_both(x, 0.5, 2 * BLK)
        assert gc == wc == BLK
        np.testing.assert_array_equal(gi, wi)
        np.testing.assert_array_equal(gv, wv)

    def test_repair_branch_scattered_overflow(self):
        """A few scattered overflowing blocks (0 < novf <= _novf_cap):
        the repair-kernel branch, mixed 128/1024-wide staging layout."""
        from oktopk_tpu.ops.compaction import CAPB_FAST, _novf_cap

        rng = np.random.RandomState(11)
        n = 64 * BLK
        cap = 8 * BLK
        x = rng.randn(n).astype(np.float32) * 0.1
        for b in (3, 17, 40):            # ~5% of blocks, far over CAPB_FAST
            x[b * BLK:(b + 1) * BLK] = rng.randn(BLK) * 10 + 20
        # the repair branch condition of select_by_threshold_pallas,
        # asserted directly: some blocks overflow the fast staging in a
        # way that matters, but fewer than the repair-list capacity
        raw = (np.abs(x.reshape(-1, BLK)) >= 1.0).sum(axis=1)
        excl = np.cumsum(raw) - raw
        novf = int(((raw > CAPB_FAST) & (excl + CAPB_FAST < cap)).sum())
        assert 0 < novf <= _novf_cap(64)
        (gv, gi, gc), (wv, wi, wc) = run_both(x, 1.0, cap)
        assert gc == wc
        np.testing.assert_array_equal(gi, wi)
        np.testing.assert_array_equal(gv, wv)

    def test_wide_fallback_when_repair_list_overflows(self):
        """More overflowing blocks than the repair-list capacity
        (novf > _novf_cap): the full-width re-stage fallback."""
        from oktopk_tpu.ops.compaction import CAPB_FAST, _novf_cap

        rng = np.random.RandomState(12)
        n = 16 * BLK
        assert _novf_cap(16) == 8
        # randn*0.5 + 20 guarantees |x| >= 1 everywhere (min ~ 20 - 5*0.5):
        # the earlier randn*10 + 20 left 158/16384 elements below threshold
        # with seed 12, breaking the full-density assumption (ADVICE r5)
        x = (rng.randn(n).astype(np.float32) * 0.5 + 20)  # all blocks dense
        # the wide-fallback branch condition, asserted directly: every
        # block overflows the fast staging, far beyond the repair list
        raw = (np.abs(x.reshape(16, BLK)) >= 1.0).sum(axis=1)
        assert (raw > CAPB_FAST).sum() > _novf_cap(16)
        (gv, gi, gc), (wv, wi, wc) = run_both(x, 1.0, n)
        assert gc == wc == n
        np.testing.assert_array_equal(gi, wi)
        np.testing.assert_array_equal(gv, wv)

    def test_range_restriction(self):
        rng = np.random.RandomState(3)
        x = rng.randn(3 * BLK).astype(np.float32)
        lo, hi = BLK // 2, 2 * BLK + 17
        gv, gi, gc, _ = [np.asarray(a) for a in
                         select_by_threshold_pallas(
                          jnp.asarray(x), 2.0, 512,
                          lo=jnp.int32(lo), hi=jnp.int32(hi),
                          interpret=True)]
        want = np.where(np.abs(x) >= 2.0)[0]
        want = want[(want >= lo) & (want < hi)]
        assert gc == len(want)
        np.testing.assert_array_equal(gi[:gc], want)
        np.testing.assert_array_equal(gv[:gc], x[want])


class TestPackRegionsParity:
    """Single-sweep multi-region kernel vs the portable pack_by_region."""

    @pytest.mark.parametrize("bounds", [
        [0, 1024, 2048, 3072],          # block-aligned
        [0, 700, 1930, 3072],           # unaligned
        [0, 64, 80, 3072],              # tiny regions inside one block
        [0, 0, 1500, 3072],             # empty first region
    ])
    def test_matches_portable(self, bounds):
        from oktopk_tpu.ops.compaction import pack_by_region_pallas
        from oktopk_tpu.ops.select import pack_by_region

        n = 3 * BLK
        rng = np.random.RandomState(5)
        x = rng.randn(n).astype(np.float32)
        t, cap = 1.0, 256
        R = len(bounds) - 1
        b = jnp.asarray(bounds, jnp.int32)
        gv, gi, gc, _ = [np.asarray(a) for a in pack_by_region_pallas(
            jnp.asarray(x), t, b, R, cap, interpret=True)]
        wv, wi, wc = [np.asarray(a) for a in pack_by_region(
            jnp.asarray(x), jnp.abs(jnp.asarray(x)) >= t, b, R, cap)]
        np.testing.assert_array_equal(gc, wc)
        np.testing.assert_array_equal(gi, wi)
        np.testing.assert_array_equal(gv, wv)

    def test_repair_branch_with_straddling_boundary(self):
        """An overflowing block that also contains a region boundary: the
        straddle row must be fetched from the repaired (1024-wide) staging,
        not the truncated fast row."""
        from oktopk_tpu.ops.compaction import pack_by_region_pallas
        from oktopk_tpu.ops.select import pack_by_region

        from oktopk_tpu.ops.compaction import CAPB_FAST, _novf_cap

        rng = np.random.RandomState(13)
        n = 16 * BLK
        x = rng.randn(n).astype(np.float32) * 0.1
        x[5 * BLK:6 * BLK] = rng.randn(BLK) * 10 + 20     # block 5 dense
        # pack's repair branch condition (ovf = raw > CAPB_FAST), directly
        raw = (np.abs(x.reshape(-1, BLK)) >= 1.0).sum(axis=1)
        assert 0 < int((raw > CAPB_FAST).sum()) <= _novf_cap(16)
        # boundary inside the dense block, past the 128 fast-staged slots
        b = jnp.asarray([0, 5 * BLK + 700, n], jnp.int32)
        gv, gi, gc, _ = [np.asarray(a) for a in pack_by_region_pallas(
            jnp.asarray(x), 1.0, b, 2, 2 * BLK, interpret=True)]
        wv, wi, wc = [np.asarray(a) for a in pack_by_region(
            jnp.asarray(x), jnp.abs(jnp.asarray(x)) >= 1.0, b, 2, 2 * BLK)]
        np.testing.assert_array_equal(gc, wc)
        np.testing.assert_array_equal(gi, wi)
        np.testing.assert_array_equal(gv, wv)

    def test_cap_overflow_per_region(self):
        from oktopk_tpu.ops.compaction import pack_by_region_pallas
        from oktopk_tpu.ops.select import pack_by_region

        n = 2 * BLK
        rng = np.random.RandomState(6)
        x = rng.randn(n).astype(np.float32)
        b = jnp.asarray([0, n // 2, n], jnp.int32)
        gv, gi, gc, _ = [np.asarray(a) for a in pack_by_region_pallas(
            jnp.asarray(x), 0.3, b, 2, 64, interpret=True)]  # far over cap
        wv, wi, wc = [np.asarray(a) for a in pack_by_region(
            jnp.asarray(x), jnp.abs(jnp.asarray(x)) >= 0.3, b, 2, 64)]
        np.testing.assert_array_equal(gc, wc)
        np.testing.assert_array_equal(gi, wi)
        np.testing.assert_array_equal(gv, wv)


def _run_oktopk_both_paths(mesh8, cfg0, base, steps, check_vma=None):
    """Run the full oktopk step for use_pallas False/True on the same data;
    returns ({use_pallas: [per-step results]}, {use_pallas: final state}).
    ``check_vma=None``: off for the Pallas path — the interpreter cannot
    mix VMA-tracked operands (tests/test_tpu_hw.py passes True: compiled
    through Mosaic it can)."""
    from oktopk_tpu.collectives.api import (batched_init_state,
                                            build_allreduce_step)

    outs, states = {}, {}
    for up in (False, True):
        cfg = cfg0.replace(use_pallas=up)
        step = build_allreduce_step(
            "oktopk", cfg, mesh8, warmup=False,
            check_vma=(not up) if check_vma is None else check_vma)
        state = batched_init_state(cfg)
        rs = []
        for _ in range(steps):
            out, state = step(jnp.asarray(base), state)
            rs.append(np.asarray(out[0]))
        outs[up], states[up] = rs, state
    return outs, states


class TestOkTopkPallasParity:
    # slow: the full oktopk step through the Pallas INTERPRETER (4 steps x
    # 2 selection paths each) is ~2 min on the CPU mesh; the kernel-level
    # parity (every dispatch branch) stays in the tier-1 classes above,
    # and the algorithm-level wiring is also exercised on real hardware
    # via tests/test_tpu_hw.py.
    @pytest.mark.slow
    def test_full_algorithm_matches_portable(self, mesh8, monkeypatch):
        """The whole oktopk step with the Pallas selection path (interpret
        mode) must produce the same reduced result, volumes and state as
        the portable path when counts sit inside the capacity bounds."""
        monkeypatch.setenv("OKTOPK_PALLAS_INTERPRET", "1")
        from oktopk_tpu.config import OkTopkConfig

        P, n = 8, 8192
        rng = np.random.RandomState(4)
        base = rng.randn(P, n).astype(np.float32)
        cfg0 = OkTopkConfig(n=n, num_workers=P, density=0.05,
                            warmup_steps=0, local_recompute_every=2,
                            global_recompute_every=4)
        outs, states = _run_oktopk_both_paths(mesh8, cfg0, base, steps=4)
        for a, b in zip(outs[False], outs[True]):
            np.testing.assert_allclose(a, b, atol=1e-6)
        np.testing.assert_allclose(
            np.asarray(states[False].last_volume),
            np.asarray(states[True].last_volume))
        np.testing.assert_allclose(
            np.asarray(states[False].residual),
            np.asarray(states[True].residual), atol=1e-6)

    @pytest.mark.slow
    def test_full_algorithm_overflow_takes_wide_path(self, mesh8,
                                                     monkeypatch):
        """Spatially concentrated gradients overflow the CAPB_FAST staging
        in the hot blocks, so the algorithm-level step must take the
        capb=BLK wide-kernel cond branch under shard_map — and still match
        the portable path. (The unit tests exercise overflow outside
        shard_map; this pins the cond wiring inside the real step.)"""
        monkeypatch.setenv("OKTOPK_PALLAS_INTERPRET", "1")
        from oktopk_tpu.config import OkTopkConfig
        from oktopk_tpu.ops.compaction import CAPB_FAST

        P, n = 8, 8192
        rng = np.random.RandomState(9)
        # hot first block: far more than CAPB_FAST survivors land in one
        # 1024-element block; elsewhere near-silence
        base = 0.01 * rng.randn(P, n).astype(np.float32)
        base[:, :BLK] = 10.0 * rng.randn(P, BLK).astype(np.float32)
        cfg0 = OkTopkConfig(n=n, num_workers=P, density=0.2,
                            warmup_steps=0, local_recompute_every=2,
                            global_recompute_every=4)
        assert cfg0.cap_pair > CAPB_FAST   # overflow can matter => wide path
        outs, _ = _run_oktopk_both_paths(mesh8, cfg0, base, steps=3)
        # the wide branch really fired: more than CAPB_FAST of the hot
        # block's elements made the global result, so its raw survivor
        # count (a superset) must have exceeded the fast staging width
        assert (outs[False][0][:BLK] != 0).sum() > CAPB_FAST
        for a, b in zip(outs[False], outs[True]):
            assert np.isfinite(a).all()
            np.testing.assert_allclose(a, b, atol=1e-6)
