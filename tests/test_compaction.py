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

from oktopk_tpu.ops.compaction import (BLK, CAPB_FAST, _novf_cap,
                                       select_by_threshold_pallas)
from oktopk_tpu.ops.select import select_by_threshold

# `pytest -m kernels` runs the Pallas parity suites standalone during
# kernel iteration (pytest.ini)
pytestmark = pytest.mark.kernels


def run_both(x, thresh, cap):
    got = select_by_threshold_pallas(jnp.asarray(x), thresh, cap,
                                     interpret=True)[:3]
    want = select_by_threshold(jnp.asarray(x), thresh, cap)
    return [np.asarray(g) for g in got], [np.asarray(w) for w in want]


# Survivor counts of the overflowing blocks of one vector, keyed by how many
# there are (novf): one page and one survivor (129), three pages (300), five
# (640) and all eight (1,024) — what the repair kernel's page gate splits
# on. 64 blocks make the repair list eight entries long, so 8 fills it.
REPAIR_NBLOCKS = 64
REPAIR_SURVIVORS = {
    1: (300,),
    2: (129, 1024),
    4: (129, 300, 640, 1024),
    8: (129, 300, 640, 1024, 1024, 640, 300, 129),
}


def overflow_vector(survivors, seed=21):
    """(x, blocks): ``survivors[i]`` elements of magnitude >= 5 at random
    places of block ``blocks[i]`` (block 0, which a padded list entry
    repeats, and the last block among them), three in every other block,
    the rest under 1: at threshold 1.0 exactly ``len(survivors)`` blocks
    overflow the fast staging width."""
    rng = np.random.RandomState(seed)
    nb = REPAIR_NBLOCKS
    assert _novf_cap(nb) == 8 and min(survivors) > CAPB_FAST
    x = (rng.rand(nb, BLK).astype(np.float32) - 0.5)
    blocks = np.linspace(0, nb - 1, len(survivors)).astype(int)
    count = np.full(nb, 3)
    count[blocks] = survivors
    for b in range(nb):
        at = rng.choice(BLK, count[b], replace=False)
        x[b, at] = (5.0 + rng.rand(count[b])) * rng.choice([-1.0, 1.0],
                                                           count[b])
    raw = (np.abs(x) >= 1.0).sum(axis=1)
    assert int((raw > CAPB_FAST).sum()) == len(survivors)
    return x.reshape(-1), blocks


def straddling_bounds(blocks):
    """Two regions whose boundary lies inside the last overflowing block,
    past its fast-staged slots."""
    return np.asarray([0, blocks[-1] * BLK + 700, REPAIR_NBLOCKS * BLK],
                      np.int32)


# ---- live-prefix cases (``_gather_live``) ------------------------------
# One geometry for every case, so that each fresh jit compiles once: 64
# blocks, CHUNK patched to 1,024, capacities that are no multiple of it
# (the last chunk starts early and overlaps the one before).
LP_NBLOCKS = 64
LP_CHUNK = 1024
LP_SELECT_CAP = 5000
LP_PACK_CAP = 2500
# blocks over the fast staging width, by branch: none; two (the first and
# the last block); ten, more than the repair list's eight
LP_HOT = {
    "fast": {},
    "repair": {0: 300, LP_NBLOCKS - 1: 129},
    "wide": {**{b: 129 for b in range(9)}, LP_NBLOCKS - 1: 300},
}
LP_BRANCH = {"fast": 0, "repair": 1, "wide": 2}
# survivor counts at the ends of a chunk (of the first where the branch's
# hot blocks leave room, else of the second), at cap and over it
LP_SELECT_COUNTS = {
    "fast": (0, 1, LP_CHUNK - 1, LP_CHUNK, LP_CHUNK + 1, LP_SELECT_CAP,
             LP_SELECT_CAP + 600),
    "repair": (LP_CHUNK - 1, LP_CHUNK, LP_CHUNK + 1, LP_SELECT_CAP,
               LP_SELECT_CAP + 600),
    "wide": (2 * LP_CHUNK - 1, 2 * LP_CHUNK, 2 * LP_CHUNK + 1,
             LP_SELECT_CAP, LP_SELECT_CAP + 600),
}
# per-region counts that end in different chunks: none, one, either side
# of a chunk's end, cap, over cap
LP_REGION_COUNTS = {
    "ends": (0, LP_CHUNK - 1, LP_CHUNK + 1, LP_PACK_CAP + 200),
    "full": (1, LP_CHUNK, LP_PACK_CAP, 300),
}


def counted_vector(block_counts, seed=31):
    """``block_counts[b]`` elements of magnitude >= 5 at random places of
    block b, the rest under 1."""
    rng = np.random.RandomState(seed)
    nb = len(block_counts)
    x = (rng.rand(nb, BLK).astype(np.float32) - 0.5)
    for b, c in enumerate(block_counts):
        at = rng.choice(BLK, c, replace=False)
        x[b, at] = (5.0 + rng.rand(c)) * rng.choice([-1.0, 1.0], c)
    return x.reshape(-1)


def prefix_vector(branch, total):
    """A vector of ``total`` survivors at threshold 1.0 whose overflowing
    blocks are ``LP_HOT[branch]``; the others share the rest evenly (empty
    blocks where ``total`` is small)."""
    hot = LP_HOT[branch]
    cool = [b for b in range(LP_NBLOCKS) if b not in hot]
    rest = total - sum(hot.values())
    assert 0 <= rest <= CAPB_FAST * len(cool)
    counts = np.zeros(LP_NBLOCKS, int)
    counts[cool] = rest // len(cool)
    counts[cool[:rest % len(cool)]] += 1
    for b, c in hot.items():
        counts[b] = c
    return counted_vector(counts)


def rank_bounds(x, region_counts):
    """Boundaries (unaligned) that give region r exactly
    ``region_counts[r]`` of ``x``'s survivors, the last region the rest."""
    at = np.flatnonzero(np.abs(x) >= 1.0)
    ends = np.cumsum(region_counts[:-1])
    assert sum(region_counts) == at.size
    return np.asarray([0, *at[ends], x.size], np.int32)


def small_chunk_jits(monkeypatch, interpret):
    """(select, pack): fresh jits of the two public functions, traced
    under ``CHUNK = LP_CHUNK`` (the module's own jits would keep whatever
    chunk they were first traced with)."""
    import functools

    import jax

    from oktopk_tpu.ops import compaction
    monkeypatch.setattr(compaction, "CHUNK", LP_CHUNK)
    select = jax.jit(functools.partial(
        compaction.select_by_threshold_pallas.__wrapped__,
        interpret=interpret), static_argnames=("cap",))
    pack = jax.jit(functools.partial(
        compaction._pack_by_region_pallas.__wrapped__, interpret=interpret),
        static_argnames=("num_regions", "cap"))
    return select, pack


def check_select_prefix(select, branch, count):
    x = prefix_vector(branch, count)
    gv, gi, gc, br = [np.asarray(a) for a in
                      select(jnp.asarray(x), 1.0, cap=LP_SELECT_CAP)]
    wv, wi, wc = [np.asarray(a) for a in
                  select_by_threshold(jnp.asarray(x), 1.0, LP_SELECT_CAP)]
    assert br[0] == LP_BRANCH[branch]
    assert gc == wc == min(count, LP_SELECT_CAP)
    np.testing.assert_array_equal(gi, wi)
    np.testing.assert_array_equal(gv.view(np.int32), wv.view(np.int32))


def check_pack_prefix(pack, branch, case):
    from oktopk_tpu.ops.select import pack_by_region

    region_counts = LP_REGION_COUNTS[case]
    x = prefix_vector(branch, sum(region_counts))
    bnd = jnp.asarray(rank_bounds(x, region_counts))
    R = len(region_counts)
    gv, gi, gc, br = [np.asarray(a) for a in pack(
        jnp.asarray(x), 1.0, bnd, num_regions=R, cap=LP_PACK_CAP)]
    wv, wi, wc = [np.asarray(a) for a in pack_by_region(
        jnp.asarray(x), jnp.abs(jnp.asarray(x)) >= 1.0, bnd, R,
        LP_PACK_CAP)]
    assert br[0] == LP_BRANCH[branch]
    np.testing.assert_array_equal(gc, np.minimum(region_counts,
                                                 LP_PACK_CAP))
    np.testing.assert_array_equal(gc, wc)
    np.testing.assert_array_equal(gi, wi)
    np.testing.assert_array_equal(gv.view(np.int32), wv.view(np.int32))


# ---- the one-hot product (``_stage_tile``) -----------------------------
# Eight blocks a call. Block b gives a packed slot to the in-block offsets
# o with o % (1024 / capb) == b % (1024 / capb): every offset 0-1023 is
# staged, from all eight sublane rows of a block, at capb 128 (an eighth of
# them a block) and at 1,024 (all of them in every block). The layouts say
# which slot an offset gets: its rank (offset 0 or b in the first slot, as
# the kernels pack survivors), the reverse (offset 1023 in the first slot,
# the smallest in the last), a seeded shuffle, and a shuffle in which every
# third element is dropped (slot ``capb``, which no one-hot row matches).
STAGE_TILE_BLOCKS = 8
STAGE_TILE_LAYOUTS = ("ascending", "descending", "shuffled", "dropped")


def stage_tile_slots(capb, layout, seed=41):
    """``sel [8 * 8, 128]`` i32 (a block's slots in its [8, 128] tile
    layout; ``capb`` = no slot) and the portable staging rows
    ``[8, capb]`` f32 they must give: ``stage[b, sel] = offset``, 0 where
    no element's slot is."""
    rng = np.random.RandomState(seed)
    stride = BLK // capb
    sel = np.full((STAGE_TILE_BLOCKS, BLK), capb, np.int32)
    want = np.zeros((STAGE_TILE_BLOCKS, capb), np.float32)
    for b in range(STAGE_TILE_BLOCKS):
        offsets = np.arange(b % stride, BLK, stride)
        slots = np.arange(capb)
        if layout == "descending":
            slots = slots[::-1]
        elif layout in ("shuffled", "dropped"):
            slots = rng.permutation(capb)
        keep = np.ones(capb, bool)
        if layout == "dropped":
            keep[rng.randint(3)::3] = False
        sel[b, offsets[keep]] = slots[keep]
        want[b, slots[keep]] = offsets[keep]
    return sel.reshape(-1, 128), want


def stage_tile_call(capb, interpret, undigited=False):
    """``_stage_tile`` alone over eight blocks' slots, as a kernel of its
    own: ``sel [64, 128]`` i32 -> ``[8, capb]`` f32. ``undigited`` stages
    the whole offset ``r * 128 + l`` as ONE row of the same single-pass
    product."""
    import jax
    import jax.experimental.pallas as pl

    from oktopk_tpu.ops.compaction import (BLK_COLS, BLK_ROWS, STAGE_ROWS,
                                           _onehot_pass, _stage_tile)

    def whole_offset(sel_b, capb):
        mio = jax.lax.broadcasted_iota(jnp.int32, (STAGE_ROWS, BLK_COLS), 0)
        lane = jax.lax.broadcasted_iota(jnp.int32, (STAGE_ROWS, BLK_COLS), 1)
        acc = jnp.zeros((STAGE_ROWS, capb), jnp.float32)
        for r in range(BLK_ROWS):
            rows = jnp.where(mio == 0, r * BLK_COLS + lane, 0)
            acc = acc + _onehot_pass(rows.astype(jnp.float32),
                                     sel_b[r:r + 1], capb)
        return acc[0:1]

    stage = whole_offset if undigited else _stage_tile

    def kernel(sel_ref, w_ref):
        s = sel_ref[:]
        w_ref[:] = jnp.concatenate([
            stage(s[b * BLK_ROWS:(b + 1) * BLK_ROWS], capb)
            for b in range(STAGE_TILE_BLOCKS)], axis=0)

    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((STAGE_TILE_BLOCKS, capb),
                                       jnp.float32),
        interpret=interpret, name=f"stage_tile_w{capb}")


def check_stage_tile(capb, layout, interpret):
    sel, want = stage_tile_slots(capb, layout)
    got = np.asarray(stage_tile_call(capb, interpret)(jnp.asarray(sel)))
    np.testing.assert_array_equal(got.view(np.int32), want.view(np.int32))


def check_whole_offset_rounds(interpret):
    """Why the digits: the offset as one row of the same bf16 pass is exact
    up to 256 (8 significant bits) and wrong at every odd offset above."""
    sel, want = stage_tile_slots(BLK, "ascending")
    assert (want == np.arange(BLK)).all()             # slot j holds j
    got = np.asarray(stage_tile_call(BLK, interpret, undigited=True)(
        jnp.asarray(sel)))
    np.testing.assert_array_equal(got[:, :257], want[:, :257])
    assert (got[:, 257::2] != want[:, 257::2]).all()
    assert np.abs(got - want).max() == 2.0            # 8 of 10 bits kept


SELECT_PREFIX_CASES = [(b, c) for b in LP_BRANCH
                       for c in LP_SELECT_COUNTS[b]]
PACK_PREFIX_CASES = [(b, c) for b in LP_BRANCH for c in LP_REGION_COUNTS]


class TestStageTile:
    """The MXU "scatter" of all three kernels alone (``_stage_tile``): one
    bf16 pass stages a block's lane and row digits, and the offset put
    together from them is the portable one bit for bit."""

    @pytest.mark.parametrize("layout", STAGE_TILE_LAYOUTS)
    @pytest.mark.parametrize("capb", [CAPB_FAST, BLK])
    def test_every_offset_exact(self, capb, layout):
        check_stage_tile(capb, layout, interpret=True)

    def test_whole_offset_in_one_pass_rounds_above_256(self):
        check_whole_offset_rounds(interpret=True)


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

    @pytest.mark.parametrize("novf", sorted(REPAIR_SURVIVORS))
    def test_repair_branch_pages_and_list_lengths(self, novf):
        """Repair branch with 1, 2, 4 and exactly ``_novf_cap`` listed
        blocks holding 129 to 1,024 survivors: the kernel skips the padded
        list entries and the pages past a block's count."""
        x, _ = overflow_vector(REPAIR_SURVIVORS[novf])
        gv, gi, gc, branch = [np.asarray(a) for a in
                              select_by_threshold_pallas(
                                  jnp.asarray(x), 1.0, x.size,
                                  interpret=True)]
        wv, wi, wc = [np.asarray(a) for a in
                      select_by_threshold(jnp.asarray(x), 1.0, x.size)]
        np.testing.assert_array_equal(branch, [1, novf])
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

    @pytest.mark.parametrize("novf", sorted(REPAIR_SURVIVORS))
    def test_repair_branch_pages_and_list_lengths(self, novf):
        """As the select test of the same name, through the region
        finalisation, with the boundary inside an overflowing block."""
        from oktopk_tpu.ops.compaction import pack_by_region_pallas
        from oktopk_tpu.ops.select import pack_by_region

        x, blocks = overflow_vector(REPAIR_SURVIVORS[novf])
        b = jnp.asarray(straddling_bounds(blocks))
        gv, gi, gc, branch = [np.asarray(a) for a in pack_by_region_pallas(
            jnp.asarray(x), 1.0, b, 2, x.size // 2, interpret=True)]
        wv, wi, wc = [np.asarray(a) for a in pack_by_region(
            jnp.asarray(x), jnp.abs(jnp.asarray(x)) >= 1.0, b, 2,
            x.size // 2)]
        np.testing.assert_array_equal(branch, [1, novf])
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


def _phys_base(ovf):
    """Where block b's staging row starts in [w_fast | w_rep]."""
    rank = np.cumsum(ovf) - ovf
    return np.where(ovf, ovf.size * CAPB_FAST + rank * BLK,
                    np.arange(ovf.size) * CAPB_FAST)


def _het_layout(raw, region_ranks, cap):
    """What ``_het_addresses`` is given and what it must return, in plain
    numpy. ``raw[b]`` survivors a block (over ``CAPB_FAST``: a repaired,
    1024-wide row), regions cut at the survivor ranks ``region_ranks``.
    Returns ``(ovf, cnt_rb, off_rb, counts, phys, blk)``; ``phys`` and
    ``blk`` by the definition, a slot at a time, -1 on dead slots."""
    raw = np.asarray(raw)
    edges = np.asarray([0, *region_ranks, raw.sum()])
    excl = np.cumsum(raw) - raw
    # survivors of block b in region r: overlap of two rank intervals
    cnt_rb = np.clip(np.minimum(excl[:, None] + raw[:, None], edges[1:])
                     - np.maximum(excl[:, None], edges[:-1]), 0, None)
    off_rb = np.cumsum(cnt_rb, axis=1) - cnt_rb
    counts = np.minimum(cnt_rb.sum(axis=0), cap)
    ovf = raw > CAPB_FAST
    phys_base = _phys_base(ovf)
    c_incl = np.cumsum(cnt_rb, axis=0)
    R = cnt_rb.shape[1]
    phys = -np.ones((R, cap), np.int64)
    blk = -np.ones((R, cap), np.int64)
    for r in range(R):
        for j in range(counts[r]):
            b = np.searchsorted(c_incl[:, r], j, side="right")
            phys[r, j] = (phys_base[b] + off_rb[b, r]
                          + j - (c_incl[b, r] - cnt_rb[b, r]))
            blk[r, j] = b
    return ovf, cnt_rb, off_rb, counts, phys, blk


def _flat_plus_delta(ovf, cnt_rb, off_rb, cap):
    """The address as the three-round materialise formed it: the virtual
    address ``flat`` plus a per-slot lookup ``delta[b]``."""
    nb, R = cnt_rb.shape
    capb_b = np.where(ovf, BLK, CAPB_FAST)
    vbase = np.cumsum(capb_b) - capb_b
    delta = _phys_base(ovf) - vbase
    off_next = np.concatenate([off_rb[1:], off_rb[-1:]])
    fval = capb_b[:, None] + off_next - off_rb - cnt_rb
    pos = np.minimum(np.cumsum(cnt_rb, axis=0), cap)
    fjump = np.zeros((R, cap + 1), np.int64)
    bjump = np.zeros((R, cap + 1), np.int64)
    for r in range(R):
        np.add.at(fjump[r], pos[:, r], fval[:, r])
        np.add.at(bjump[r], pos[:, r], 1)
    flat = off_rb[0][:, None] + np.cumsum(fjump, axis=1)[:, :cap] \
        + np.arange(cap)
    b = np.minimum(np.cumsum(bjump, axis=1)[:, :cap], nb - 1)
    return flat + delta[b], b


# raw survivors a block (16 blocks), cap: what each layout is there for
HET_LAYOUTS = {
    "empty_blocks": ([0, 0, 300, 0, 5, 0, 0, 129, 0, 0, 0, 40, 0, 0, 0, 0],
                     600),
    "overflow_first_and_last": ([1024, 3, 0, 7, 128, 9, 1, 0, 2, 127, 130,
                                 4, 4, 0, 6, 700], 2500),
    "boundary_in_overflow": ([10, 900, 20, 0, 0, 640, 3, 3, 3, 128, 129, 0,
                              50, 1, 1024, 2], 3000),
    "total_over_cap": ([100, 500, 100, 0, 1024, 100, 90, 80, 300, 0, 0, 70,
                        60, 50, 800, 40], 1000),
}


class TestTelescopedAddress:
    """``_het_addresses``: the cumsum of the jumps IS the physical staging
    address, equal on every live slot to the definition and to the
    ``flat + delta[b]`` it replaced (dead slots are masked downstream and
    may differ)."""

    @pytest.mark.parametrize("R", [1, 4])
    @pytest.mark.parametrize("layout", sorted(HET_LAYOUTS))
    def test_equals_flat_plus_delta_on_live_slots(self, layout, R):
        from oktopk_tpu.ops.compaction import _het_addresses

        raw, cap = HET_LAYOUTS[layout]
        total = sum(raw)
        # R = 4: region ends inside overflowing blocks, the first one a
        # few slots past the fast width of the first block over it
        first = int(np.argmax(np.asarray(raw) > CAPB_FAST))
        ranks = [] if R == 1 else [sum(raw[:first]) + CAPB_FAST + 7,
                                   total // 2, total - 60]
        ovf, cnt_rb, off_rb, counts, want_phys, want_blk = _het_layout(
            raw, ranks, cap)
        assert ovf[first] and (R == 1 or cnt_rb[first].min() >= 0
                               and (cnt_rb[first] > 0).sum() >= 2)
        if layout == "total_over_cap":
            assert cnt_rb.sum(axis=0).max() > cap
        phys, blk = [np.asarray(a) for a in _het_addresses(
            jnp.asarray(ovf), jnp.asarray(cnt_rb, jnp.int32),
            jnp.asarray(off_rb, jnp.int32), CAPB_FAST, cap)]
        old_phys, old_blk = _flat_plus_delta(ovf, cnt_rb, off_rb, cap)
        live = np.arange(cap)[None, :] < counts[:, None]
        assert live.any() and (want_phys >= 0)[live].all()
        np.testing.assert_array_equal(phys[live], want_phys[live])
        np.testing.assert_array_equal(blk[live], want_blk[live])
        np.testing.assert_array_equal(phys[live], old_phys[live])
        np.testing.assert_array_equal(blk[live], old_blk[live])


class TestLivePrefix:
    """The materialise gathers ``ceil(max(counts) / CHUNK)`` chunks of the
    slot axis and leaves the rest at value 0 / index n: bit-equal to the
    portable path in every branch, for counts at the ends of a chunk."""

    @pytest.fixture(scope="class")
    def jits(self):
        with pytest.MonkeyPatch.context() as mp:
            yield small_chunk_jits(mp, interpret=True)

    @pytest.mark.parametrize("branch,count", SELECT_PREFIX_CASES)
    def test_select(self, jits, branch, count):
        check_select_prefix(jits[0], branch, count)

    @pytest.mark.parametrize("branch,case", PACK_PREFIX_CASES)
    def test_pack_regions_end_in_different_chunks(self, jits, branch, case):
        check_pack_prefix(jits[1], branch, case)

    @pytest.mark.parametrize("R", [1, 2])
    def test_loop_under_varying_axes_tracking(self, mesh4, monkeypatch, R):
        """The gather loop inside ``shard_map(check_vma=True)``, a count a
        worker (the loop's carry starts as constants, which the tracking
        types as unvarying): what ``build_allreduce_step``'s default runs
        on the chip. The staging rows are made here, in numpy: the
        interpreter cannot run a kernel under the tracking."""
        import jax
        from jax.sharding import PartitionSpec as P_

        from oktopk_tpu.ops import compaction
        from oktopk_tpu.ops.select import pack_by_region

        monkeypatch.setattr(compaction, "CHUNK", 256)
        nb, cap = 16, 600
        n = nb * BLK
        totals = (0, 255, 700, 2000)          # trips 0, 1, 3, 3 (over cap)
        xs, stages, cnts, bnds = [], [], [], []
        for w, total in enumerate(totals):
            per = np.full(nb, total // nb)
            per[:total % nb] += 1
            x = counted_vector(per, seed=40 + w)
            at = np.flatnonzero(np.abs(x) >= 1.0)
            bnd = np.asarray([0, n] if R == 1 else
                             [0, at[total // 3] if total else n // 2, n],
                             np.int32)
            stage = np.zeros((nb, CAPB_FAST), np.float32)
            cnt = np.zeros((nb, R), np.int32)
            for b in range(nb):
                inb = at[at // BLK == b]
                stage[b, :inb.size] = inb % BLK
                for r in range(R):
                    cnt[b, r] = ((inb >= bnd[r]) & (inb < bnd[r + 1])).sum()
            xs.append(x), stages.append(stage), cnts.append(cnt)
            bnds.append(bnd)

        def per_worker(stage, x, cnt):
            stage, x, cnt = stage[0], x[0], cnt[0]
            off = jnp.cumsum(cnt, axis=1) - cnt
            counts = jnp.minimum(jnp.sum(cnt, axis=0), cap)
            v, i = compaction._materialize(stage, x, cnt, off, CAPB_FAST,
                                           cap, counts, n)
            return v[None], i[None]

        got_v, got_i = jax.jit(jax.shard_map(
            per_worker, mesh=mesh4, in_specs=(P_("data"),) * 3,
            out_specs=(P_("data"),) * 2, check_vma=True))(
                jnp.asarray(np.stack(stages)), jnp.asarray(np.stack(xs)),
                jnp.asarray(np.stack(cnts)))
        for w in range(len(totals)):
            wv, wi, _ = pack_by_region(
                jnp.asarray(xs[w]), jnp.abs(jnp.asarray(xs[w])) >= 1.0,
                jnp.asarray(bnds[w]), R, cap)
            np.testing.assert_array_equal(np.asarray(got_i[w]),
                                          np.asarray(wi))
            np.testing.assert_array_equal(np.asarray(got_v[w]),
                                          np.asarray(wv))


class TestRepairSkipInvariant:
    """What the repair kernel's skipping rests on (``_run_repair``): the
    consumers of ``w_rep`` read a listed block's row below its survivor
    count only, so the rows of padded list entries and the slots at or past
    a count may hold anything."""

    @pytest.mark.parametrize("chunk", [None, 256])
    @pytest.mark.parametrize("novf", [2, 4])
    def test_unaddressed_slots_may_hold_anything(self, novf, chunk,
                                                 monkeypatch):
        """``chunk``: the module's own (the survivors end in the first
        chunk of several) and a small one (the gather loop makes 3 to 8
        trips and leaves the rest of the buffer untouched)."""
        from oktopk_tpu.ops import compaction
        from oktopk_tpu.ops.compaction import (
            BLK_COLS, _materialize_het, _prep, _region_counts, _run_repair,
            _run_stage, _vma_of)
        from oktopk_tpu.ops.select import pack_by_region

        if chunk is not None:
            monkeypatch.setattr(compaction, "CHUNK", chunk)

        x, blocks = overflow_vector(REPAIR_SURVIVORS[novf])
        R, cap = 2, x.size // 2
        assert cap > compaction.CHUNK          # the loop, not the whole
        bnd = jnp.asarray(straddling_bounds(blocks))
        xp, xflat, t, rng, n, nb = _prep(jnp.asarray(x), 1.0, None, None)
        vma = _vma_of(xp)
        w_f, stored_f, raw = _run_stage(xp, t, rng, CAPB_FAST, nb, True, vma)
        ovf = raw > CAPB_FAST
        ncap = _novf_cap(nb)
        bl = jnp.nonzero(ovf, size=ncap, fill_value=0)[0].astype(jnp.int32)
        w_rep = _run_repair(xp, t, rng, bl, jnp.sum(ovf), ncap, True, vma)

        # NaN wherever the invariant says nobody looks
        listed = np.arange(ncap) < novf
        count = np.where(listed, np.asarray(raw)[np.asarray(bl)], 0)
        dead = np.arange(BLK)[None, :] >= count[:, None]   # [ncap, 1024]
        assert dead[~listed].all() and not dead[listed].all()
        poisoned = np.where(dead, np.nan,
                            np.asarray(w_rep).reshape(ncap, BLK))
        poisoned = jnp.asarray(poisoned.reshape(-1, BLK_COLS), jnp.float32)

        def finalize(w_rep):
            # _pack_finalize's repair branch, from w_rep on
            stored_v = jnp.where(ovf, raw, stored_f)
            rank = jnp.cumsum(ovf.astype(jnp.int32)) - ovf
            phys_base = jnp.where(
                ovf, nb * CAPB_FAST + rank * BLK,
                jnp.arange(nb, dtype=jnp.int32) * CAPB_FAST)
            stage_all = jnp.concatenate([w_f.reshape(-1),
                                         w_rep.reshape(-1)])
            cnt_rb = _region_counts(stage_all, phys_base, stored_v, BLK,
                                    bnd, R, nb)
            off_rb = jnp.cumsum(cnt_rb, axis=1) - cnt_rb
            counts = jnp.minimum(jnp.sum(cnt_rb, axis=0), cap)
            values, indices = _materialize_het(
                w_f, w_rep, ovf, xflat, cnt_rb, off_rb, CAPB_FAST, cap,
                counts, n)
            return [np.asarray(a) for a in (values, indices, counts)]

        want = [np.asarray(a) for a in pack_by_region(
            jnp.asarray(x), jnp.abs(jnp.asarray(x)) >= 1.0, bnd, R, cap)]
        for nm, clean, dirty, w in zip(("values", "indices", "counts"),
                                       finalize(w_rep), finalize(poisoned),
                                       want):
            np.testing.assert_array_equal(dirty, clean, err_msg=nm)
            np.testing.assert_array_equal(dirty, w, err_msg=nm)


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
