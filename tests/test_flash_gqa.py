"""ops/flash_gqa.py: the grouped-head flash-attention kernels, interpreted,
against the blocked XLA form's own block function as oracle; the tile
arithmetic against a brute-force mask; what ``snapshot()`` says of the
calls; and the kernels compiled by Mosaic for a described v5e at the
benchmark's widths (nothing runs), those of ``ops/delta_rule.py`` with
them: this is the one file that describes a chip. The models through the
kernels are in ``tests/test_attention.py``."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from oktopk_tpu.models import attention
from oktopk_tpu.models.attention import ATTN_OUT
from oktopk_tpu.ops import flash_gqa
from oktopk_tpu.utils import profiling


def oracle(q, k, v, scale, window):
    """``_attend_block_gqa`` over one block that is the whole sequence."""
    _, t, h, d = q.shape
    g = k.shape[2]

    def one(qq, kk, vv):
        return attention._attend_block_gqa(
            qq.reshape(t, g, h // g, d), kk, vv, 0, t, scale, 0,
            window).reshape(t, h, d)
    return jax.vmap(one)(q, k, v)


def inputs(b, t, r, d, g=2, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(ks[0], (b, t, g * r, d)),
            jax.random.normal(ks[1], (b, t, g, d)),
            jax.random.normal(ks[2], (b, t, g, d)),
            jax.random.normal(ks[3], (b, t, g * r, d)))


# name -> (tokens, (tq, tk), window)
MASKS = {
    "causal": (24, (8, 8), None),
    "window_under_t": (32, (8, 16), 11),
    "window_at_least_t": (24, (8, 8), 24),
    "window_under_a_tile": (24, (8, 8), 3),
    "t_no_whole_tiles": (21, (8, 8), 10),
}
# laguna's: six query heads a key-value head, and a band one tile wide or
# narrower (every visited tile is an edge tile and builds its mask)
NARROW = {
    "window_is_a_tile": (32, (8, 8), 8),
    "window_under_a_tile": MASKS["window_under_a_tile"],
    "causal": MASKS["causal"],
}
# small groups, whose key-value heads ride a grid step several at a time:
# (R, G) -> the pack that ``kv_heads_a_step`` gives at d = 128
PACKS = {(1, 16): 8, (1, 3): 3, (2, 4): 4, (3, 4): 2, (1, 9): 3}
# ... and at d = 64, where only a pack is whole lane rows: lfm2's groups of
# four over eight key-value heads, groups of two and of one
NARROW_PACKS = {(4, 8): 2, (2, 4): 4, (1, 16): 8, (1, 6): 6}


def through(fn, q, k, v, w):
    """(out, dq, dk, dv) of ``fn`` under the cotangent ``w``."""
    out, vjp = jax.vjp(fn, q, k, v)
    return (out,) + vjp(w)


class TestKernelsAgainstTheBlockFunction:
    @pytest.mark.parametrize("b", [1, 2])
    @pytest.mark.parametrize("r", [1, 7, 8])
    @pytest.mark.parametrize("d", [128, 256])
    @pytest.mark.parametrize("mask", list(MASKS))
    def test_forward_and_three_gradients(self, mask, d, r, b):
        t, tiles, window = MASKS[mask]
        q, k, v, w = inputs(b, t, r, d)
        scale = d ** -0.5

        def through(fn):
            def loss(q, k, v):
                out = fn(q, k, v)
                return jnp.sum(out * w), out
            (_, out), grads = jax.jit(jax.value_and_grad(
                loss, (0, 1, 2), has_aux=True))(q, k, v)
            return (out,) + grads

        got = through(lambda q, k, v: flash_gqa.flash_gqa(
            q, k, v, scale, window, interpret=True, tiles=tiles))
        want = through(lambda q, k, v: oracle(q, k, v, scale, window))
        for name, a, e in zip(("out", "dq", "dk", "dv"), got, want):
            np.testing.assert_allclose(a, e, rtol=2e-5, atol=2e-5,
                                       err_msg=name)

    @pytest.mark.parametrize("mask", ["causal", "window_under_t"])
    @pytest.mark.parametrize("r,g", list(PACKS))
    def test_packs_of_key_value_heads(self, r, g, mask):
        """Where a group is small a grid step takes several key-value heads
        with their query heads: a group of ONE head (ouro's 16 over 16; 3
        and 9, which 8 does not divide), and groups of 2 and 3 whose heads
        sum into their key-value head's columns of the pack's dk and dv."""
        t, tiles, window = MASKS[mask]
        q, k, v, w = inputs(2, t, r, 128, g=g)
        assert flash_gqa.kv_heads_a_step(r * g, g, 128) == PACKS[r, g]
        got = through(lambda q, k, v: flash_gqa.flash_gqa(
            q, k, v, 0.09, window, interpret=True, tiles=tiles), q, k, v, w)
        want = through(lambda q, k, v: oracle(q, k, v, 0.09, window),
                       q, k, v, w)
        for name, a, e in zip(("out", "dq", "dk", "dv"), got, want):
            np.testing.assert_allclose(a, e, rtol=2e-5, atol=2e-5,
                                       err_msg=name)

    @pytest.mark.parametrize("mask", ["causal", "window_under_t",
                                      "t_no_whole_tiles"])
    @pytest.mark.parametrize("r,g", list(NARROW_PACKS))
    def test_a_64_wide_head_rides_in_packs_of_whole_lane_rows(self, r, g,
                                                              mask):
        """A head narrower than a lane row (models/lfm2.py: 32 query heads
        over 8 key-value heads of 64): two and more key-value heads ride a
        step, their tile [tk, own x 64] whole lane rows and a head a
        64-lane cut of it, in the slab of q, in the output and in the
        pack's dk and dv. Forward and the three gradients."""
        t, tiles, window = MASKS[mask]
        q, k, v, w = inputs(2, t, r, 64, g=g)
        own = flash_gqa.kv_heads_a_step(r * g, g, 64)
        assert own == NARROW_PACKS[r, g] and (own * 64) % 128 == 0
        got = through(lambda q, k, v: flash_gqa.flash_gqa(
            q, k, v, 0.125, window, interpret=True, tiles=tiles), q, k, v, w)
        want = through(lambda q, k, v: oracle(q, k, v, 0.125, window),
                       q, k, v, w)
        for name, a, e in zip(("out", "dq", "dk", "dv"), got, want):
            np.testing.assert_allclose(a, e, rtol=2e-5, atol=2e-5,
                                       err_msg=name)

    @pytest.mark.parametrize("t,tiles,window", [
        (32, (8, 16), None), (32, (8, 16), 11), (48, (16, 16), 20)])
    @pytest.mark.parametrize("g,d", [(16, 128), (3, 128), (9, 128),
                                     (16, 256)])
    def test_a_pack_at_a_group_of_one_is_one_head_a_step_to_the_last_bit(
            self, g, d, t, tiles, window):
        """The same products on the same operands in the same order over a
        head's key tiles, and at R = 1 no sum crosses heads: the output and
        the three gradients of the pack are the numbers of the same call
        forced to one key-value head a grid step. (Key tiles of 16: at 8,
        under one vector of this CPU, XLA:CPU's own code for the interpreted
        kernel parts by 1-4 ulp in a few rows with the fusion it lands in;
        from 16 on, 48 geometries read equal.)"""
        q, k, v, w = inputs(2, t, 1, d, g=g)
        assert flash_gqa.kv_heads_a_step(g, g, d) > 1

        def both(q, k, v, w):
            return [through(lambda q, k, v: flash_gqa.flash_gqa(
                q, k, v, d ** -0.5, window, interpret=True, tiles=tiles,
                heads=heads), q, k, v, w) for heads in (None, 1)]

        packed, one = jax.jit(both)(q, k, v, w)
        for name, a, e in zip(("out", "dq", "dk", "dv"), packed, one):
            np.testing.assert_array_equal(a, e, err_msg=name)

    @pytest.mark.parametrize("r", [6, 8])
    @pytest.mark.parametrize("mask", list(NARROW))
    def test_six_heads_a_group_and_a_band_one_tile_wide(self, mask, r):
        """Values and the three gradients against the WHOLE masked score
        matrix (no block function): laguna's two call shapes, R = 6 causal
        and R = 8 in a window equal to the tile, and the window under a
        tile; with a window of a tile no visited tile is whole."""
        t, tiles, window = NARROW[mask]
        q, k, v, w = inputs(1, t, r, 128)
        scale = 128 ** -0.5
        if window is not None:
            nq = t // tiles[0]
            assert not any(
                flash_gqa._interior(i, j, *tiles, window)
                for i in range(nq)
                for j in range(flash_gqa.kv_tiles(i, *tiles, window)[0],
                               flash_gqa.kv_tiles(i, *tiles, window)[1] + 1))

        def masked(q, k, v):
            kk, vv = (jnp.repeat(x, r, axis=2) for x in (k, v))
            s = jnp.einsum("bqhd,bkhd->bhqk", q, kk) * scale
            i, j = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
            seen = j <= i
            if window is not None:
                seen = seen & (i - j < window)
            p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
            return jnp.einsum("bhqk,bkhd->bqhd", p, vv)

        def through(fn):
            out, vjp = jax.vjp(fn, q, k, v)
            return (out,) + vjp(w)

        got = through(lambda q, k, v: flash_gqa.flash_gqa(
            q, k, v, scale, window, interpret=True, tiles=tiles))
        for name, a, e in zip(("out", "dq", "dk", "dv"), got,
                              through(masked)):
            np.testing.assert_allclose(a, e, rtol=2e-5, atol=2e-5,
                                       err_msg=name)

    def test_a_bfloat16_caller_gets_bfloat16_cotangents(self):
        """A model that computes in bfloat16 (the benchmark's control) hands
        the kernels bfloat16 q, k, v: they are widened on entry, the output
        is float32 and the three gradients come back in the caller's type
        (before PR 44 the backward rule returned float32 ones and the
        gradient's program did not trace)."""
        t, tiles, window = MASKS["window_under_t"]
        q, k, v, w = inputs(1, t, 6, 128)
        narrow = [x.astype(jnp.bfloat16) for x in (q, k, v)]

        def through(fn, args):
            out, vjp = jax.vjp(fn, *args)
            return (out,) + vjp(w)

        got = through(lambda q, k, v: flash_gqa.flash_gqa(
            q, k, v, 128 ** -0.5, window, interpret=True, tiles=tiles),
            narrow)
        want = through(lambda q, k, v: oracle(q, k, v, 128 ** -0.5, window),
                       [x.astype(jnp.float32) for x in narrow])
        assert got[0].dtype == jnp.float32
        assert [g.dtype for g in got[1:]] == [jnp.bfloat16] * 3
        for name, a, e in zip(("out", "dq", "dk", "dv"), got, want):
            np.testing.assert_allclose(a.astype(jnp.float32), e, rtol=1e-2,
                                       atol=1e-2, err_msg=name)

    @pytest.mark.parametrize("mask", ["causal", "window_under_t"])
    def test_rounds_where_the_plain_form_rounds(self, mask, monkeypatch):
        """What the chip computes: operands rounded to bfloat16 at every
        product, and at the SAME places as XLA:TPU's one-pass ``einsum`` of
        the plain form and its transposes: the normalised probabilities for
        ``p v`` and for dv, the scaled score gradient for dq and dk. (The
        first kernel on the chip rounded ``exp(x - running max)`` and
        scaled after the product: the same precision, other numbers, and
        the benchmark's gradient check read twice its sound value.)"""
        t, tiles, window = MASKS[mask]
        q, k, v, w = inputs(1, t, 7, 128)
        b, _, h, d = q.shape
        g, scale = k.shape[2], d ** -0.5

        def rnd(x):
            return x.astype(jnp.bfloat16).astype(jnp.float32)

        def grouped(x):
            return x.reshape(b, t, g, h // g, d)

        rows, cols = np.arange(t)[:, None], np.arange(t)[None]
        seen = cols <= rows
        if window is not None:
            seen &= rows - cols < window
        x = jnp.einsum("bqgrd,bkgd->bgrqk", rnd(grouped(q)), rnd(k)) * scale
        prob = jax.nn.softmax(jnp.where(seen, x, -jnp.inf), axis=-1)
        out = jnp.einsum("bgrqk,bkgd->bqgrd", rnd(prob), rnd(v))
        dprob = jnp.einsum("bqgrd,bkgd->bgrqk", rnd(grouped(w)), rnd(v))
        # the rows' sum of p dp as the kernels take it: out . dout, with
        # dout rounded as the product that makes dp rounds it (p's own
        # rounding inside out is the one place they part from the plain
        # form's backward pass)
        delta = jnp.einsum("bqgrd,bqgrd->bgrq", out, rnd(grouped(w)))
        dx = prob * (dprob - delta[..., None])
        want = (out.reshape(q.shape),
                jnp.einsum("bgrqk,bkgd->bqgrd", rnd(dx * scale),
                           rnd(k)).reshape(q.shape),
                jnp.einsum("bgrqk,bqgrd->bkgd", rnd(dx * scale),
                           rnd(grouped(q))),
                jnp.einsum("bgrqk,bqgrd->bkgd", rnd(prob),
                           rnd(grouped(w))))

        monkeypatch.setattr(flash_gqa, "_product",
                            lambda interpret: jnp.bfloat16)
        got, vjp = jax.vjp(lambda q, k, v: flash_gqa.flash_gqa(
            q, k, v, scale, window, interpret=True, tiles=tiles), q, k, v)
        # a rounding that falls elsewhere reads 2e-3 here
        for name, a, e in zip(("out", "dq", "dk", "dv"), (got,) + vjp(w),
                              want):
            err = float(jnp.linalg.norm(a - e) / jnp.linalg.norm(e))
            assert err < 2e-4, (name, err)

    @pytest.mark.parametrize("mask", list(MASKS))
    def test_log_sum_exp(self, mask):
        t, (tq, tk), window = MASKS[mask]
        if window is not None and window >= t:
            window = None
        b, g, r, d = 2, 2, 3, 128
        q, k, v, _ = inputs(b, t, r, d)
        pad = -t % max(tq, tk)
        flat = [jnp.pad(x.reshape(b, t, -1), ((0, 0), (0, pad), (0, 0)))
                for x in (q, k, v)]
        plan = flash_gqa._Plan(0.2, window, tq, tk, g, r, d, "float32", True,
                               None)
        _, lse = flash_gqa._forward(plan, *flat)
        s = jnp.einsum("bqgrd,bkgd->bgrqk", q.reshape(b, t, g, r, d),
                       k) * 0.2
        rows, cols = np.arange(t)[:, None], np.arange(t)[None]
        seen = cols <= rows
        if window is not None:
            seen &= rows - cols < window
        want = jax.nn.logsumexp(jnp.where(seen, s, -jnp.inf), axis=-1)
        np.testing.assert_allclose(lse[:, :, :t],
                                   want.reshape(b, g * r, t), rtol=1e-5,
                                   atol=1e-5)


GEOMETRIES = [(64, 8, 8, None), (64, 8, 8, 20), (64, 16, 8, 20),
              (64, 8, 16, 20), (64, 8, 8, 1), (64, 8, 8, 3), (64, 8, 8, 8),
              (64, 8, 8, 9), (64, 16, 16, 17), (64, 32, 8, 40),
              (16384, 512, 512, 4096), (16384, 512, 512, None),
              (8192, 256, 512, None), (16384, 512, 512, 512)]


class TestWhichTiles:
    @staticmethod
    def pairs(t, tq, tk, window):
        """[nq, nk, 2]: whether tile (i, j) holds a seen pair, and whether
        all its pairs are seen."""
        rows, cols = np.arange(t)[:, None], np.arange(t)[None]
        seen = cols <= rows
        if window is not None:
            seen &= rows - cols < window
        tiles = seen.reshape(t // tq, tq, t // tk, tk)
        return tiles.any(axis=(1, 3)), tiles.all(axis=(1, 3))

    @pytest.mark.parametrize("t,tq,tk,window", GEOMETRIES)
    def test_every_tile_with_a_seen_pair_is_visited_and_no_other(
            self, t, tq, tk, window):
        if t > 4096:    # the benchmark's shapes at an eighth of a tile
            t, tq, tk = t // 64, tq // 64, tk // 64
            window = window and window // 64
        some, every = self.pairs(t, tq, tk, window)
        nq, nk = some.shape
        for i in range(nq):
            first, last = flash_gqa.kv_tiles(i, tq, tk, window)
            assert [j for j in range(nk) if some[i, j]] == list(
                range(first, last + 1))
        for j in range(nk):
            first, last = flash_gqa.q_tiles(j, tq, tk, window, nq)
            assert [i for i in range(nq) if some[i, j]] == list(
                range(first, last + 1))
        for i in range(nq):
            for j in range(nk):
                assert bool(flash_gqa._interior(i, j, tq, tk, window)) == (
                    bool(every[i, j]))
        visited, causal = flash_gqa.tile_counts(t, tq, tk, window)
        assert visited == some.sum()
        assert causal == self.pairs(t, tq, tk, None)[0].sum()

    def test_the_benchmarks_counts(self):
        """smallthinker's band at 512-wide tiles: nine key tiles a query
        tile past the eighth; the triangle 528."""
        assert flash_gqa.tile_counts(16384, 512, 512, 4096) == (
            sum(min(i + 1, 9) for i in range(32)), 528)
        assert flash_gqa.tile_counts(16384, 512, 512, None) == (528, 528)
        # laguna's band is one tile wide: two key tiles a query tile past
        # the first, neither whole (524,288 pairs computed a query tile for
        # the 262,144 in the band: tile_rule does not follow the window)
        assert flash_gqa.tile_rule(16384, 8, 128) == (512, 512)
        assert flash_gqa.tile_rule(16384, 6, 128) == (512, 512)
        assert flash_gqa.tile_counts(16384, 512, 512, 512) == (63, 528)

    @pytest.mark.parametrize("t,r,d", [(16384, 7, 128), (8192, 8, 256),
                                       (64, 2, 32), (300, 1, 128),
                                       (16384, 6, 128), (16384, 8, 128),
                                       (4096, 1, 128)])
    def test_tile_rule_is_whole_lane_rows_inside_its_plan(self, t, r, d):
        tq, tk = flash_gqa.tile_rule(t, r, d)
        assert tq % 128 == 0 and tk % 128 == 0 and tk <= 512
        assert tk - 128 < t or tk == 128


@pytest.fixture
def fresh_calls(monkeypatch):
    monkeypatch.setattr(flash_gqa, "_calls", {})


class TestSnapshot:
    def test_a_call_is_recorded_once_a_shape(self, fresh_calls, monkeypatch):
        q, k, v, _ = inputs(1, 64, 2, 32)
        for _ in range(2):
            attention.blocked_causal_gqa(q, k, v, 0.1, 16, 24)
        attention.blocked_causal_gqa(q, k, v, 0.1, 16, 64)
        assert profiling.snapshot()["attention"] == [
            {"kernel": False, "window": 24,
             "tiles_visited": flash_gqa.tile_counts(64, 16, 16, 24)[0],
             "tiles_causal": 10, "kv_heads_a_step": 0},
            {"kernel": False, "window": None, "tiles_visited": 10,
             "tiles_causal": 10, "kv_heads_a_step": 0}]
        monkeypatch.setenv("OKTOPK_PALLAS_INTERPRET", "1")
        attention.blocked_causal_gqa(q, k, v, 0.1, 16, 24)
        assert profiling.snapshot()["attention"][-1] == {
            "kernel": True, "window": 24, "tiles_visited": 1,
            "tiles_causal": 1, "kv_heads_a_step": 1}
        # a group of one head over four key-value heads: the four ride a step
        q, k, v, _ = inputs(1, 64, 1, 128, g=4)
        attention.blocked_causal_gqa(q, k, v, 0.1, 16)
        assert profiling.snapshot()["attention"][-1] == {
            "kernel": True, "window": None, "tiles_visited": 1,
            "tiles_causal": 1, "kv_heads_a_step": 4}

    def test_empty_without_such_a_layer(self, fresh_calls):
        assert profiling.snapshot()["attention"] == []

    @pytest.mark.parametrize("h,g,d,want", [
        (32, 8, 64, True),      # lfm2: two key-value heads of 64 a step
        (16, 16, 64, True), (6, 6, 64, True),
        (3, 3, 64, False),      # no pack is whole lane rows: the XLA form
        (8, 1, 64, False), (4, 2, 32, False),
        (28, 4, 128, True), (16, 2, 256, True), (16, 16, 128, True),
        (48, 8, 96, False)])
    def test_compiled_for_a_tpu_the_kernels_want_a_pack_of_whole_lane_rows(
            self, fresh_calls, monkeypatch, h, g, d, want):
        """The admission rule: a step's tile of keys [tk, own x d] is whole
        lane rows. Every head of 128 or 256 is admitted as before (a pack
        of them is whole rows where one is); a head of 64 where two or more
        ride a step."""
        monkeypatch.delenv("OKTOPK_PALLAS_INTERPRET", raising=False)
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        assert flash_gqa.on_this_platform(8192, h, g, d, None, 512) is want
        call, = profiling.snapshot()["attention"]
        assert call["kernel"] is want
        assert (call["kv_heads_a_step"] > 0) is want


# ---- compiled for a described v5e (nothing runs) ---------------------------

@pytest.fixture(scope="module")
def one_chip():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


# the benchmark's seven call shapes: (B, T, H, G, d, window); the last but
# one is a group of ONE head (models/ouro.py: 16 query heads over 16
# key-value heads), the last a head of 64 (models/lfm2.py)
CALLS = {"smallthinker_window": (1, 16384, 28, 4, 128, 4096),
         "smallthinker_global": (1, 16384, 28, 4, 128, None),
         "qwen3next_full": (2, 8192, 16, 2, 256, None),
         "laguna_full": (1, 16384, 48, 8, 128, None),
         "laguna_sliding": (1, 16384, 64, 8, 128, 512),
         "ouro_full": (2, 4096, 16, 16, 128, None),
         "lfm2_full": (2, 8192, 32, 8, 64, None)}
# ... and the key-value heads that ride a grid step there
OWN = {"ouro_full": 8, "lfm2_full": 2}


@pytest.mark.parametrize("call", list(CALLS))
def test_key_value_heads_a_step_at_the_benchmarks_calls(call):
    """A group of six, seven or eight query heads fills a grid step: one
    key-value head a step, the program those cells ran before there were
    packs. ouro's group of one head: eight key-value heads a step, two
    packs a sequence; lfm2's groups of four at a head of 64: two a step,
    four packs. Either way the rule's tiles, with no halving (inside
    ``VMEM_PLAN``), and a tile of k whole lane rows."""
    _, t, h, g, d, _ = CALLS[call]
    own = flash_gqa.kv_heads_a_step(h, g, d)
    assert own == OWN.get(call, 1)
    assert g % own == 0 and (own * d) % flash_gqa.LANES == 0
    assert own == 1 or own * (h // g) <= flash_gqa.HEADS_A_STEP
    assert flash_gqa.tile_rule(t, own * (h // g), d, own=own) == (512, 512)


@pytest.mark.parametrize("h,g,d,want", [
    (16, 16, 256, 4),       # a slab of 8 x 256 at 512 queries is the cliff
    (8, 4, 128, 4), (12, 4, 128, 2), (9, 9, 128, 3), (3, 3, 128, 3),
    (5, 5, 128, 5), (7, 7, 128, 7), (11, 11, 128, 1), (64, 32, 128, 4),
    (16, 16, 64, 8), (6, 6, 64, 6), (3, 3, 64, 1), (4, 2, 32, 1),
    (40, 8, 128, 1), (16, 1, 128, 1)])
def test_key_value_heads_a_step_from_the_shapes(h, g, d, want):
    """The largest divisor of G whose query heads are no more than
    ``HEADS_A_STEP``, whose slab stays under the cliff and whose tile is
    whole lane rows; one where there is none."""
    own = flash_gqa.kv_heads_a_step(h, g, d)
    assert own == want and g % own == 0
    if own > 1:
        assert own * (h // g) <= flash_gqa.HEADS_A_STEP
        assert (own * d) % flash_gqa.LANES == 0
        assert (flash_gqa.TILE * own * (h // g) * d * 4
                < flash_gqa.SLAB_CLIFF)


@pytest.mark.parametrize("call", list(CALLS))
def test_mosaic_compiles_the_three_kernels_at_the_benchmarks_widths(
        call, one_chip):
    b, t, h, g, d, window = CALLS[call]
    q = jax.ShapeDtypeStruct((b, t, h, d), jnp.float32, sharding=one_chip)
    k = jax.ShapeDtypeStruct((b, t, g, d), jnp.float32, sharding=one_chip)

    def grads(q, k, v, w):
        return jax.grad(lambda q, k, v: jnp.sum(flash_gqa.flash_gqa(
            q, k, v, d ** -0.5, window, save_as=ATTN_OUT,
            interpret=False) * w),
            (0, 1, 2))(q, k, v)

    cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        text = jax.jit(grads).lower(q, k, k, q).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache)
    for kernel in ("fwd", "dq", "dkv"):
        assert f"oktopk_flash_gqa_{kernel}" in text
    # no score block of [heads, queries, keys] is left in the program
    assert not re.search(rf"f32\[[\d,]*{min(t, 512)},\d{{4,}}\]", text)


def test_mosaic_compiles_the_three_kernels_at_mlas_widths(one_chip):
    """``dsv2lite_dense_x1``'s call: 4 sequences of 4,096 tokens, 16 split
    heads of 128 + 64 | 128 and the one shared rotary key, at the rule's
    heads a step and tiles."""
    b, t, h, d, rope, dv = 4, 4096, 16, 128, 64, 128
    shapes = [(b, t, h, d), (b, t, h, rope), (b, t, h, d), (b, t, rope),
              (b, t, h, dv), (b, t, h, dv)]

    def grads(q_nope, q_pe, k_nope, k_pe, v, w):
        return jax.grad(lambda *x: jnp.sum(flash_gqa.flash_mla(
            *x, (d + rope) ** -0.5, save_as=ATTN_OUT, interpret=False) * w),
            (0, 1, 2, 3, 4))(q_nope, q_pe, k_nope, k_pe, v)

    cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        text = jax.jit(grads).lower(*[jax.ShapeDtypeStruct(
            s, jnp.float32, sharding=one_chip) for s in shapes]
        ).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache)
    for kernel in ("fwd", "dq", "dkv"):
        assert f"oktopk_flash_mla_{kernel}" in text
    # no score block of [heads, queries, keys] is left in the program
    assert not re.search(r"f32\[[\d,]*512,\d{4,}\]", text)


def test_mosaic_compiles_the_delta_rules_kernels_at_the_benchmarks_widths(
        one_chip):
    """``qwen3next_dense_x1``'s walk: a segment of 16 chunks of 64 tokens,
    2 sequences, 32 value heads with a state of [128, 128], at the rule's
    heads and chunks a grid step; forward (the kernel that keeps the
    states) and backward."""
    from oktopk_tpu.ops import delta_rule
    n, b, hv, c, dk, dv = 16, 2, 32, 64, 128, 128
    stacked = [(n, b, hv, c, dv), (n, b, hv, c, dk), (n, b, hv, c, c),
               (n, b, hv, c, dk), (n, b, hv, c, dk), (n, b, hv)]
    state = (b, hv, dk, dv)

    def grads(*x):
        out, vjp = jax.vjp(lambda *a: delta_rule.delta_rule(
            *a, interpret=False), *x[:7])
        return out, vjp(x[7:])

    cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        text = jax.jit(grads).lower(*[jax.ShapeDtypeStruct(
            s, jnp.float32, sharding=one_chip)
            for s in stacked + [state, stacked[0], state]]
        ).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache)
    for kernel in ("fwd", "bwd"):
        assert f"oktopk_delta_rule_{kernel}" in text
