"""Bit-parity of the fused selection front-end (ops/fused_select.py)
against the portable separate-pass implementation, in Pallas interpret
mode — the same way ops/compaction.py earned trust (tests/test_compaction
.py; the real-chip mirrors live in tests/test_tpu_hw.py).

Unit level: every output of the single sweep (acc, staged region buffers,
realised count, unclamped probe count, histogram) across the fast, repair
and wide overflow branches, in both forms of the kernel: with the
histogram output and without it (``with_hist``; the form a step under
``threshold_method="bisect"`` runs), which must agree bit for bit in
everything else. Algorithm level: the whole oktopk step with
``fuse_select`` on vs off must carry bit-identical results AND state for
both threshold methods — the fused kernel may not change the algorithm.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from oktopk_tpu.ops.compaction import BLK, CAPB_FAST, SB, _novf_cap
from oktopk_tpu.ops.fused_select import (
    fused_pack_finalize,
    fused_select_pallas,
    fused_select_reference,
    fused_select_stage,
)

pytestmark = pytest.mark.kernels

NAMES = ("acc", "values", "indices", "counts", "local_count",
         "probe_count", "hist")


STAGE_FIELDS = ("acc", "w_f", "stored_f", "raw", "local_count",
                "probe_count")
FINAL_NAMES = ("values", "indices", "counts", "branch")

# both forms of the kernel, a case each
both_forms = pytest.mark.parametrize("with_hist", [True, False],
                                     ids=["hist", "nohist"])


def stage_and_finalize(g, r, t, tp, bnd, num_regions, cap, with_hist,
                       interpret=True):
    st = fused_select_stage(g, r, t, tp, with_hist=with_hist,
                            interpret=interpret)
    return st, fused_pack_finalize(st, bnd, num_regions, cap,
                                   interpret=interpret)


def run_both(g, r, t, bnd, num_regions, cap, with_hist=True,
             probe_ratio=1.25, interpret=True):
    """(got, want) in NAMES order. With the histogram: the one-call form
    against the reference. Without it: stage + finalize with
    ``with_hist=False``, first held bit for bit against the same two calls
    with it on (staging rows, raw counts and the dispatch branch too), then
    returned beside the reference's first six."""
    g, r = jnp.asarray(g), jnp.asarray(r)
    bnd = jnp.asarray(bnd, jnp.int32)
    want = [np.asarray(w) for w in fused_select_reference(
        g, r, t, t * probe_ratio, bnd, num_regions, cap)]
    if with_hist:
        got = fused_select_pallas(g, r, t, t * probe_ratio, bnd,
                                  num_regions, cap, interpret=interpret)
        return [np.asarray(a) for a in got], want
    st, fin = stage_and_finalize(g, r, t, t * probe_ratio, bnd, num_regions,
                                 cap, False, interpret)
    st_h, fin_h = stage_and_finalize(g, r, t, t * probe_ratio, bnd,
                                     num_regions, cap, True, interpret)
    assert st.hist is None and st_h.hist is not None
    for f in STAGE_FIELDS:
        np.testing.assert_array_equal(np.asarray(getattr(st, f)),
                                      np.asarray(getattr(st_h, f)),
                                      err_msg=f"{f}: nohist vs hist")
    for nm, a, b in zip(FINAL_NAMES, fin, fin_h):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=f"{nm}: nohist vs hist")
    got = (st.acc, *fin[:3], st.local_count, st.probe_count)
    return [np.asarray(a) for a in got], want[:6]


def assert_all_equal(got, want):
    assert len(got) == len(want)
    for nm, a, b in zip(NAMES, got, want):
        np.testing.assert_array_equal(a, b, err_msg=nm)


class TestFusedUnitParity:
    @both_forms
    @pytest.mark.parametrize("n", [BLK, 3 * BLK, 4 * BLK + 777])
    def test_fast_branch(self, n, with_hist):
        rng = np.random.RandomState(0)
        g = rng.randn(n).astype(np.float32)
        r = (0.1 * rng.randn(n)).astype(np.float32)
        bnd = [0, n // 3, n]
        got, want = run_both(g, r, 2.0, bnd, 2, max(64, int(0.05 * n)),
                             with_hist)
        assert_all_equal(got, want)

    @both_forms
    def test_residual_changes_selection(self, with_hist):
        # the residual add must happen BEFORE the mask: elements pushed
        # over/under the threshold by the residual flip membership
        n = 2 * BLK
        g = np.full(n, 1.9, np.float32)
        r = np.zeros(n, np.float32)
        r[::7] = 0.2                      # push every 7th over t=2.0
        got, want = run_both(g, r, 2.0, [0, n], 1, 1024, with_hist)
        assert_all_equal(got, want)
        assert got[4] == (n + 6) // 7     # local_count

    @both_forms
    def test_bit_exact_wide_dynamic_range(self, with_hist):
        # adversarial exponents: the histogram bins, staged values and acc
        # must come back bit-exact (octave-boundary magnitudes included)
        rng = np.random.RandomState(1)
        n = 2 * BLK
        g = (rng.randn(n) * 10.0 ** rng.randint(-30, 20, n)) \
            .astype(np.float32)
        g[::11] = np.exp2(rng.randint(-40, 20, len(g[::11]))) \
            .astype(np.float32)           # exact powers of two
        r = (rng.randn(n) * 1e-3).astype(np.float32)
        t = float(np.quantile(np.abs(g), 0.97))
        got, want = run_both(g, r, t, [0, n], 1, 4096, with_hist)
        assert_all_equal(got, want)
        for nm, a in zip(NAMES, got):
            if nm in ("acc", "values"):
                np.testing.assert_array_equal(
                    a.view(np.int32),
                    dict(zip(NAMES, want))[nm].view(np.int32),
                    err_msg=f"{nm} bitwise")

    @both_forms
    def test_probe_count_unclamped(self, with_hist):
        # the probe threshold is used UNCLAMPED (parity with the portable
        # jnp.sum(abs >= lt * ratio), which has no min-normal clamp): at
        # t=0 the staging mask clamps (selects only nonzeros) while the
        # probe counts everything
        n = BLK
        g = np.zeros(n, np.float32)
        g[:10] = 3.0
        r = np.zeros(n, np.float32)
        got, want = run_both(g, r, 0.0, [0, n], 1, 64, with_hist)
        assert_all_equal(got, want)
        assert got[4] == 10               # staged: nonzeros only
        assert got[5] == n                # probe at 0.0: everything

    @both_forms
    def test_repair_branch(self, with_hist):
        # a few blocks overflow CAPB_FAST -> repair kernel re-stages them;
        # condition asserted directly (as the compaction tests pin it)
        n = SB * BLK * 3
        rng = np.random.RandomState(2)
        g = np.zeros(n, np.float32)
        g[:BLK] = 10.0 + rng.rand(BLK).astype(np.float32)
        g[5 * BLK:5 * BLK + 300] = 5.0
        r = np.zeros(n, np.float32)
        raw = np.add.reduceat(np.abs(g) >= 1.0, np.arange(0, n, BLK))
        novf = int(np.sum(raw > CAPB_FAST))
        assert 0 < novf <= _novf_cap(n // BLK)
        got, want = run_both(g, r, 1.0, [0, n // 2, n], 2, 2048, with_hist)
        assert_all_equal(got, want)

    @both_forms
    def test_wide_branch(self, with_hist):
        # most blocks overflow -> the whole-width re-stage branch
        n = SB * BLK * 2
        rng = np.random.RandomState(3)
        g = (rng.randn(n) + 3.0).astype(np.float32)
        r = (0.01 * rng.randn(n)).astype(np.float32)
        raw = np.add.reduceat(np.abs(g + r) >= 0.5, np.arange(0, n, BLK))
        assert np.sum(raw > CAPB_FAST) > _novf_cap(n // BLK)
        got, want = run_both(g, r, 0.5, [0, n], 1, 8192, with_hist)
        assert_all_equal(got, want)

    def test_hist_matches_standalone(self):
        from oktopk_tpu.ops.hist_threshold import log2_hist

        rng = np.random.RandomState(4)
        n = BLK + 100                     # padded tail must not pollute
        g = (rng.randn(n) * 10.0 ** rng.randint(-20, 10, n)) \
            .astype(np.float32)
        r = (0.1 * rng.randn(n)).astype(np.float32)
        got, _ = run_both(g, r, 0.5, [0, n], 1, 512)
        np.testing.assert_array_equal(
            got[6], np.asarray(log2_hist(jnp.asarray(g + r))))


def _pallas_calls(jaxpr, name):
    """Every ``pallas_call`` equation named ``name`` in ``jaxpr``, nested
    programs (pjit, shard_map, cond branches) included."""
    for eqn in jaxpr.eqns:
        if (eqn.primitive.name == "pallas_call"
                and eqn.params["name"] == name):
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _pallas_calls(sub, name)


class TestHistogramOnlyWhereRead:
    @pytest.mark.parametrize("method,outputs", [("bisect", 4), ("hist", 5)])
    def test_step_program_fused_call_outputs(self, mesh8, method, outputs):
        """The histogram is an output of a pallas_call, which XLA cannot
        drop: under "bisect" nobody reads it, so the jitted oktopk step
        must hold the four-output form of the fused kernel (acc, staging
        rows, raw and probe counts), and the fifth only under "hist"."""
        from oktopk_tpu.collectives.api import (batched_init_state,
                                                build_allreduce_step)
        from oktopk_tpu.config import OkTopkConfig

        P, n = 8, 4096
        cfg = OkTopkConfig(n=n, num_workers=P, density=0.05, warmup_steps=0,
                           use_pallas=True, threshold_method=method)
        step = build_allreduce_step("oktopk", cfg, mesh8, warmup=False,
                                    check_vma=False)
        jaxpr = jax.make_jaxpr(step)(jnp.zeros((P, n), jnp.float32),
                                     batched_init_state(cfg))
        calls = list(_pallas_calls(jaxpr.jaxpr, "oktopk_fused_select"))
        assert len(calls) == 1
        assert len(calls[0].outvars) == outputs


class TestFusedAlgorithmParity:
    # slow: the full oktopk step through the Pallas INTERPRETER; the
    # kernel-level branches are covered above in tier-1, and the real-chip
    # wiring by tests/test_tpu_hw.py.
    @pytest.mark.slow
    @pytest.mark.parametrize("method", ["bisect", "hist"])
    def test_fused_step_bitwise_equals_unfused(self, mesh8, monkeypatch,
                                               method):
        """fuse_select on vs off at use_pallas=True: results and EVERY
        state leaf bit-identical over steps covering recompute, predicted
        and repartition branches — for both threshold methods."""
        monkeypatch.setenv("OKTOPK_PALLAS_INTERPRET", "1")
        from oktopk_tpu.collectives.api import (batched_init_state,
                                                build_allreduce_step)
        from oktopk_tpu.config import OkTopkConfig

        P, n = 8, 4096
        rng = np.random.RandomState(5)
        base = rng.randn(P, n).astype(np.float32)
        cfg0 = OkTopkConfig(n=n, num_workers=P, density=0.05,
                            warmup_steps=0, local_recompute_every=2,
                            global_recompute_every=2, repartition_every=4,
                            use_pallas=True, threshold_method=method,
                            wire_dtype="float32")
        outs, states = {}, {}
        for fuse in (None, False):
            cfg = cfg0.replace(fuse_select=fuse)
            step = build_allreduce_step("oktopk", cfg, mesh8,
                                        warmup=False, check_vma=False)
            state = batched_init_state(cfg)
            rs = []
            for s in range(5):
                out, state = step(jnp.asarray(base * (1.0 + 0.01 * s)),
                                  state)
                rs.append(np.asarray(out[0]))
            outs[fuse] = rs
            states[fuse] = jax.tree.map(np.asarray, state)
        for a, b in zip(outs[None], outs[False]):
            np.testing.assert_array_equal(a.view(np.int32),
                                          b.view(np.int32))
        for f in states[None].__dataclass_fields__:
            np.testing.assert_array_equal(
                getattr(states[None], f), getattr(states[False], f),
                err_msg=f"state.{f}")
