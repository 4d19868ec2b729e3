"""Bit-parity of the fused selection front-end (ops/fused_select.py)
against the portable separate-pass implementation, in Pallas interpret
mode — the same way ops/compaction.py earned trust (tests/test_compaction
.py; the real-chip mirrors live in tests/test_tpu_hw.py).

Unit level: every output of the single sweep (acc, staged region buffers,
realised count, unclamped probe count) across the fast, repair and wide
overflow branches, each over one region and over three regions whose
boundaries lie inside a block (the finalize assigns regions at cap scale,
from the staging rows: a boundary inside an overflowing or a clamped
block is where it can go wrong). Algorithm level: the whole oktopk step
on the Pallas path must carry bit-identical results AND state to the
portable step — the fused kernel may not change the algorithm.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from oktopk_tpu.ops.compaction import BLK, CAPB_FAST, SB, _novf_cap
from oktopk_tpu.ops.fused_select import (
    fused_pack_finalize,
    fused_select_reference,
    fused_select_stage,
)

pytestmark = pytest.mark.kernels

NAMES = ("acc", "values", "indices", "counts", "local_count",
         "probe_count")

# region layouts, a case each: one region, and three whose two interior
# boundaries straddle a block (cf. test_compaction.straddling_bounds)
layouts = pytest.mark.parametrize("layout", ["one", "straddle3"])


def region_bounds(layout, n, cuts):
    """``[0, n]``, or ``[0, *cuts, n]`` with both cuts inside a block."""
    if layout == "one":
        return [0, n]
    assert len(cuts) == 2 and all(0 < c < n and c % BLK for c in cuts)
    return [0, *cuts, n]


def run_both(g, r, t, bnd, cap, probe_ratio=1.25, interpret=True):
    """(got, want, branch): stage + finalize against the portable
    reference, in NAMES order, and the finalize's dispatch branch
    (i32[2]: fast / repair / wide, overflowing blocks)."""
    g, r = jnp.asarray(g), jnp.asarray(r)
    bnd = jnp.asarray(bnd, jnp.int32)
    num_regions = bnd.size - 1
    want = fused_select_reference(g, r, t, t * probe_ratio, bnd,
                                  num_regions, cap)
    st = fused_select_stage(g, r, t, t * probe_ratio, interpret=interpret)
    values, indices, counts, branch = fused_pack_finalize(
        st, bnd, num_regions, cap, interpret=interpret)
    got = (st.acc, values, indices, counts, st.local_count, st.probe_count)
    return ([np.asarray(a) for a in got], [np.asarray(w) for w in want],
            np.asarray(branch))


def assert_all_equal(got, want):
    assert len(got) == len(want) == len(NAMES)
    for nm, a, b in zip(NAMES, got, want):
        np.testing.assert_array_equal(a, b, err_msg=nm)


class TestFusedUnitParity:
    @layouts
    @pytest.mark.parametrize("n", [BLK, 3 * BLK, 4 * BLK + 777])
    def test_fast_branch(self, n, layout):
        rng = np.random.RandomState(0)
        g = rng.randn(n).astype(np.float32)
        r = (0.1 * rng.randn(n)).astype(np.float32)
        bnd = region_bounds(layout, n, (n // 3 + 5, n - 300))
        got, want, branch = run_both(g, r, 2.0, bnd, max(64, int(0.05 * n)))
        assert_all_equal(got, want)
        assert branch[0] == 0

    @layouts
    def test_residual_changes_selection(self, layout):
        # the residual add must happen BEFORE the mask: elements pushed
        # over/under the threshold by the residual flip membership
        n = 2 * BLK
        g = np.full(n, 1.9, np.float32)
        r = np.zeros(n, np.float32)
        r[::7] = 0.2                      # push every 7th over t=2.0
        bnd = region_bounds(layout, n, (500, BLK + 3))
        got, want, _ = run_both(g, r, 2.0, bnd, 1024)
        assert_all_equal(got, want)
        assert got[4] == (n + 6) // 7     # local_count

    @layouts
    def test_bit_exact_wide_dynamic_range(self, layout):
        # adversarial exponents: the staged values and acc must come back
        # bit-exact (octave-boundary magnitudes included)
        rng = np.random.RandomState(1)
        n = 2 * BLK
        g = (rng.randn(n) * 10.0 ** rng.randint(-30, 20, n)) \
            .astype(np.float32)
        g[::11] = np.exp2(rng.randint(-40, 20, len(g[::11]))) \
            .astype(np.float32)           # exact powers of two
        r = (rng.randn(n) * 1e-3).astype(np.float32)
        t = float(np.quantile(np.abs(g), 0.97))
        bnd = region_bounds(layout, n, (BLK - 1, BLK + 600))
        got, want, _ = run_both(g, r, t, bnd, 4096)
        assert_all_equal(got, want)
        for nm, a in zip(NAMES, got):
            if nm in ("acc", "values"):
                np.testing.assert_array_equal(
                    a.view(np.int32),
                    dict(zip(NAMES, want))[nm].view(np.int32),
                    err_msg=f"{nm} bitwise")

    @layouts
    def test_probe_count_unclamped(self, layout):
        # the probe threshold is used UNCLAMPED (parity with the portable
        # jnp.sum(abs >= lt * ratio), which has no min-normal clamp): at
        # t=0 the staging mask clamps (selects only nonzeros) while the
        # probe counts everything; the boundaries part the ten nonzeros
        # 4 / 6 / 0
        n = BLK
        g = np.zeros(n, np.float32)
        g[:10] = 3.0
        r = np.zeros(n, np.float32)
        bnd = region_bounds(layout, n, (4, 700))
        got, want, _ = run_both(g, r, 0.0, bnd, 64)
        assert_all_equal(got, want)
        assert got[4] == 10               # staged: nonzeros only
        assert got[5] == n                # probe at 0.0: everything
        if layout == "straddle3":
            assert got[3].tolist() == [4, 6, 0]

    @layouts
    def test_repair_branch(self, layout):
        # a few blocks overflow CAPB_FAST -> repair kernel re-stages them;
        # condition asserted directly (as the compaction tests pin it).
        # Both cuts lie inside an overflowing block, past the slots its
        # fast staging row holds
        n = SB * BLK * 3
        rng = np.random.RandomState(2)
        g = np.zeros(n, np.float32)
        g[:BLK] = 10.0 + rng.rand(BLK).astype(np.float32)
        g[5 * BLK:5 * BLK + 300] = 5.0
        r = np.zeros(n, np.float32)
        raw = np.add.reduceat(np.abs(g) >= 1.0, np.arange(0, n, BLK))
        novf = int(np.sum(raw > CAPB_FAST))
        assert 0 < novf <= _novf_cap(n // BLK)
        bnd = region_bounds(layout, n, (700, 5 * BLK + CAPB_FAST + 22))
        got, want, branch = run_both(g, r, 1.0, bnd, 2048)
        assert_all_equal(got, want)
        assert branch.tolist() == [1, novf]

    @layouts
    def test_wide_branch(self, layout):
        # most blocks overflow -> the whole-width re-stage branch
        n = SB * BLK * 2
        rng = np.random.RandomState(3)
        g = (rng.randn(n) + 3.0).astype(np.float32)
        r = (0.01 * rng.randn(n)).astype(np.float32)
        raw = np.add.reduceat(np.abs(g + r) >= 0.5, np.arange(0, n, BLK))
        assert np.sum(raw > CAPB_FAST) > _novf_cap(n // BLK)
        bnd = region_bounds(layout, n, (BLK + 700, n - 2 * BLK - 300))
        got, want, branch = run_both(g, r, 0.5, bnd, 8192)
        assert_all_equal(got, want)
        assert branch[0] == 2


def _pallas_calls(jaxpr, name):
    """Every ``pallas_call`` equation named ``name`` in ``jaxpr``, nested
    programs (pjit, shard_map, cond branches) included."""
    for eqn in jaxpr.eqns:
        if (eqn.primitive.name == "pallas_call"
                and eqn.params["name"] == name):
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _pallas_calls(sub, name)


class TestStepProgram:
    @pytest.mark.parametrize("P", [1, 8])
    def test_step_program_fused_call_outputs(self, devices, monkeypatch,
                                             P):
        """The jitted oktopk step on the Pallas path holds the fused
        kernel once, in its one form: four outputs (acc, staging rows, raw
        and probe counts). P = 1 is the shape the benchmark's sparse cell
        runs, where the exchange folds away."""
        monkeypatch.setenv("OKTOPK_PALLAS_INTERPRET", "1")
        from oktopk_tpu.collectives.api import (batched_init_state,
                                                build_allreduce_step)
        from oktopk_tpu.comm import get_mesh
        from oktopk_tpu.config import OkTopkConfig

        n = 4096
        mesh = get_mesh((P,), ("data",), devices=devices[:P])
        cfg = OkTopkConfig(n=n, num_workers=P, density=0.05, warmup_steps=0,
                           use_pallas=True)
        step = build_allreduce_step("oktopk", cfg, mesh, warmup=False,
                                    check_vma=False)
        jaxpr = jax.make_jaxpr(step)(jnp.zeros((P, n), jnp.float32),
                                     batched_init_state(cfg))
        calls = list(_pallas_calls(jaxpr.jaxpr, "oktopk_fused_select"))
        assert len(calls) == 1
        assert len(calls[0].outvars) == 4


def _via_allreduce_step(cfg, mesh, **_):
    from oktopk_tpu.collectives.api import (batched_init_state,
                                            build_allreduce_step)
    step = build_allreduce_step("oktopk", cfg, mesh, warmup=False,
                                check_vma=False)
    return step, (jnp.zeros((cfg.num_workers, cfg.n), jnp.float32),
                  batched_init_state(cfg))


def _via_quality_step(cfg, mesh, **_):
    from oktopk_tpu.collectives.api import (batched_init_state,
                                            build_quality_allreduce_step)
    from oktopk_tpu.obs.metrics_buffer import init_buffer
    from oktopk_tpu.obs.quality import QualityConfig

    q = QualityConfig()
    step = build_quality_allreduce_step("oktopk", cfg, mesh, q,
                                        warmup=False, check_vma=False)
    qbuf = jax.tree.map(
        lambda x: jnp.broadcast_to(x, (cfg.num_workers,) + x.shape),
        init_buffer(q.every, q.sig_bins))
    return step, (jnp.zeros((cfg.num_workers, cfg.n), jnp.float32),
                  batched_init_state(cfg), qbuf)


def _via_autotune_trial(cfg, mesh, monkeypatch, **_):
    """The step ``autotune/trial.py`` builds for ``time_allreduce_step``,
    caught where it would be timed."""
    from oktopk_tpu.autotune.trial import TrialRunner
    from oktopk_tpu.collectives import api

    caught = {}

    def catch(step_fn, grads, state, iters=3, warmup_iters=1):
        caught["step"], caught["args"] = step_fn, (grads, state)
        return [1.0] * iters, state

    monkeypatch.setattr(api, "time_allreduce_step", catch)
    TrialRunner(mesh=mesh, base_cfg=cfg).measure("oktopk", cfg.n,
                                                 cfg.density)
    return caught["step"], caught["args"]


def _via_sparse_grad_step(cfg, mesh, **_):
    from oktopk_tpu.optim import sgd
    from oktopk_tpu.optim.distributed import (build_sparse_grad_step,
                                              init_dist_state)

    def loss_fn(params, model_state, batch, rng):
        return jnp.sum(params["w"] * batch), (model_state, {})

    opt = sgd(0.1)
    state = init_dist_state({"w": jnp.zeros((cfg.n,), jnp.float32)}, {},
                            opt, cfg)
    step = build_sparse_grad_step(loss_fn, opt, cfg, mesh, warmup=False)
    return step, (state, jnp.zeros((cfg.num_workers, cfg.n), jnp.float32),
                  jax.random.PRNGKey(0))


def _via_hierarchical_outer(cfg, devices, **_):
    from oktopk_tpu.collectives.api import (batched_init_state,
                                            build_allreduce_step)
    from oktopk_tpu.collectives.hierarchical import make_hierarchical_config
    from oktopk_tpu.comm.mesh import hierarchical_mesh

    h = make_hierarchical_config(cfg, num_pods=2, outer="oktopk")
    step = build_allreduce_step(
        "hierarchical", h, hierarchical_mesh(2, 2, devices=devices[:4]),
        warmup=False, check_vma=False)
    return step, (jnp.zeros((cfg.num_workers, cfg.n), jnp.float32),
                  batched_init_state(h))


class TestKernelChoiceAtEveryBuilder:
    """``OkTopkConfig.use_pallas`` left at None is resolved from the mesh
    by each public step builder; one that forgot would read ``bool(None)``
    and run the portable path on the chip, silently (ROADMAP D15). Trace
    only: on a mesh that reports the kernels' platform, every builder's
    step holds the fused kernel."""

    @pytest.mark.parametrize("via", [
        _via_allreduce_step, _via_quality_step, _via_autotune_trial,
        _via_sparse_grad_step, _via_hierarchical_outer],
        ids=lambda f: f.__name__[len("_via_"):])
    def test_unset_use_pallas_resolves_to_the_fused_kernel(
            self, devices, mesh4, monkeypatch, via):
        from oktopk_tpu.config import OkTopkConfig
        from oktopk_tpu.ops import compaction

        monkeypatch.setenv("OKTOPK_PALLAS_INTERPRET", "1")
        monkeypatch.setattr(compaction, "mesh_supports_pallas",
                            lambda mesh: True)
        cfg = OkTopkConfig(n=4096, num_workers=4, density=0.05,
                           warmup_steps=0)
        assert cfg.use_pallas is None
        step, args = via(cfg, mesh=mesh4, devices=devices,
                         monkeypatch=monkeypatch)
        jaxpr = jax.make_jaxpr(step)(*args)
        assert list(_pallas_calls(jaxpr.jaxpr, "oktopk_fused_select"))


class TestFusedAlgorithmParity:
    # slow: the full oktopk step through the Pallas INTERPRETER; the
    # kernel-level branches are covered above in tier-1, and the real-chip
    # wiring by tests/test_tpu_hw.py.
    @pytest.mark.slow
    def test_fused_step_bitwise_equals_unfused(self, mesh8, monkeypatch):
        """The fused step (use_pallas=True) against the portable step
        (use_pallas=False), float32 wire: results and EVERY state leaf
        bit-identical over steps covering recompute, predicted and
        repartition branches."""
        monkeypatch.setenv("OKTOPK_PALLAS_INTERPRET", "1")
        from test_compaction import _run_oktopk_both_paths

        from oktopk_tpu.config import OkTopkConfig

        P, n = 8, 4096
        base = np.random.RandomState(5).randn(P, n).astype(np.float32)
        cfg0 = OkTopkConfig(n=n, num_workers=P, density=0.05,
                            warmup_steps=0, local_recompute_every=2,
                            global_recompute_every=2, repartition_every=4,
                            wire_dtype="float32")
        outs, states = _run_oktopk_both_paths(mesh8, cfg0, base, steps=5)
        for a, b in zip(outs[True], outs[False]):
            np.testing.assert_array_equal(a.view(np.int32),
                                          b.view(np.int32))
        for f in states[True].__dataclass_fields__:
            if f == "last_counters":
                continue   # the kernels' branch census: portable has none
            np.testing.assert_array_equal(
                np.asarray(getattr(states[True], f)),
                np.asarray(getattr(states[False], f)),
                err_msg=f"state.{f}")
