"""ops/delta_rule.py: the kernels, interpreted, against the ``lax.scan``
they replace (``qwen3_next._scan_chunks``) as oracle; ``gated_delta_rule``
through them against the token recurrence at lane-wide heads; the rules for
the heads and chunks of a grid step; who chooses, and what ``snapshot()``
says of the call. (The kernels compiled by Mosaic at the benchmark's widths
are in ``tests/test_flash_gqa.py``, the one file that describes a chip; the
plain form's program is pinned in ``tests/test_flash_mla.py``.)"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from test_qwen3_next import delta_inputs, token_recurrence

from oktopk_tpu.models import qwen3_next as qn
from oktopk_tpu.ops import delta_rule
from oktopk_tpu.ops.flash_gqa import VMEM_PLAN
from oktopk_tpu.utils import profiling

NAMES = ("o", "state", "du", "dw", "dqk", "dq_dec", "dk_dec", "dlast",
         "dstate")


def stacked(n, b, hv, c, dk, dv, decay, seed=0):
    """What a segment's walk takes, sized as the chunk-local part leaves
    it, a NON-ZERO incoming state last, and the two cotangents. ``decay``:
    round what a chunk leaves of the state."""
    rng = np.random.RandomState(seed)
    normal = lambda *shape: jnp.asarray(rng.standard_normal(shape),
                                        jnp.float32)
    x = (normal(n, b, hv, c, dv), normal(n, b, hv, c, dk) * .3,
         normal(n, b, hv, c, c) * .3, normal(n, b, hv, c, dk),
         normal(n, b, hv, c, dk) * .3,
         jnp.asarray(decay ** rng.uniform(.5, 1.5, (n, b, hv)), jnp.float32),
         normal(b, hv, dk, dv))
    return x, (normal(n, b, hv, c, dv), normal(b, hv, dk, dv))


def through(fn, x, cotangents):
    """``fn``'s two outputs and its seven gradients."""
    def both(x, cotangents):
        out, vjp = jax.vjp(fn, *x)
        return tuple(out) + vjp(cotangents)
    return jax.jit(both)(x, cotangents)


def assert_close(got, want):
    """To a few float32 roundings of the largest number: a state that
    sixteen chunks have grown carries the sums' order."""
    for name, a, e in zip(NAMES, got, want):
        assert a.shape == e.shape, name
        np.testing.assert_allclose(
            a, e, rtol=2e-5, atol=2e-6 * float(jnp.max(jnp.abs(e))),
            err_msg=name)


class TestKernelsAgainstTheScan:
    # chunks a segment, heads and chunks a grid step (None: the rule's),
    # and what a chunk leaves of the state: near 0 and near 1 at one chunk,
    # at two and at sixteen
    @pytest.mark.parametrize("n,heads,chunks,decay", [
        (1, 1, 1, 0.5), (1, 4, 1, 1e-6), (2, 2, 1, 0.999999),
        (2, 4, 2, 0.5), (16, 4, 4, 0.5), (16, 4, 4, 1e-6),
        (16, 4, 4, 0.999999), (16, 1, 16, 0.5), (16, 2, 2, 1e-6),
        (16, None, None, 0.999999)])
    def test_outputs_final_state_and_seven_gradients(self, n, heads, chunks,
                                                     decay):
        """From a non-zero state, with a cotangent on the outgoing one."""
        x, cotangents = stacked(n, 2, 4, 8, 16, 16, decay)
        got = through(lambda *a: delta_rule.delta_rule(
            *a, interpret=True, heads=heads, chunks=chunks), x, cotangents)
        want = through(qn._scan_chunks, x, cotangents)
        assert_close(got, want)

    def test_a_state_of_other_width_than_height(self):
        x, cotangents = stacked(2, 1, 2, 8, 16, 32, 0.5)
        got = through(lambda *a: delta_rule.delta_rule(*a, interpret=True),
                      x, cotangents)
        want = through(qn._scan_chunks, x, cotangents)
        assert_close(got, want)

    def test_the_state_before_a_chunk_is_kept_only_for_a_backward_pass(self):
        """A forward pass alone (the segments' first walk under their
        ``jax.checkpoint``) writes no [N, B, Hv, dk, dv]; under
        differentiation one forward kernel does, and one backward kernel
        reads it."""
        x, (wo, _) = stacked(2, 1, 2, 8, 16, 16, 0.5)
        walk = lambda *a: delta_rule.delta_rule(*a, interpret=True)
        kept = "f32[2,1,2,16,16]"
        forward = str(jax.make_jaxpr(walk)(*x))
        assert forward.count("name=oktopk_delta_rule_fwd") == 1
        assert kept not in forward
        both = str(jax.make_jaxpr(jax.grad(
            lambda *a: jnp.sum(walk(*a)[0] * wo), range(7)))(*x))
        assert kept in both
        for kernel in ("fwd", "bwd"):
            assert len(re.findall(rf"name=oktopk_delta_rule_{kernel}\b",
                                  both)) == 1, kernel


class TestThroughTheModelsRule:
    """``gated_delta_rule`` at lane-wide heads (dk = dv = 128), two
    segments of two chunks, a key head shared by two value heads, the
    kernels interpreted."""
    SHAPE = dict(b=2, t=32, hk=2, hv=4, dk=128, dv=128)

    @pytest.fixture
    def kernels(self, monkeypatch):
        monkeypatch.setenv("OKTOPK_PALLAS_INTERPRET", "1")
        monkeypatch.setattr(delta_rule, "_calls", {})

    def by_token(self, q, k, v, g, beta):
        return token_recurrence(*(jnp.repeat(x, 2, axis=2) for x in (q, k)),
                                v, g, beta)

    @pytest.mark.parametrize("decay", [0.5, 1e-6, 0.999999])
    def test_forward_and_gradients_are_the_token_recurrences(self, kernels,
                                                             decay):
        args = delta_inputs(**self.SHAPE, decay=decay)

        def of(rule):
            def loss(*x):
                o = rule(*x)
                return jnp.sum(o * jnp.cos(o)), o
            return jax.value_and_grad(loss, argnums=range(5),
                                      has_aux=True)(*args)

        (_, o), grads = of(lambda *x: qn.gated_delta_rule(*x, 8, 16))
        (_, want), want_grads = of(self.by_token)
        assert profiling.snapshot()["delta_rule"] == [{
            "kernel": True, "segment": 16, "chunk": 8, "value_heads": 4,
            "dk": 128, "dv": 128, "heads_a_step": 4, "chunks_a_block": 2}]
        np.testing.assert_allclose(o, want, rtol=2e-4, atol=2e-6)
        for got, ref in zip(grads, want_grads):
            assert bool(jnp.all(jnp.isfinite(got)))
            np.testing.assert_allclose(got, ref, rtol=2e-3, atol=2e-5)

    def test_a_decay_left_out_is_caught(self, kernels):
        """The control of the comparison, on the kernel path."""
        q, k, v, g, beta = delta_inputs(**self.SHAPE, decay=0.5)
        o = qn.gated_delta_rule(q, k, v, jnp.zeros_like(g), beta, 8, 16)
        assert profiling.snapshot()["delta_rule"][0]["kernel"]
        want = self.by_token(q, k, v, g, beta)
        assert float(jnp.max(jnp.abs(o - want))) > 0.1 * float(
            jnp.max(jnp.abs(want)))

    def test_one_forward_kernel_under_the_segments_checkpoint(self, kernels):
        """A segment's walk runs once forward, once more in its
        recomputation (the kernel that keeps the states) and once
        backward: three kernels in the gradient's program, inside the scans
        over the segments."""
        args = delta_inputs(**self.SHAPE, decay=0.5)
        text = str(jax.make_jaxpr(jax.grad(lambda *x: jnp.sum(
            qn.gated_delta_rule(*x, 8, 16)), range(5)))(*args))
        assert len(re.findall(r"name=oktopk_delta_rule_fwd\b", text)) == 2
        assert len(re.findall(r"name=oktopk_delta_rule_bwd\b", text)) == 1


class TestWhoChooses:
    @pytest.fixture(autouse=True)
    def fresh_calls(self, monkeypatch):
        monkeypatch.setattr(delta_rule, "_calls", {})

    def walk(self, dk, dv, chunk=8):
        q, k, v, g, beta = delta_inputs(1, 32, 2, 4, dk, dv, 0.5)
        return str(jax.make_jaxpr(lambda *x: qn.chunk_gated_delta_rule(
            *x, jnp.zeros((1, 4, dk, dv)), chunk))(q, k, v, g, beta))

    @pytest.mark.parametrize("dk,dv", [(128, 128), (16, 16)])
    def test_off_a_tpu_the_plain_scan(self, dk, dv):
        """Lane-wide heads or ``qwen3_next_tiny``'s: off a TPU backend the
        ``lax.scan``, and the record says so."""
        text = self.walk(dk, dv)
        assert "oktopk_delta_rule" not in text and "scan[" in text
        assert profiling.snapshot()["delta_rule"] == [{
            "kernel": False, "segment": 32, "chunk": 8, "value_heads": 4,
            "dk": dk, "dv": dv, "heads_a_step": 0, "chunks_a_block": 0}]

    @pytest.mark.parametrize("dk,dv,chunk,kernel", [
        (128, 128, 64, True), (128, 256, 8, True), (16, 16, 8, False),
        (128, 64, 8, False), (128, 128, 4, False)])
    def test_compiled_for_a_tpu_the_shapes_choose(self, monkeypatch, dk, dv,
                                                  chunk, kernel):
        """The kernels where a state's rows and columns are whole lane rows
        and a chunk whole sublanes; ``qwen3_next_tiny``'s 16-wide heads
        keep the scan there too."""
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        assert delta_rule.on_this_platform(4, chunk, 4, dk, dv) is kernel
        record = profiling.snapshot()["delta_rule"][0]
        assert record["kernel"] is kernel
        assert (record["heads_a_step"] > 0) is kernel

    def test_interpreted_where_asked_at_any_width(self, monkeypatch):
        monkeypatch.setenv("OKTOPK_PALLAS_INTERPRET", "1")
        assert "name=oktopk_delta_rule_fwd" in self.walk(16, 16)

    def test_a_call_is_recorded_once_a_shape(self):
        for _ in range(2):
            self.walk(16, 16)
        self.walk(16, 16, chunk=16)
        assert [c["chunk"] for c in profiling.snapshot()["delta_rule"]] == [
            8, 16]

    def test_empty_without_such_a_layer(self):
        assert profiling.snapshot()["delta_rule"] == []


class TestAGridStep:
    @pytest.mark.parametrize("hv,want", [(32, 8), (4, 4), (6, 6), (12, 6),
                                         (7, 7), (11, 1), (1, 1)])
    def test_heads_a_step_divide_the_value_heads(self, hv, want):
        assert delta_rule.heads_a_step(hv) == want

    @pytest.mark.parametrize("n,heads,c,dk,dv", [
        (16, 8, 64, 128, 128),      # qwen3next_dense_x1's call
        (16, 1, 64, 128, 128), (128, 8, 64, 128, 128), (5, 8, 64, 128, 256),
        (16, 4, 8, 16, 16), (3, 8, 256, 256, 256)])
    def test_chunks_a_block_divide_the_segment_inside_the_plan(
            self, n, heads, c, dk, dv):
        m = delta_rule.chunks_a_block(n, heads, c, dk, dv)
        assert n % m == 0
        planned = delta_rule.vmem_planned
        assert m == 1 or planned(m, heads, c, dk, dv) <= VMEM_PLAN
        bigger = [k for k in range(m + 1, n + 1) if n % k == 0]
        assert all(planned(k, heads, c, dk, dv) > VMEM_PLAN for k in bigger)

    def test_the_benchmarks_call(self):
        """``qwen3next_dense_x1``: 32 value heads, segments of 16 chunks of
        64 tokens, states of [128, 128]."""
        heads = delta_rule.heads_a_step(32)
        assert (heads, delta_rule.chunks_a_block(16, heads, 64, 128, 128)) \
            == (8, 4)
