"""models/attention.py: the off-chip form's program, pinned to the parent
commit's, and the models that call ``blocked_causal_gqa`` through the
interpreted flash kernels (the kernels themselves: ``tests/
test_flash_gqa.py``, ``tests/test_flash_mla.py``)."""

import hashlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from oktopk_tpu.models import (attention, laguna, lfm2, qwen3_next,
                               smallthinker)


def _f32(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.float32)


# name -> (entry point, its arrays, what follows them)
OFF_CHIP = {
    "grouped_causal": (
        "blocked_causal_gqa",
        (_f32(2, 64, 4, 32), _f32(2, 64, 2, 32), _f32(2, 64, 2, 32)),
        (0.2, 16)),
    "grouped_window_24": (
        "blocked_causal_gqa",
        (_f32(2, 64, 4, 32), _f32(2, 64, 2, 32), _f32(2, 64, 2, 32)),
        (0.2, 16, 24)),
    "mla": (
        "blocked_causal_attention",
        (_f32(2, 64, 4, 16), _f32(2, 64, 4, 8), _f32(2, 64, 4, 16),
         _f32(2, 64, 8), _f32(2, 64, 4, 16)),
        (0.3, 16)),
}


def grad_jaxpr_digest(fn, arrays, rest):
    """SHA-256 of the text of ``fn``'s gradient program in every array."""
    grad = jax.grad(lambda *a: jnp.sum(fn(*a, *rest)),
                    argnums=tuple(range(len(arrays))))
    return hashlib.sha256(str(jax.make_jaxpr(grad)(*arrays)).encode()
                          ).hexdigest()


# recorded from a checkout of commit bbfda02 by grad_jaxpr_digest, there of
# qwen3_next.blocked_causal_gqa and deepseek_v2.blocked_causal_attention,
# each round a block loop of its own
AT_PARENT = {
    "grouped_causal": (
        "f61f8b79c5061ccc03131e60824b792887c0c578236fc5dc08d732182750c524"),
    "grouped_window_24": (
        "fe75c51140f876a3f3967abe5dcaa02ae50699aec85b3ea64c49d534a08625cc"),
    "mla": (
        "28e44d2efe47c0ffdec7db840d2355b4d0dcae090ff8037dc776e58368eeac23"),
}


@pytest.mark.parametrize("case", list(OFF_CHIP))
def test_the_one_block_loop_traces_the_parents_program(case):
    """The two ``_blocked_xla`` became one: forward and backward, the
    jaxpr of each entry point off the chip is the text the parent's gave
    (four blocks of 16 queries; the window is shorter than the sequence)."""
    name, arrays, rest = OFF_CHIP[case]
    assert grad_jaxpr_digest(getattr(attention, name), arrays,
                             rest) == AT_PARENT[case]


def _tiny(family):
    if family == "smallthinker":
        cfg = smallthinker.SmallThinkerConfig.tiny(
            held_experts=(0, 1, 2, 3))
        return smallthinker.SmallThinker(cfg), 4
    if family == "laguna":
        cfg = laguna.LagunaConfig.tiny(held_experts=(0, 1, 2, 3))
        return laguna.Laguna(cfg), 5
    if family == "lfm2":        # one attention layer among four convs
        cfg = lfm2.Lfm2Config.tiny(held_experts=(0, 1, 2, 3))
        return lfm2.Lfm2(cfg), 1
    cfg = qwen3_next.Qwen3NextConfig.tiny(held_experts=(0, 1, 2, 3))
    return qwen3_next.Qwen3Next(cfg), 1


class TestModelsThroughTheKernels:
    @pytest.fixture(params=["smallthinker", "qwen3_next", "laguna", "lfm2"])
    def job(self, request):
        model, layers = _tiny(request.param)
        tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0, 512)
        params = jax.jit(model.init)(jax.random.PRNGKey(0), tokens)

        def loss(p):
            logits, _ = model.apply(p, tokens)
            return jnp.mean(jax.nn.logsumexp(logits, -1) - logits[..., 0])
        return loss, params, layers

    def test_loss_and_gradients_as_the_xla_form(self, job, monkeypatch):
        loss, params, _ = job
        want = jax.jit(jax.value_and_grad(loss))(params)
        monkeypatch.setenv("OKTOPK_PALLAS_INTERPRET", "1")
        got = jax.jit(jax.value_and_grad(loss))(params)
        np.testing.assert_allclose(got[0], want[0], rtol=1e-6)
        for a, e in zip(jax.tree.leaves(got[1]), jax.tree.leaves(want[1])):
            np.testing.assert_allclose(a, e, rtol=1e-4, atol=1e-6)

    def test_one_forward_kernel_a_layer_under_the_layers_remat(
            self, job, monkeypatch):
        loss, params, layers = job
        monkeypatch.setenv("OKTOPK_PALLAS_INTERPRET", "1")
        text = str(jax.make_jaxpr(jax.grad(loss))(params))
        for kernel in ("fwd", "dq", "dkv"):
            assert len(re.findall(
                rf"name=oktopk_flash_gqa_{kernel}\b", text)) == layers, kernel
