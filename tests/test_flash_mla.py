"""ops/flash_gqa.py with MLA's split heads (``flash_mla``): the kernels,
interpreted, against ``attention._attend_block`` as oracle; where they
round; the model through them; the rules for tiles and for the heads that
ride a step; what ``snapshot()`` says of the call; and that the
grouped-head programs, and the delta rule's plain form, are the parent
commit's. (The kernels compiled by
Mosaic at the benchmark's widths are in ``tests/test_flash_gqa.py``, the
one file that describes a chip.)"""

import hashlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from oktopk_tpu.models import attention
from oktopk_tpu.models import deepseek_v2 as ds
from oktopk_tpu.ops import flash_gqa
from oktopk_tpu.utils import profiling

GRADS = ("out", "dq_nope", "dq_pe", "dk_nope", "dk_pe", "dv")


def inputs(b, t, h, d, rope, dv, seed=0):
    """q_nope, q_pe, k_nope, k_pe, v and the output's cotangent."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    return tuple(jax.random.normal(k, s) for k, s in zip(ks, (
        (b, t, h, d), (b, t, h, rope), (b, t, h, d), (b, t, rope),
        (b, t, h, dv), (b, t, h, dv))))


def oracle(q_nope, q_pe, k_nope, k_pe, v, scale):
    """``_attend_block`` over one block that is the whole sequence."""
    t = q_nope.shape[1]
    return jax.vmap(lambda *seq: attention._attend_block(*seq, 0, t, scale))(
        q_nope, q_pe, k_nope, k_pe, v)


def through(fn, x, w):
    out, vjp = jax.vjp(fn, *x)
    return (out,) + vjp(w)


# name -> (d, rope, dv): DeepSeek-V2's, a small one, a wider value
WIDTHS = {"128+64|128": (128, 64, 128), "16+8|16": (16, 8, 16),
          "128+64|256": (128, 64, 256)}


# (heads, widths, tokens: three whole tiles of 8 or a ragged number, B):
# every pairing at 3 heads, and each width once at 16 (whose interpreted
# kernels take 10-25 s a case to compile)
CASES = [(3, w, t, b) for w in ("128+64|128", "16+8|16")
         for t in (24, 21) for b in (1, 2)] + [
    (16, "128+64|128", 21, 2), (16, "16+8|16", 24, 1)]


class TestKernelsAgainstTheBlockFunction:
    @pytest.mark.parametrize("h,widths,t,b", CASES)
    def test_forward_and_five_gradients(self, h, widths, t, b):
        d, rope, dv = WIDTHS[widths]
        *x, w = inputs(b, t, h, d, rope, dv)
        scale = (d + rope) ** -0.5
        got = through(lambda *a: flash_gqa.flash_mla(
            *a, scale, interpret=True, tiles=(8, 8)), x, w)
        want = through(lambda *a: oracle(*a, scale), x, w)
        assert got[4].shape == (b, t, rope)     # summed over the heads
        for name, a, e in zip(GRADS, got, want):
            np.testing.assert_allclose(a, e, rtol=2e-5, atol=2e-5,
                                       err_msg=name)

    @pytest.mark.parametrize("heads,tiles", [(2, (16, 8)), (4, (8, 16)),
                                             (8, (8, 8))])
    def test_heads_a_step_and_tiles_that_differ(self, heads, tiles):
        """Any number of heads may ride a step and a query tile may be
        another size than a key tile; a value wider than the scores'
        no-position part keeps its own width (nothing is padded to the
        other's)."""
        d, rope, dv = WIDTHS["128+64|256"]
        *x, w = inputs(1, 32, 8, d, rope, dv)
        got = through(lambda *a: flash_gqa.flash_mla(
            *a, 0.07, interpret=True, tiles=tiles, heads=heads), x, w)
        want = through(lambda *a: oracle(*a, 0.07), x, w)
        for name, a, e in zip(GRADS, got, want):
            np.testing.assert_allclose(a, e, rtol=2e-5, atol=2e-5,
                                       err_msg=name)

    def test_rounds_where_the_plain_form_rounds(self, monkeypatch):
        """What the chip computes, in MLA's form: the two score products
        round their operands to bfloat16 and are summed in float32, the
        scale comes after in float32; the NORMALISED probabilities are
        what ``p v`` and dv round; the score gradient times the scale is
        what dq and dk round, both parts of each."""
        b, t, h = 1, 24, 4
        d, rope, dv = WIDTHS["128+64|128"]
        qn, qp, kn, kp, v, w = inputs(b, t, h, d, rope, dv)
        scale = (d + rope) ** -0.5

        def rnd(x):
            return x.astype(jnp.bfloat16).astype(jnp.float32)

        seen = np.arange(t)[None] <= np.arange(t)[:, None]
        x = (jnp.einsum("bqhd,bkhd->bhqk", rnd(qn), rnd(kn))
             + jnp.einsum("bqhd,bkd->bhqk", rnd(qp), rnd(kp))) * scale
        prob = jax.nn.softmax(jnp.where(seen, x, -jnp.inf), axis=-1)
        out = jnp.einsum("bhqk,bkhd->bqhd", rnd(prob), rnd(v))
        dprob = jnp.einsum("bqhd,bkhd->bhqk", rnd(w), rnd(v))
        # the rows' sum of p dp as the kernels take it: out . dout, dout
        # rounded as the product that makes dp rounds it
        delta = jnp.einsum("bqhd,bqhd->bhq", out, rnd(w))
        dx = rnd(prob * (dprob - delta[..., None]) * scale)
        want = (out,
                jnp.einsum("bhqk,bkhd->bqhd", dx, rnd(kn)),
                jnp.einsum("bhqk,bkd->bqhd", dx, rnd(kp)),
                jnp.einsum("bhqk,bqhd->bkhd", dx, rnd(qn)),
                jnp.einsum("bhqk,bqhd->bkd", dx, rnd(qp)),
                jnp.einsum("bhqk,bqhd->bkhd", rnd(prob), rnd(w)))

        monkeypatch.setattr(flash_gqa, "_product",
                            lambda interpret: jnp.bfloat16)
        got = through(lambda *a: flash_gqa.flash_mla(
            *a, scale, interpret=True, tiles=(8, 8), heads=2),
            (qn, qp, kn, kp, v), w)
        # a rounding that falls elsewhere reads 2e-3 here
        for name, a, e in zip(GRADS, got, want):
            err = float(jnp.linalg.norm(a - e) / jnp.linalg.norm(e))
            assert err < 2e-4, (name, err)

    def test_a_bfloat16_caller_gets_bfloat16_cotangents(self):
        *x, w = inputs(1, 24, 4, 128, 64, 128)
        narrow = [a.astype(jnp.bfloat16) for a in x]
        got = through(lambda *a: flash_gqa.flash_mla(
            *a, 0.07, interpret=True, tiles=(8, 8)), narrow, w)
        want = through(lambda *a: oracle(*a, 0.07),
                       [a.astype(jnp.float32) for a in narrow], w)
        assert got[0].dtype == jnp.float32
        assert [g.dtype for g in got[1:]] == [jnp.bfloat16] * 5
        for name, a, e in zip(GRADS, got, want):
            np.testing.assert_allclose(a.astype(jnp.float32), e, rtol=2e-2,
                                       atol=2e-2, err_msg=name)


class TestRules:
    @pytest.mark.parametrize("t,r,d", [(16384, 7, 128), (8192, 8, 256),
                                       (16384, 6, 128), (16384, 8, 128)])
    def test_the_benchmarks_grouped_head_shapes_keep_their_tiles(
            self, t, r, d):
        assert flash_gqa.tile_rule(t, r, d) == (512, 512)

    def test_the_benchmarks_split_heads(self):
        """DeepSeek-V2-Lite at 4,096 tokens: the rule's heads a step and
        tiles, inside the plan; 36 of the triangle's 36 tiles."""
        r = flash_gqa.heads_a_step(16, 64)
        assert 16 % r == 0 and (r * 64) % 128 == 0
        tq, tk = flash_gqa.tile_rule(4096, r, 128, 128, 64, own=r)
        assert tq % 128 == 0 and tk % 128 == 0 and tk <= 512
        visited, causal = flash_gqa.tile_counts(4096, tq, tk, None)
        assert visited == causal
        assert flash_gqa.tile_counts(4096, 512, 512, None) == (36, 36)

    @pytest.mark.parametrize("heads,rope,want", [
        (16, 64, flash_gqa.HEADS_A_STEP), (3, 64, 3), (4, 8, 4), (10, 64, 2),
        (16, 128, flash_gqa.HEADS_A_STEP)])
    def test_heads_a_step_keeps_the_rotary_slab_whole_lane_rows(
            self, heads, rope, want):
        """... or takes all heads, whose slab is the whole array's width."""
        r = flash_gqa.heads_a_step(heads, rope)
        assert r == want and heads % r == 0
        assert (r * rope) % 128 == 0 or r == heads


@pytest.fixture
def fresh_calls(monkeypatch):
    monkeypatch.setattr(flash_gqa, "_calls", {})


def test_the_call_is_recorded(fresh_calls, monkeypatch):
    *x, _ = inputs(1, 64, 4, 16, 8, 16)
    for _ in range(2):
        attention.blocked_causal_attention(*x, 0.2, 16)
    assert profiling.snapshot()["attention"] == [
        {"kernel": False, "window": None, "tiles_visited": 10,
         "tiles_causal": 10, "kv_heads_a_step": 0}]
    monkeypatch.setenv("OKTOPK_PALLAS_INTERPRET", "1")
    attention.blocked_causal_attention(*x, 0.2, 16)
    assert profiling.snapshot()["attention"][-1] == {
        "kernel": True, "window": None, "tiles_visited": 1,
        "tiles_causal": 1, "kv_heads_a_step": flash_gqa.heads_a_step(4, 8)}


class TestTheModelThroughTheKernels:
    @pytest.fixture(scope="class")
    def job(self):
        cfg = ds.DeepseekV2Config.tiny(held_experts=(0, 1, 2, 3))
        model = ds.DeepseekV2(cfg)
        tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0, 512)
        params = jax.jit(model.init)(jax.random.PRNGKey(0), tokens)

        def loss(p):
            logits, _ = model.apply(p, tokens)
            return jnp.mean(jax.nn.logsumexp(logits, -1) - logits[..., 0])
        return loss, params, cfg.num_hidden_layers

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
        """The output and the log-sum-exp both carry ``ATTN_OUT``: the
        layer's recomputation finds the backward kernels' residuals."""
        loss, params, layers = job
        monkeypatch.setenv("OKTOPK_PALLAS_INTERPRET", "1")
        text = str(jax.make_jaxpr(jax.grad(loss))(params))
        for kernel in ("fwd", "dq", "dkv"):
            assert len(re.findall(
                rf"name=oktopk_flash_mla_{kernel}\b", text)) == layers, kernel
        assert "oktopk_flash_gqa" not in text


# ---- the programs that had to stay are the parent's -------------------------

# the benchmark's five call shapes with six to eight query heads a group,
# (B, T, H, G, d, window), and the digest of each one's forward and backward
# lowered for the TPU platform, recorded from a checkout of the parent
# commit 1dc7418 of PR 45 by ``lowered_digest`` below; PR 47's parent
# c1f60e8 reads the same five
GROUPED = {
    "smallthinker_window": ((1, 16384, 28, 4, 128, 4096), 
        "ff954608def0c7be84a22dfe956ed72a11b580a2343663f0d7b36970f9b3b147"),
    "smallthinker_global": ((1, 16384, 28, 4, 128, None), 
        "fca83e68de28ceb50facf5d981acda6359f5155a018e2c98ffdcfec6158edeb2"),
    "qwen3next_full": ((2, 8192, 16, 2, 256, None), 
        "156817cc10a32d3419d3db917e2be9a92f8a90a6cf3fe9525300f1223ba8578c"),
    "laguna_full": ((1, 16384, 48, 8, 128, None), 
        "8eb09dbdf5b96216b0eda23107a962768c84eda56cd3de9f7b011b498656ab8f"),
    "laguna_sliding": ((1, 16384, 64, 8, 128, 512), 
        "ea95e30657b1d0d300eb191424a2f477d55db3b4d42e7f427cfbdd348dd61d6e"),
}
# recorded from a checkout of c1f60e8, PR 47's parent, by the same lines:
# ``dsv2lite_dense_x1``'s call (B, T, heads, d, rope, dv), and
# ``ouro_dense_x1``'s, a group of ONE head, as it was at one key-value head
# a grid step
MLA = ((4, 4096, 16, 128, 64, 128), 
       "2a39a05ea1906e9c07333c904c8fc43c7b955d1564ca0a8801d3f26c8bf48365")
OURO = ((2, 4096, 16, 16, 128, None), 
        "e82a4bab2847258507c30695fc88777082afc6a61877043b7535e94a513859e2")


def lowered_digest(grads, shapes, monkeypatch, kernels=3):
    """SHA-256 of ``grads`` (a call's forward and backward) at float32
    arguments of ``shapes``, lowered for the TPU platform (no chip, nothing
    compiled): the StableHLO text, and each of the ``kernels`` kernels'
    Mosaic module WITHOUT its source locations in place of the serialized
    body, which embeds the call stack's line numbers and so changes with
    any line added above a kernel."""
    from jax._src import tpu_custom_call
    bodies = []
    serialize = tpu_custom_call._lower_mosaic_module_to_asm

    def keep(module, **kw):
        bodies.append(module.operation.get_asm(enable_debug_info=False))
        return serialize(module, **kw)

    monkeypatch.setattr(tpu_custom_call, "_lower_mosaic_module_to_asm", keep)
    text = jax.jit(grads).trace(*[
        jax.ShapeDtypeStruct(shape, jnp.float32) for shape in shapes]).lower(
        lowering_platforms=("tpu",)).as_text()
    assert len(bodies) == kernels
    text = re.sub(r'\\22body\\22: \\22[^\\]*\\22', "", text)
    return hashlib.sha256("\n".join([text] + bodies).encode()).hexdigest()


def grouped_digest(b, t, h, g, d, window, monkeypatch):
    def grads(q, k, v, w):
        return jax.grad(lambda q, k, v: jnp.sum(flash_gqa.flash_gqa(
            q, k, v, d ** -0.5, window, save_as=attention.ATTN_OUT,
            interpret=False) * w), (0, 1, 2))(q, k, v)

    return lowered_digest(grads, [(b, t, h, d), (b, t, g, d), (b, t, g, d),
                                  (b, t, h, d)], monkeypatch)


def split_digest(b, t, h, d, rope, dv, monkeypatch):
    def grads(q_nope, q_pe, k_nope, k_pe, v, w):
        return jax.grad(lambda *x: jnp.sum(flash_gqa.flash_mla(
            *x, (d + rope) ** -0.5, save_as=attention.ATTN_OUT,
            interpret=False) * w), (0, 1, 2, 3, 4))(
            q_nope, q_pe, k_nope, k_pe, v)

    return lowered_digest(grads, [
        (b, t, h, d), (b, t, h, rope), (b, t, h, d), (b, t, rope),
        (b, t, h, dv), (b, t, h, dv)], monkeypatch)


@pytest.mark.parametrize("call", list(GROUPED))
def test_the_grouped_head_programs_are_the_parents(call, monkeypatch):
    """MLA's layout (PR 45) and the packs of key-value heads (PR 47) went
    into the kernels that grouped heads run: at the call shapes of
    ``smallthinker_dense_x1``, ``qwen3next_dense_x1`` and
    ``laguna_xs2_dense_x1``, whose groups of six to eight heads fill a
    step, nothing of what XLA:TPU and Mosaic are handed changed, so those
    cells' steps are the parent's."""
    shape, at_parent = GROUPED[call]
    assert grouped_digest(*shape, monkeypatch) == at_parent


def test_mlas_program_is_the_parents(monkeypatch):
    """MLA became the pack ``own = r`` with a rotary part and no branch of
    its own: ``dsv2lite_dense_x1``'s call is handed to XLA:TPU and Mosaic
    as the parent handed it."""
    shape, at_parent = MLA
    assert split_digest(*shape, monkeypatch) == at_parent


def test_a_group_of_one_head_is_another_program(monkeypatch):
    """... and ``ouro_dense_x1``'s call is not: eight key-value heads ride
    its grid step where one did."""
    shape, at_parent = OURO
    assert flash_gqa.kv_heads_a_step(*shape[2:5]) == 8
    assert grouped_digest(*shape, monkeypatch) != at_parent


# ``qwen3next_dense_x1``'s delta rule (B, T, key heads, value heads, dk, dv,
# chunk, segment) in the form it takes OFF a TPU backend, forward and five
# gradients: recorded from a checkout of fbb78f3, PR 48's parent, by
# ``delta_rule_digest`` below
DELTA_RULE = ((2, 8192, 16, 32, 128, 128, 64, 1024),
              "abb9c9a60e0656e035f8ece899c543e0505158606644ec6ba837ace1edccf463")


def delta_rule_digest(b, t, hk, hv, dk, dv, chunk, segment, monkeypatch):
    from oktopk_tpu.models import qwen3_next

    def grads(q, k, v, g, beta, w):
        return jax.grad(lambda *x: jnp.sum(qwen3_next.gated_delta_rule(
            *x, chunk, segment) * w), (0, 1, 2, 3, 4))(q, k, v, g, beta)

    return lowered_digest(grads, [
        (b, t, hk, dk), (b, t, hk, dk), (b, t, hv, dv), (b, t, hv),
        (b, t, hv), (b, t, hv, dv)], monkeypatch, kernels=0)


def test_the_delta_rules_plain_form_is_the_parents(monkeypatch):
    """PR 48 put Pallas kernels behind the walk over a segment's chunks
    where the program is compiled for a TPU; off one (this process: the
    chooser asks the backend, not the platform lowered for)
    ``gated_delta_rule`` is the parent's text, ``lax.scan`` and all."""
    shape, at_parent = DELTA_RULE
    assert delta_rule_digest(*shape, monkeypatch) == at_parent
