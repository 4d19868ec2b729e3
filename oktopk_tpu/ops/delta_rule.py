"""The gated delta rule's walk over a segment's chunks as two Pallas kernels
whose state never leaves VMEM, forward or backward.

What is walked (``models/qwen3_next.chunk_gated_delta_rule``): the
chunk-local part of the rule (decays, ``k k^T``, the inverse, ``u``, ``w``,
``q k^T``) is parallel over a segment's N chunks and stays in XLA; what is
left carries the [dk, dv] state S of every value head from chunk to chunk,

    v' = u - w S        o = q_dec S + qk v'        S <- S last + k_dec^T v'

with u [C, dv], w, q_dec and k_dec [C, dk], qk [C, C] and ``last`` one
number a chunk and head. As a ``lax.scan`` each of the four products is a
fusion of its own and the state goes through HBM between them, as do all
eight of the backward scan that JAX derives: on a v5e that walk was ~60 ms
of ``qwen3next_dense_x1``'s 921 ms step, and the kernels take 42 (0.40 us a
chunk and head forward, 0.52 backward, which is what the backward kernel's
bytes take at the chip's memory bandwidth; PERF.md, Findings, PR 48). Here:

* **Forward** (``oktopk_delta_rule_fwd``): grid (sequence, pack of value
  heads, block of chunks), the chunks innermost and in order. The pack's
  states live in the block of the outgoing state, which stays in VMEM for
  the whole walk: set from the incoming state at the first chunk, written
  out once after the last. Under differentiation the kernel also writes the
  state BEFORE each chunk, [N, B, Hv, dk, dv]: the residual that the
  backward kernel reads, and what the scan kept.
* **Backward** (``oktopk_delta_rule_bwd``): the same grid walked from the
  last chunk to the first, the state's cotangent dS in VMEM the same way.
  A chunk recomputes v' from its saved state and then, with ``dv' = qk^T do
  + k_dec dS``: ``du = dv'``, ``dqk = do v'^T``, ``dk_dec = v' dS^T``,
  ``dlast = sum(S dS)``, ``[dq_dec; -dw] = [do; dv'] S^T`` and ``dS <- dS
  last + [q_dec; -w]^T [do; dv']``: the two pairs of products that share an
  operand are one product of twice the rows each, seven a chunk (as the
  forward kernel's ``[w; q_dec] S``: by Mosaic's schedule for a v5e what
  binds these kernels is the one store slot a bundle, and nine separate
  products store a sixth more than seven).
* **Several heads ride a grid step** (:func:`heads_a_step`): a chunk's
  products depend on each other (``w S`` before ``k_dec^T v'``, and that
  before the next chunk's), the heads of a pack do not, so the matrix units
  have another head's product to take while one waits (one head a step
  read a sixth to a quarter slower on the chip, two to eight alike). And as many chunks
  a block as :func:`chunks_a_block` plans VMEM for (one to sixteen read
  alike). Both from the call's shapes alone.
* **Precision**: float32 operands and the six-pass product everywhere
  (``precision=HIGHEST``; it is Mosaic's own default for float32 too),
  float32 state and sums. Nothing is rounded to bfloat16.

Off a TPU backend the kernels run only interpreted (tests); see
``models/qwen3_next.chunk_gated_delta_rule`` for who chooses.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

from oktopk_tpu.ops.flash_gqa import (_NN, _NT, LANES, VMEM_LIMIT, VMEM_PLAN,
                                      _kernels_here)

SUBLANES = 8
# a^T . b over the last two dims, beside flash_gqa's a . b and a . b^T
_TN = (((0,), (0,)), ((), ()))
# the most value heads that ride one grid step: on a v5e two, four and eight
# read alike and one a sixth to a quarter slower (PERF.md, Findings, PR 48);
# every head more is its share of both kernels' text again
HEADS_A_STEP = 8

# every distinct call traced in this process, for
# ``utils/profiling.snapshot``: what ran it and how (static)
_calls = {}


def calls():
    """``[{"kernel", "segment", "chunk", "value_heads", "dk", "dv",
    "heads_a_step", "chunks_a_block"}, ...]``, an entry a distinct call
    shape, in the order first traced."""
    return [dict(c) for c in _calls.values()]


def heads_a_step(hv: int) -> int:
    """The value heads that ride one grid step: as many as divide ``hv``
    and are not over ``HEADS_A_STEP``."""
    return max(r for r in range(1, min(hv, HEADS_A_STEP) + 1) if hv % r == 0)


def vmem_planned(chunks: int, heads: int, c: int, dk: int, dv: int) -> int:
    """The bytes that the backward kernel, the larger of the two, keeps in
    VMEM for a grid step of ``chunks`` chunks and ``heads`` heads: a
    chunk's inputs (do, u, w, qk, q_dec, k_dec, last, its saved state) and
    its six gradients, two buffers each, a row of qk padded to whole lanes
    and a chunk's one number to a whole tile."""
    def lanes(x):
        return -(-x // LANES) * LANES

    a_chunk = (3 * c * lanes(dv) + 6 * c * lanes(dk) + 2 * c * lanes(c)
               + 2 * SUBLANES * LANES + dk * lanes(dv))
    return 2 * chunks * heads * a_chunk * 4


def chunks_a_block(n: int, heads: int, c: int, dk: int, dv: int) -> int:
    """The chunks of one grid step: as many as divide ``n`` and stay
    inside ``VMEM_PLAN`` (:func:`vmem_planned`)."""
    return max((m for m in range(1, n + 1) if n % m == 0
                and vmem_planned(m, heads, c, dk, dv) <= VMEM_PLAN),
               default=1)


def on_this_platform(n: int, c: int, hv: int, dk: int, dv: int) -> bool:
    """Whether the kernels run a segment of ``n`` chunks of ``c`` tokens
    here (``flash_gqa._kernels_here``: a state's rows and columns are whole
    lane rows; and a chunk whole sublanes), and the call's record. The
    plain form has no grid and says 0 heads a step and 0 chunks a block."""
    kernel = _kernels_here(dk, dv) and c % SUBLANES == 0
    heads = heads_a_step(hv) if kernel else 0
    _calls.setdefault((kernel, n, c, hv, dk, dv), {
        "kernel": kernel, "segment": n * c, "chunk": c, "value_heads": hv,
        "dk": dk, "dv": dv, "heads_a_step": heads,
        "chunks_a_block": chunks_a_block(n, heads, c, dk, dv) if kernel
        else 0})
    return kernel


# ---- kernels ---------------------------------------------------------------

class _Plan(NamedTuple):
    """What a call is, beside its arrays: static, and hashable."""
    heads: int              # value heads a grid step
    chunks: int             # chunks a grid step
    interpret: bool


def _dot(a, b, dims):
    return lax.dot_general(a, b, dims, precision=lax.Precision.HIGHEST,
                           preferred_element_type=jnp.float32)


def _fwd_kernel(u_ref, w_ref, qk_ref, q_ref, k_ref, last_ref, s0_ref,
                o_ref, s_ref, *kept_ref, p: _Plan):
    """A block of chunks, first to last, for a pack of heads. ``s_ref``,
    the outgoing state's block, holds the pack's states between the grid's
    steps; ``kept_ref``: where each chunk's incoming state goes (under
    differentiation)."""
    import jax.experimental.pallas as pl

    @pl.when(pl.program_id(2) == 0)
    def _():
        s_ref[...] = s0_ref[...]

    def one_chunk(c, carry):
        for h in range(p.heads):
            s = s_ref[h]
            if kept_ref:
                kept_ref[0][c, h] = s
            w = w_ref[c, h]
            rows = w.shape[0]
            # w S and q_dec S as one product of twice the rows
            through_s = _dot(jnp.concatenate([w, q_ref[c, h]], axis=0), s,
                             _NN)
            v_new = u_ref[c, h] - through_s[:rows]
            o_ref[c, h] = through_s[rows:] + _dot(qk_ref[c, h], v_new, _NN)
            s_ref[h] = s * last_ref[c, h] + _dot(k_ref[c, h], v_new, _TN)
        return carry

    lax.fori_loop(0, p.chunks, one_chunk, 0)


def _bwd_kernel(do_ref, u_ref, w_ref, qk_ref, q_ref, k_ref, last_ref,
                kept_ref, ds1_ref, du_ref, dw_ref, dqk_ref, dq_ref, dk_ref,
                dlast_ref, ds_ref, *, p: _Plan):
    """A block of chunks, last to first. ``ds_ref``, the block of the
    incoming state's cotangent, holds the pack's dS between the grid's
    steps, from ``ds1_ref``, the outgoing state's cotangent."""
    import jax.experimental.pallas as pl

    @pl.when(pl.program_id(2) == 0)
    def _():
        ds_ref[...] = ds1_ref[...]

    def one_chunk(i, carry):
        c = p.chunks - 1 - i
        for h in range(p.heads):
            s, ds, do = kept_ref[c, h], ds_ref[h], do_ref[c, h]
            w, q, k = w_ref[c, h], q_ref[c, h], k_ref[c, h]
            rows = w.shape[0]
            v_new = u_ref[c, h] - _dot(w, s, _NN)
            dv_new = _dot(qk_ref[c, h], do, _TN) + _dot(k, ds, _NN)
            du_ref[c, h] = dv_new
            dqk_ref[c, h] = _dot(do, v_new, _NT)
            dk_ref[c, h] = _dot(v_new, ds, _NT)
            dlast_ref[c, h] = jnp.sum(s * ds, keepdims=True)
            # o and v' both read S, as q_dec S and -w S
            both = jnp.concatenate([do, dv_new], axis=0)
            through_s = _dot(both, s, _NT)
            dq_ref[c, h] = through_s[:rows]
            dw_ref[c, h] = -through_s[rows:]
            ds_ref[h] = ds * last_ref[c, h] + _dot(
                jnp.concatenate([q, -w], axis=0), both, _TN)
        return carry

    lax.fori_loop(0, p.chunks, one_chunk, 0)


# ---- calls -----------------------------------------------------------------

@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3, 5))
def _call(kernel, name, p: _Plan, backward: bool, inputs, outputs):
    """``kernel`` over the grid (sequence, pack of heads, block of chunks).
    ``inputs`` are arrays and ``outputs`` shapes, and the rank says which
    kind: [N, B, Hv, rows, columns], a block of chunks at a time (the last
    block first where ``backward``), or [B, Hv, dk, dv], a pack's states,
    one block held for the whole walk. Under ``jax.jit`` so that a model's
    layers, which call with the same shapes, trace a kernel's unrolled
    heads once and not once a layer and pass (a second and more of
    ``qwen3next_dense_x1``'s set-up otherwise)."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    n, b, hv = inputs[0].shape[:3]
    blocks = n // p.chunks

    def spec(shape):
        if len(shape) == 4:
            return pl.BlockSpec((None, p.heads) + shape[2:],
                                lambda bb, hh, cc: (bb, hh, 0, 0))
        return pl.BlockSpec(
            (p.chunks, None, p.heads) + shape[3:], lambda bb, hh, cc: (
                blocks - 1 - cc if backward else cc, bb, hh, 0, 0))

    return pl.pallas_call(
        functools.partial(kernel, p=p),
        grid=(b, hv // p.heads, blocks),
        in_specs=[spec(x.shape) for x in inputs],
        out_specs=[spec(shape) for shape in outputs],
        out_shape=[jax.ShapeDtypeStruct(shape, jnp.float32)
                   for shape in outputs],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=p.interpret, name=name)(*inputs)


def _forward(p: _Plan, u, w, qk, q_dec, k_dec, last, state, keep: bool):
    """(o, the state after the last chunk[, the state before each chunk])."""
    return _call(
        _fwd_kernel, "oktopk_delta_rule_fwd", p, False,
        (u, w, qk, q_dec, k_dec, last, state),
        (u.shape, state.shape)
        + ((u.shape[:3] + state.shape[2:],) if keep else ()))


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _walk(p: _Plan, u, w, qk, q_dec, k_dec, last, state):
    return tuple(_forward(p, u, w, qk, q_dec, k_dec, last, state, False))


def _walk_fwd(p: _Plan, *x):
    o, state, kept = _forward(p, *x, True)
    return (o, state), (x[:-1], kept)


def _walk_bwd(p: _Plan, saved, cotangents):
    chunked, kept = saved
    do, ds = cotangents
    return tuple(_call(
        _bwd_kernel, "oktopk_delta_rule_bwd", p, True,
        (do,) + chunked + (kept, ds),
        tuple(x.shape for x in chunked) + (ds.shape,)))


_walk.defvjp(_walk_fwd, _walk_bwd)


def delta_rule(u, w, qk, q_dec, k_dec, last, state, *,
               interpret: Optional[bool] = None,
               heads: Optional[int] = None, chunks: Optional[int] = None):
    """A segment's chunks walked from ``state`` [B, Hv, dk, dv]: u [N, B,
    Hv, C, dv], w, q_dec and k_dec [N, B, Hv, C, dk], qk [N, B, Hv, C, C],
    last [N, B, Hv], all float32 -> o [N, B, Hv, C, dv] and the state after
    the last chunk, the numbers of the ``lax.scan`` in the module
    docstring. Differentiable in all seven. ``interpret``: left out, the
    kernels are compiled on a TPU backend and interpreted off one (a test
    that compiles for a described chip says False). ``heads`` and
    ``chunks``, a grid step's, are :func:`heads_a_step`'s and
    :func:`chunks_a_block`'s where not given (tests give small ones)."""
    n, _, hv, c, dv = u.shape
    dk = w.shape[-1]
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    heads = heads or heads_a_step(hv)
    plan = _Plan(heads, chunks or chunks_a_block(n, heads, c, dk, dv),
                 interpret)
    # a chunk's one number as a [1, 1] block of its own
    return _walk(plan, u, w, qk, q_dec, k_dec, last[..., None, None], state)
