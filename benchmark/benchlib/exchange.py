"""Plain reference of one predicted-threshold step of the Ok-Topk exchange,
and the numbers that hold the program to it.

``jax.numpy`` in float32, no kernels, no collectives, nothing of the
program. What it is handed is the state the program had *before* the step
(each worker's residual r_i, its threshold, the global threshold, the
thresholds' drift rate, the region boundaries) and each worker's gradient
g_i from the plain model reference. From those alone it says what the step
has to deliver and what every worker has to keep, as the paper and
``collectives/oktopk.py``'s documented behaviour state it:

    acc_i    = g_i + r_i
    sent_i   = |acc_i| >= lt_i                    lt_i = threshold_i * drift_i
    reduced  = sum over the senders of wire(acc_i)       (wire = bfloat16)
    winner   = |reduced| >= gt_o                  gt_o = global threshold * drift
                                                  of the region's owner o
    ghat     = wire(reduced) / P at the winners, 0 elsewhere
    r_i'     = acc_i                               off the winners
               acc_i - wire(acc_i)                 at a winner that i sent
               0                                   at a winner that i did not
                                                   send (the mass is discarded,
                                                   as VGG/allreducer.py:1051-52)
               + reduced - wire(reduced)           at a winner of i's own
                                                   region (the owner keeps the
                                                   second rounding)

On a step that recomputes a threshold exactly or repartitions the regions
(one in 32 and one in 64) the thresholds are made inside the step; such a
step is not compared. A worker's buffer to a region holds ``cap_pair``
values and a region's gather ``cap_gather``; what passes a capacity stays
in the residual, which this reference does not follow: it counts them
(``over_capacity``) and the harness then compares another step.

Numbers (shares; lower is closer):

- ``delivered_gap``: || ghat_program - ghat || / || ghat ||, over all
  coordinates, with ghat_program recovered from the program's parameters
  and momentum round the step. A dropped payload, a wrong divisor or a
  coarser wire show here.
- ``residual_gap``: the largest over the workers of
  || r_i'_program - r_i' || / || acc_i ||. Mass that is neither delivered
  nor kept, or kept and delivered both, shows here.
- ``support_mismatch``: coordinates delivered by one side and not by the
  other, over those the reference delivers. Threshold edges flip with the
  gradient's rounding; a selection that is not by magnitude reads near 1.
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax.numpy as jnp
import numpy as np


def wire(x, wire_dtype: str):
    """``x`` as it is after crossing the wire and back."""
    if wire_dtype == "float32":
        return x
    return x.astype(jnp.dtype(wire_dtype)).astype(x.dtype)


def step(acc, lt, gt, boundaries, wire_dtype: str):
    """``acc`` [P, n] float32, ``lt`` and ``gt`` [P], ``boundaries`` [P + 1]
    -> (ghat [n], residuals [P, n], winners [n], sent [P, n])."""
    acc = jnp.asarray(acc, jnp.float32)
    workers, n = acc.shape
    sent = jnp.abs(acc) >= jnp.asarray(lt, jnp.float32)[:, None]
    on_wire = wire(acc, wire_dtype)
    reduced = jnp.sum(jnp.where(sent, on_wire, 0.0), axis=0)
    owner = jnp.searchsorted(jnp.asarray(boundaries)[1:], jnp.arange(n),
                             side="right")
    winners = ((jnp.abs(reduced) >= jnp.asarray(gt, jnp.float32)[owner])
               & (reduced != 0.0))
    delivered = wire(reduced, wire_dtype)
    ghat = jnp.where(winners, delivered, 0.0) / workers
    kept = jnp.where(winners[None], jnp.where(sent, acc - on_wire, 0.0), acc)
    second = jnp.where(winners, reduced - delivered, 0.0)
    kept = kept + jnp.where(owner[None] == jnp.arange(workers)[:, None],
                            second[None], 0.0)
    return ghat, kept, winners, sent


def over_capacity(sent, winners, boundaries, cap_pair: int,
                  cap_gather: int) -> int:
    """How many (worker, region) buffers and region gathers hold more than
    their capacity."""
    edges = np.asarray(boundaries)
    over = 0
    for lo, hi in zip(edges[:-1], edges[1:]):
        pair = np.asarray(jnp.sum(sent[:, lo:hi], axis=1))
        over += int(np.sum(pair > cap_pair))
        over += int(int(jnp.sum(winners[lo:hi])) > cap_gather)
    return over


def compare(ghat_program, residuals_program, acc, lt, gt, boundaries,
            wire_dtype: str, cap_pair: int,
            cap_gather: int) -> Tuple[Dict[str, float], int]:
    """The three numbers, and the count of capacities passed."""
    ghat, kept, winners, sent = step(acc, lt, gt, boundaries, wire_dtype)
    workers = acc.shape[0]
    norm = lambda x: float(jnp.linalg.norm(jnp.asarray(x, jnp.float32)))
    ghat_program = jnp.asarray(ghat_program, jnp.float32)
    # what the recovery of ghat from the momentum leaves where nothing was
    # delivered is rounding, far under the smallest delivered value
    least = jnp.min(jnp.where(winners, jnp.abs(ghat), jnp.inf))
    theirs = jnp.abs(ghat_program) > 0.5 * least
    out = {
        "delivered_gap": norm(ghat_program - ghat) / norm(ghat),
        "residual_gap": max(
            norm(jnp.asarray(residuals_program[i]) - kept[i])
            / norm(acc[i]) for i in range(workers)),
        "support_mismatch": float(jnp.sum(theirs != winners))
        / max(1.0, float(jnp.sum(winners))),
    }
    return out, over_capacity(sent, winners, boundaries, cap_pair, cap_gather)
