"""Online α-β fabric calibration.

`utils/cost_model.py` ships Piz Daint-era MPI constants and hand-estimated
ICI ones; neither describes the fabric a run actually lands on (CPU test
mesh, a v5e host, a future multi-host slice). This module measures it:
time a few dense allreduce probes of increasing size over the real mesh,
then least-squares fit the ring-allreduce α-β law

    t(n) = msgs(P) * α + elems(n, P) * β,
    msgs(P) = 2 (P-1),  elems(n, P) = 2 n (P-1) / P        (P > 1)

which is linear in (α, β). With P == 1 the collective is a no-op and the
probe times only dispatch + memory traffic; the design matrix degenerates
to (1, n) so α absorbs the dispatch floor and β the per-element pass —
exactly the quantities the single-chip cost comparison needs.

The fitted coefficients feed `policy.predict_ms` as the prior over
candidates; they replace (per run, not in source) the ICI_ALPHA/ICI_BETA
defaults, which remain the fallback when probing is disabled.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Callable, Optional, Sequence, Tuple

import numpy as np

from oktopk_tpu.utils.cost_model import ICI_ALPHA, ICI_BETA

# Probe sizes: span the bucket sizes real models produce (64k..4M elements
# covers mnistnet through VGG-16 buckets) without making startup slow.
DEFAULT_PROBE_SIZES = (1 << 16, 1 << 18, 1 << 20, 1 << 22)


@dataclasses.dataclass(frozen=True)
class FabricCoefficients:
    """Measured (or default) α-β coefficients for one fabric."""

    alpha: float                   # seconds per message round
    beta: float                    # seconds per element
    source: str = "default"        # "measured" | "default" | "injected"
    nsamples: int = 0
    residual: float = 0.0          # rms relative fit error over the samples

    def as_dict(self):
        return dataclasses.asdict(self)


def default_coefficients() -> FabricCoefficients:
    return FabricCoefficients(alpha=ICI_ALPHA, beta=ICI_BETA,
                              source="default")


def _design_row(n: int, p: int) -> Tuple[float, float]:
    """(α-coefficient, β-coefficient) of one probe in the allreduce law."""
    if p > 1:
        return 2.0 * (p - 1), 2.0 * n * (p - 1) / p
    return 1.0, float(n)


def fit_alpha_beta(sizes: Sequence[int], times_s: Sequence[float],
                   num_workers: int,
                   source: str = "measured") -> FabricCoefficients:
    """Least-squares α-β fit of measured allreduce times.

    ``times_s[i]`` is the per-step time (seconds) of an allreduce over
    ``sizes[i]`` f32 elements on ``num_workers`` workers. Coefficients are
    clamped to a tiny positive floor — a fit driven negative by noise would
    otherwise make every predicted cost meaningless.
    """
    sizes = list(sizes)
    times = np.asarray(list(times_s), np.float64)
    if len(sizes) != len(times) or len(sizes) < 2:
        raise ValueError(
            f"need >= 2 (size, time) samples, got {len(sizes)}/{len(times)}")
    A = np.asarray([_design_row(n, num_workers) for n in sizes], np.float64)
    coef, *_ = np.linalg.lstsq(A, times, rcond=None)
    alpha = float(max(coef[0], 1e-12))
    beta = float(max(coef[1], 1e-15))
    pred = A @ np.asarray([alpha, beta])
    rel = (pred - times) / np.maximum(times, 1e-12)
    return FabricCoefficients(
        alpha=alpha, beta=beta, source=source, nsamples=len(sizes),
        residual=float(np.sqrt(np.mean(rel ** 2))))


def _default_measure(mesh, axis_name: str,
                     repeats: int) -> Callable[[int], Sequence[float]]:
    """Time a real psum over the mesh at size n (median-friendly repeat
    list; each sample ends in ``block_until_ready``)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from oktopk_tpu.comm import compat

    p = int(np.prod([mesh.shape[a] for a in (axis_name,)]))

    def measure(n: int) -> Sequence[float]:
        def shard_fn(x):
            return jax.lax.pmean(x, axis_name)

        spec = P(axis_name)
        step = jax.jit(compat.shard_map(
            shard_fn, mesh=mesh, in_specs=(spec,), out_specs=spec,
            check_vma=False))
        x = jnp.zeros((p, n), jnp.float32)
        jax.block_until_ready(step(x))            # compile + warm
        out = []
        for _ in range(repeats):
            t0 = time.perf_counter()
            jax.block_until_ready(step(x))
            out.append(time.perf_counter() - t0)
        return out

    return measure


def probe_fabric(mesh=None, axis_name: str = "data",
                 sizes: Sequence[int] = DEFAULT_PROBE_SIZES,
                 repeats: int = 3,
                 measure: Optional[Callable[[int], Sequence[float]]] = None,
                 num_workers: Optional[int] = None) -> FabricCoefficients:
    """Measure the fabric: run probe allreduces and fit α-β.

    ``measure(n) -> [seconds, ...]`` can be injected (tests, or fabrics
    timed elsewhere); the default builds and times a real psum over
    ``mesh``. The median over repeats of each size enters the fit.
    """
    src = "injected"
    if measure is None:
        if mesh is None:
            raise ValueError("probe_fabric needs a mesh or a measure fn")
        num_workers = int(np.prod([mesh.shape[a] for a in (axis_name,)]))
        measure = _default_measure(mesh, axis_name, repeats)
        src = "measured"
    elif num_workers is None:
        raise ValueError("num_workers is required with an injected measure")
    med = [float(np.median(list(measure(n)))) for n in sizes]
    return fit_alpha_beta(sizes, med, num_workers, source=src)
