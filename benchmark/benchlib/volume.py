"""The paper's wire budget: an Ok-Topk step sends fewer than 6k scalars a
worker, i.e. 3k (index, value) pairs.

From ``oktopk_tpu/obs/volume.py::budget_bytes`` (the ``oktopk`` and ``dense``
rows) at commit 669e046, in scalars instead of bytes: the step's
``comm_volume`` metric counts scalars, two to a pair.
"""


def k_of(n: int, density: float) -> int:
    return max(1, int(density * n))


def budget_scalars(compressor: str, n: int, density: float) -> float:
    """Upper limit of ``comm_volume`` (scalars a worker, one step)."""
    if compressor == "dense":
        return 2.0 * n
    return 6.0 * k_of(n, density)
