"""bench.py record contract (the driver's round-end artifact).

The driver runs ``python bench.py`` and parses the LAST stdout line as the
round's machine-readable perf record; a schema break silently costs a round
of perf evidence, so the contract is pinned here. ``python bench.py`` itself
needs a TPU (its step child fails without one), so this reads the volume
half through the ``--volume-probe`` child — the virtual 8-worker CPU mesh —
and builds the record from it the way ``bench.main`` does.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def bench():
    sys.path.insert(0, REPO)
    try:
        import bench
    finally:
        sys.path.remove(REPO)
    return bench


@pytest.mark.slow
def test_bench_emits_parseable_volume_record(bench):
    probe = bench._child("--volume-probe", "VOLUME_PROBE ",
                         dict(os.environ, JAX_PLATFORMS="cpu"))
    rec = json.loads(json.dumps(bench._record(probe, {})))
    for key in ("metric", "value", "unit", "vs_baseline", "volume_elems",
                "wire_dtype", "conformance_ratio"):
        assert key in rec, (key, rec)
    assert rec["metric"] == "oktopk_sparse_allreduce_volume_bytes_per_step"
    assert rec["unit"] == "bytes/step/worker"
    assert rec["vs_baseline"] > 1.0
    # the headline property at the probe's operating point
    # (n=2^20, d=0.01): steady-state mean under the 6k-scalar budget,
    # with the r5 controller margin
    k = 0.01 * (1 << 20)
    assert rec["volume_elems"] < 0.85 * 6 * k, rec["volume_elems"]


def test_peak_table_refuses_unknown_device_kind(bench):
    assert bench.peak_flops("TPU v5 lite") == 197e12
    with pytest.raises(KeyError, match="cpu"):
        bench.peak_flops("cpu")


def test_bench_and_sweep_parents_import_no_jax():
    """One process per chip: a parent that has touched jax holds the TPU
    and its child then fails or hangs, so the two parents that start
    children must stay off jax (and off the package, whose __init__
    imports it)."""
    code = ("import sys; sys.path[:0] = [{repo!r}, {scripts!r}]\n"
            "import bench, sweep\n"
            "bad = [m for m in sys.modules if m.split('.')[0] in "
            "('jax', 'jaxlib', 'oktopk_tpu')]\n"
            "assert not bad, bad\n").format(
                repo=REPO, scripts=os.path.join(REPO, "scripts"))
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=60)
    assert r.returncode == 0, r.stderr[-2000:]
