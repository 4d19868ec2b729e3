"""The exact-threshold contract, over both methods of
``ops.topk.k2threshold_method``: "sort" (``lax.top_k``, the
reference-faithful one) and "bisect" (count bisection in log space,
ops/pallas_topk.py, the default on the chip)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from oktopk_tpu.config import OkTopkConfig
from oktopk_tpu.ops.topk import k2threshold, k2threshold_method

MIN_NORMAL = np.float32(1.17549435e-38)   # the selection kernels' clamp

methods = pytest.mark.parametrize("method", ["sort", "bisect"])


def threshold(x, k, method):
    return float(k2threshold_method(jnp.asarray(x), k, method))


@methods
class TestExactThresholdContract:
    def test_matches_sort_threshold_count(self, rng, method):
        x = np.abs(rng.randn(4096).astype(np.float32))
        k = 100
        t_sort = float(k2threshold(jnp.asarray(x), k))
        t = threshold(x, k, method)
        # both thresholds select ~k elements; bisect's bracket is below
        # float resolution so the counts agree except at exact ties
        assert abs(int(np.sum(x >= t_sort)) - int(np.sum(x >= t))) <= 2
        assert abs(t_sort - t) < 1e-3

    def test_extreme_k(self, rng, method):
        x = np.abs(rng.randn(256).astype(np.float32))
        assert int(np.sum(x >= threshold(x, 256, method))) == 256
        assert int(np.sum(x >= threshold(x, 1, method))) >= 1

    def test_threshold_resolves_tiny_kth_value(self, method):
        """Error feedback at convergence: a few huge residuals over many
        tiny gradients (> 30 bits of dynamic range). The linear-space
        bisection returned exactly 0 here — an absorbing state for the
        multiplicative threshold controller (observed as local_k == n and
        a loss blow-up on the convergence harness); log-space cuts must
        resolve the true k-th value."""
        rng = np.random.RandomState(0)
        x = np.abs(rng.randn(1 << 16).astype(np.float32)) * 1e-9
        x[:64] = np.abs(rng.randn(64)).astype(np.float32) * 100.0
        k = 1024
        t = threshold(x, k, method)
        kth = float(np.sort(x)[::-1][k - 1])
        assert t > 0.0, "threshold collapsed to the absorbing zero"
        count = int(np.sum(x >= t))
        assert k <= count <= int(1.01 * k) + 8, (count, k)
        assert abs(t - kth) <= 1e-3 * kth + 1e-12, (t, kth)

    def test_all_zero_input_gives_zero(self, method):
        assert threshold(np.zeros(4096, np.float32), 16, method) == 0.0

    def test_tiny_magnitude_input_never_returns_zero(self, method):
        """max|x| ~ 1e-30: exp2 of the bracket floor would underflow to an
        exact 0 without the min-normal clamp, re-entering the absorbing
        zero state."""
        rng = np.random.RandomState(1)
        x = np.abs(rng.randn(4096).astype(np.float32)) * 1e-30
        assert threshold(x, 4096, method) > 0.0

    def test_fewer_live_than_k_selects_only_live(self, method):
        """Fewer live elements than k: what the selection kernels stage
        (|x| >= the min-normal-clamped threshold) is the live elements
        and no zero. The methods differ in the value: "sort" returns the
        k-th value itself, 0; "bisect" the positive bracket floor, never
        the absorbing 0 (its documented divergence)."""
        x = np.zeros(4096, np.float32)
        x[:10] = 1.0
        t = threshold(x, 16, method)
        assert int(np.sum(x >= max(np.float32(t), MIN_NORMAL))) == 10
        if method == "bisect":
            assert t > 0.0 and int(np.sum(x >= t)) == 10
        else:
            assert t == 0.0

    def test_ties_select_at_least_k(self, method):
        """All magnitudes equal: the threshold may not pass them by."""
        x = np.full(1024, 0.37, np.float32)
        t = threshold(x, 100, method)
        assert 0.0 < t <= float(x[0])
        assert int(np.sum(x >= t)) >= 100


def test_traced_k_matches_static():
    """``density_schedule`` hands "bisect" a traced k ("sort" needs it
    static: ``lax.top_k``, refused at config time)."""
    x = jnp.abs(jnp.asarray(
        np.random.RandomState(3).randn(2048).astype(np.float32)))
    f = jax.jit(lambda x, k: k2threshold_method(x, k, "bisect"))
    for k in (16, 300):
        assert float(f(x, jnp.int32(k))) == float(
            k2threshold_method(x, k, "bisect"))


@pytest.mark.parametrize("method", ["hist", "median"])
def test_unknown_threshold_method_is_refused(method):
    with pytest.raises(ValueError, match="'sort' or 'bisect'") as e:
        OkTopkConfig(n=1024, num_workers=2, threshold_method=method)
    assert repr(method) in str(e.value)
