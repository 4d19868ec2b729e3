"""The compile-cache placement rule and chip_smoke.py's refusals.

Each case runs in a child process: ``ensure_compile_cache`` writes jax's
process-wide config, which must not leak into the rest of the suite.
"""

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_PRINT_CACHE = (
    "import jax\n"
    "from oktopk_tpu.utils.compile_cache import ensure_compile_cache\n"
    "a = ensure_compile_cache(); b = ensure_compile_cache()\n"
    "print(a); print(b); print(jax.config.jax_compilation_cache_dir)\n")


def _run(args, cwd=REPO, **env):
    full = {k: v for k, v in os.environ.items()
            if k not in ("JAX_COMPILATION_CACHE_DIR",
                         "OKTOPK_PALLAS_INTERPRET")}
    full.update(JAX_PLATFORMS="cpu", PYTHONPATH=REPO, **env)
    return subprocess.run([sys.executable, *args], capture_output=True,
                          text=True, cwd=cwd, env=full, timeout=300)


def test_cache_placed_from_outside_is_left_alone(tmp_path):
    placed = str(tmp_path / "placed")
    r = _run(["-c", _PRINT_CACHE], JAX_COMPILATION_CACHE_DIR=placed)
    assert r.returncode == 0, r.stderr[-2000:]
    assert r.stdout.split() == [placed, placed, placed]
    assert not os.path.exists(os.path.join(REPO, ".jax_cache", "placed"))


def test_cache_default_is_fixed_checkout_path(tmp_path):
    want = os.path.join(REPO, ".jax_cache")
    outs = []
    for cwd in (REPO, str(tmp_path)):      # two processes, two cwds
        r = _run(["-c", _PRINT_CACHE], cwd=cwd)
        assert r.returncode == 0, r.stderr[-2000:]
        outs.append(r.stdout.split())
    assert outs[0] == outs[1] == [want, want, want]


def test_chip_smoke_refuses_without_tpu():
    r = _run([os.path.join(REPO, "chip_smoke.py")])
    assert r.returncode != 0
    assert "needs a TPU" in r.stderr and "'cpu'" in r.stderr, r.stderr
    assert '"ok"' not in r.stdout


def test_chip_smoke_refuses_interpreted_kernels():
    r = _run([os.path.join(REPO, "chip_smoke.py")],
             OKTOPK_PALLAS_INTERPRET="1")
    assert r.returncode != 0
    assert "OKTOPK_PALLAS_INTERPRET" in r.stderr
    assert '"ok"' not in r.stdout
