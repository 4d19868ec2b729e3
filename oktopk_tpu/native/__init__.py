"""Native (C++) runtime components, bound through ctypes.

The reference delegates its native performance to external libraries (MPI,
cuDNN, apex — SURVEY.md §2.4); the TPU build keeps the *compute* path in
XLA and implements the host-side runtime pieces natively here:

- ``native/wordpiece.cpp`` — WordPiece tokenizer (the vendored
  BERT/bert/transformers/tokenization.py hot loop);
- ``native/prefetch.cpp`` — background-thread shuffled batch loader (the
  torch DataLoader worker replacement, VGG/dl_trainer.py:286-343).

The library is compiled on first use with the in-image g++ (no pip deps;
pybind11 intentionally avoided — plain C ABI + ctypes). Every consumer
falls back to the pure-Python implementation when a toolchain is missing,
so the framework never hard-requires the native path.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC_DIR = os.path.join(os.path.dirname(os.path.dirname(_HERE)), "native")
_LIB_PATH = os.path.join(_HERE, "liboktopk_native.so")

_lock = threading.Lock()
_lib = None
_build_error: str | None = None


def _needs_build() -> bool:
    if not os.path.exists(_LIB_PATH):
        return True
    lib_mtime = os.path.getmtime(_LIB_PATH)
    for f in os.listdir(_SRC_DIR):
        if f.endswith(".cpp") and os.path.getmtime(
                os.path.join(_SRC_DIR, f)) > lib_mtime:
            return True
    return False


def _build() -> None:
    srcs = sorted(
        os.path.join(_SRC_DIR, f) for f in os.listdir(_SRC_DIR)
        if f.endswith(".cpp"))
    # compile to a per-pid temp and atomically rename: concurrent processes
    # (multi-rank launch, parallel pytest) must never load a half-written .so
    tmp = f"{_LIB_PATH}.tmp{os.getpid()}"
    cmd = ["g++", "-O3", "-fPIC", "-std=c++17", "-Wall", "-Wextra",
           "-shared", "-pthread", "-o", tmp] + srcs
    try:
        subprocess.run(cmd, check=True, capture_output=True, text=True,
                       timeout=300)
        os.replace(tmp, _LIB_PATH)
    finally:
        if os.path.exists(tmp):
            os.remove(tmp)


def _declare(lib: ctypes.CDLL) -> None:
    i64, i32p = ctypes.c_int64, ctypes.POINTER(ctypes.c_int32)
    lib.okn_wp_new_from_buffer.restype = ctypes.c_void_p
    lib.okn_wp_new_from_buffer.argtypes = [ctypes.c_char_p, i64, ctypes.c_int]
    lib.okn_wp_free.argtypes = [ctypes.c_void_p]
    lib.okn_wp_vocab_size.restype = i64
    lib.okn_wp_vocab_size.argtypes = [ctypes.c_void_p]
    lib.okn_wp_encode.restype = i64
    lib.okn_wp_encode.argtypes = [ctypes.c_void_p, ctypes.c_char_p, i32p, i64]
    lib.okn_wp_encode_pair.restype = i64
    lib.okn_wp_encode_pair.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p, ctypes.c_char_p, i64,
        ctypes.c_int32, ctypes.c_int32, i32p, i32p, i32p]

    u8p = ctypes.POINTER(ctypes.c_uint8)
    lib.okn_loader_new.restype = ctypes.c_void_p
    lib.okn_loader_new.argtypes = [u8p, i64, i64, i64, ctypes.c_uint64,
                                   i64, i64, i64, ctypes.c_int]
    lib.okn_loader_next.restype = i64
    lib.okn_loader_next.argtypes = [ctypes.c_void_p, u8p]
    lib.okn_loader_stop.argtypes = [ctypes.c_void_p]
    lib.okn_loader_free.argtypes = [ctypes.c_void_p]


def load():
    """The shared library, building it if needed; None when unavailable
    (no g++, sandboxed filesystem, OKTOPK_NO_NATIVE=1)."""
    global _lib, _build_error
    if os.environ.get("OKTOPK_NO_NATIVE") == "1":
        return None
    with _lock:
        if _lib is not None or _build_error is not None:
            return _lib
        try:
            if _needs_build():
                _build()
            _lib = ctypes.CDLL(_LIB_PATH)
            _declare(_lib)
        except Exception as e:  # toolchain missing, etc. — fall back
            _build_error = str(e)
            _lib = None
        return _lib


def available() -> bool:
    return load() is not None


def build_error() -> str | None:
    load()
    return _build_error


_resolved: dict = {}

_OFF_MODES = ("0", "off", "no", "false")
_REQUIRE_MODES = ("1", "require", "on", "true")


def _multi_process() -> bool:
    """True when this is one process of a multi-host run. Probes the
    jax.distributed global state directly — NOT jax.process_count(), which
    initialises a backend (and so fixes the platform before the caller
    can choose one). Not initialised ⇒ treated as
    single-process; launch.maybe_initialize() re-checks consistency after
    rendezvous (check_multiprocess_consistency)."""
    import sys
    if sys.modules.get("jax") is None:
        return False
    try:
        from jax._src import distributed
        n = getattr(distributed.global_state, "num_processes", None)
        return n is not None and n > 1
    except Exception:
        return False


def resolve(component: str) -> bool:
    """Whether ``component`` ("loader", "tokenizer") should use the native
    path. Policy via OKTOPK_NATIVE:

    - ``1``/``require``/``on`` — native required; raises if the toolchain is
      missing (so a multi-host run fails loudly instead of diverging);
    - ``0``/``off``/``no`` (or legacy OKTOPK_NO_NATIVE=1) — pure Python;
    - unset/``auto`` — native when available in *single-process* runs only.

    In multi-process runs ``auto`` resolves to the Python path: the native
    shuffle (splitmix64 Fisher-Yates) and tokenizer are each deterministic
    but differ from their Python counterparts, so a per-host build failure
    under a silent try/except would feed hosts different data into the same
    sharded step with no error (advisor finding r1). The choice must be a
    global config decision, not per-host toolchain luck.
    """
    mode = os.environ.get("OKTOPK_NATIVE", "auto").strip().lower()
    if os.environ.get("OKTOPK_NO_NATIVE") == "1":
        mode = "0"
    key = (component, mode, _multi_process())
    if key in _resolved:
        return _resolved[key]
    import logging
    log = logging.getLogger("oktopk_tpu.native")
    if mode in _OFF_MODES:
        use = False
        log.info("native %s: disabled (OKTOPK_NATIVE=%s)", component, mode)
    elif mode in _REQUIRE_MODES:
        if load() is None:
            raise RuntimeError(
                f"OKTOPK_NATIVE={mode} but the native library is "
                f"unavailable for {component}: {build_error()}")
        use = True
        log.info("native %s: enabled (required)", component)
    else:  # auto
        if _multi_process():
            use = False
            log.info("native %s: off in multi-process run under auto "
                     "policy (set OKTOPK_NATIVE=1 to force it everywhere)",
                     component)
        else:
            use = load() is not None
            log.info("native %s: %s (auto%s)", component,
                     "enabled" if use else "unavailable, python fallback",
                     "" if use else f"; {build_error()}")
    _resolved[key] = use
    return use


def check_multiprocess_consistency() -> None:
    """Called by launch.maybe_initialize() right after
    jax.distributed.initialize. If a component already resolved to the
    native path under the 'auto' policy while this process looked
    single-process (data pipeline built before rendezvous), the choice was
    per-host toolchain luck after all — refuse to continue rather than let
    hosts silently shuffle/tokenize differently (advisor finding r1)."""
    if not _multi_process():
        return
    # ANY pre-rendezvous auto resolution is unverifiable cross-host — a host
    # that resolved to python (toolchain failure) is just as divergent as one
    # that resolved to native, and must error here rather than hang in the
    # first collective while its peer raises.
    tainted = [comp for (comp, mode, multi), use in _resolved.items()
               if mode not in _OFF_MODES + _REQUIRE_MODES and not multi]
    if tainted:
        raise RuntimeError(
            "native components %s were auto-resolved before "
            "jax.distributed.initialize; in multi-host runs set "
            "OKTOPK_NATIVE=1 (require everywhere) or OKTOPK_NATIVE=0 "
            "(disable everywhere) explicitly" % sorted(set(tainted)))
