"""JIT C++ build core (ref: magi_attention/common/jit/core.py).

Compiles csrc/magi_host.cpp with g++ -O3 into a per-source-hash cache dir
(MAGI_ATTENTION_JIT_CACHE_DIR, default ~/.cache/magiattention_tpu) and loads
it via ctypes. Thread-safe single build per process; a failed toolchain
falls back to the pure-Python implementations (common/__init__ catches the
ImportError).
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading
from pathlib import Path

_SRC = Path(__file__).resolve().parents[2] / "csrc" / "magi_host.cpp"
_LOCK = threading.Lock()
_LIB: ctypes.CDLL | None = None
_LIB_ERR: ImportError | None = None  # memoized failure: never retry builds


def _cache_dir() -> Path:
    from ..env.general import jit_cache_dir

    return Path(jit_cache_dir())


def _build(src: Path, out: Path) -> None:
    out.parent.mkdir(parents=True, exist_ok=True)
    # a name of this process's own: processes that build at once (test
    # workers sharing the cache) each rename a whole file into place, and
    # none loads one that another compiler is still writing
    tmp = out.with_name(f"{out.name}.{os.getpid()}.tmp")
    cmd = [
        "g++", "-O3", "-std=c++17", "-shared", "-fPIC",
        "-o", str(tmp), str(src),
    ]
    subprocess.run(cmd, check=True, capture_output=True)
    tmp.replace(out)


def get_lib() -> ctypes.CDLL:
    """Build (once, cached by source hash) and load the native library.

    Failures are memoized (raised as the same ImportError on every later
    call) so hot paths with a Python fallback — e.g. the default-on native
    FFA plan builder — never retry a failing toolchain per call.
    """
    global _LIB, _LIB_ERR
    if _LIB is not None:
        return _LIB
    if _LIB_ERR is not None:
        raise _LIB_ERR
    with _LOCK:
        if _LIB is not None:
            return _LIB
        if _LIB_ERR is not None:
            raise _LIB_ERR
        try:
            if not _SRC.exists():
                raise ImportError(f"native source missing: {_SRC}")
            digest = hashlib.sha256(_SRC.read_bytes()).hexdigest()[:16]
            so = _cache_dir() / f"magi_host_{digest}.so"
            if not so.exists():
                try:
                    _build(_SRC, so)
                except (subprocess.CalledProcessError, FileNotFoundError) as e:
                    raise ImportError(f"native build failed: {e}") from e
            try:
                lib = ctypes.CDLL(str(so))
            except OSError as e:  # stale/foreign .so in a shared cache
                raise ImportError(f"native lib unloadable: {e}") from e
            _declare(lib)
        except ImportError as e:
            _LIB_ERR = e
            raise
        _LIB = lib
        return lib


def host_backend() -> str:
    """Which implementation plans on this host: ``native`` when the C++
    library built and loaded, else ``python`` with the reason (a missing
    ``g++`` selects the Python planners — same results, slower)."""
    try:
        get_lib()
    except ImportError as e:
        return f"python ({e})"
    return "native"


def _declare(lib: ctypes.CDLL) -> None:
    i64, i32p, i64p = ctypes.c_int64, ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_int64)
    lib.magi_band_area.restype = i64
    lib.magi_band_area.argtypes = [i64] * 6
    lib.magi_chunk_areas.restype = None
    lib.magi_chunk_areas.argtypes = [i64p, i64, i64, i64, i64p]
    lib.magi_ranges_merge.restype = i64
    lib.magi_ranges_merge.argtypes = [i32p, i64, i32p]
    lib.magi_ranges_holes.restype = i64
    lib.magi_ranges_holes.argtypes = [i32p, i64, i32p, i64, i32p]
    lib.magi_ranges_overlap.restype = i64
    lib.magi_ranges_overlap.argtypes = [i32p, i64, i32p, i64, i32p]
    lib.magi_ranges_make_local.restype = i64
    lib.magi_ranges_make_local.argtypes = [i32p, i64, i32p, i64, i32p]
    lib.magi_minheap_solve.restype = None
    lib.magi_minheap_solve.argtypes = [i64p, i64, i64, i64, i32p]
    lib.magi_binary_greedy_solve.restype = ctypes.c_int32
    lib.magi_binary_greedy_solve.argtypes = [
        i64p, i64p, i64p, i64p, i64p, i32p, i32p,
        i64, i64, ctypes.c_double, i64, i32p,
    ]
    lib.magi_ffa_plan_count.restype = ctypes.c_int32
    lib.magi_ffa_plan_count.argtypes = [
        i32p, i32p, i32p, i32p, i64, i64, i64, i64, i64, i64p, i64p,
    ]
    lib.magi_ffa_plan_fill.restype = None
    lib.magi_ffa_plan_fill.argtypes = [
        i32p, i32p, i32p, i32p, i64, i64, i64, i64, i64,
        i64p, i64p, i64p, i64p,
        i32p, i32p, i32p, i32p, i32p, i32p,
    ]
