"""Build + ctypes bindings for the native batched env engine (vecenv.cpp).

The shared library is compiled on first use with the system g++
(`-O3 -march=native`, autovectorized; no pybind11 in this image, so the
boundary is a plain C ABI over NumPy buffers — SURVEY.md §2.2) and cached
next to the source as `_vecenv.<key>.so`. The key hashes the source, the
compiler flags and this machine's CPU: `-march=native` code is only valid
on the CPU that built it, and a working tree can be copied whole to
another machine, so a library is loaded only when its key says it was
built here from this source. If no compiler is available, `load()` raises
ImportError and callers (envs/native_pool.py) surface a clear message —
the gymnasium backend remains the fallback.
"""

from __future__ import annotations

import ctypes
import glob
import hashlib
import os
import platform
import subprocess
from functools import lru_cache

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "vecenv.cpp")
# -ffp-contract=off: gymnasium's NumPy arithmetic never fuses
# multiply-adds, so FMA contraction (default under -O3) silently
# breaks the engine's bit-parity contract — measured as a 1-ulp
# velocity difference in MountainCar's force*power - cosTerm.
_FLAGS = ("-O3", "-march=native", "-ffp-contract=off", "-shared", "-fPIC")

_u8p = ctypes.POINTER(ctypes.c_uint8)
_i32p = ctypes.POINTER(ctypes.c_int32)
_i64p = ctypes.POINTER(ctypes.c_int64)
_u64p = ctypes.POINTER(ctypes.c_uint64)
_f32p = ctypes.POINTER(ctypes.c_float)
_f64p = ctypes.POINTER(ctypes.c_double)


def _cpu_identity() -> str:
    """What `-march=native` resolves against: the architecture plus the
    first CPU's model and feature flags (/proc/cpuinfo; the bare
    architecture where that file does not exist)."""
    lines = [platform.machine()]
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if not line.strip():
                    break  # end of the first processor's block
                if line.split(":")[0].strip() in ("model name", "flags", "Features"):
                    lines.append(line.strip())
    except OSError:
        pass
    return "\n".join(lines)


def lib_path() -> str:
    """`_vecenv.<key>.so` for THIS source, flags and machine."""
    h = hashlib.sha256()
    with open(_SRC, "rb") as f:
        h.update(f.read())
    h.update(" ".join(_FLAGS).encode())
    h.update(_cpu_identity().encode())
    return os.path.join(_DIR, f"_vecenv.{h.hexdigest()[:16]}.so")


def _build(lib: str) -> None:
    # Compile to a per-process temp path, then atomically rename: a
    # concurrent process must never dlopen a half-written .so.
    tmp = f"{lib}.{os.getpid()}.tmp"
    cmd = ["g++", *_FLAGS, _SRC, "-o", tmp]
    try:
        subprocess.run(cmd, check=True, capture_output=True, text=True)
        os.replace(tmp, lib)
    except FileNotFoundError as e:
        raise ImportError(f"native vecenv needs g++ to build: {e}") from e
    except subprocess.CalledProcessError as e:
        raise ImportError(f"native vecenv build failed:\n{e.stderr}") from e
    finally:
        if os.path.exists(tmp):
            os.remove(tmp)
    # Libraries under any other key (an edited source, another machine's
    # copy) are dead weight; a process that has one open keeps its inode.
    for stale in glob.glob(os.path.join(_DIR, "_vecenv*.so")):
        if stale != lib:
            os.remove(stale)


@lru_cache(maxsize=1)
def load() -> ctypes.CDLL:
    """The compiled engine, building first unless a library with this
    source's and this machine's key is already cached."""
    path = lib_path()
    if not os.path.exists(path):
        _build(path)
    lib = ctypes.CDLL(path)

    lib.cartpole_reset.argtypes = [_f64p, _f32p, ctypes.c_int, _u64p, _i32p]
    lib.cartpole_step.argtypes = [
        _f64p, _i64p, ctypes.c_int, _u64p, _i32p, ctypes.c_int32,
        _f32p, _f32p, _u8p, _u8p, _f32p,
    ]
    lib.pendulum_reset.argtypes = [_f64p, _f32p, ctypes.c_int, _u64p, _i32p]
    lib.pendulum_step.argtypes = [
        _f64p, _f32p, ctypes.c_int, _u64p, _i32p, ctypes.c_int32,
        _f32p, _f32p, _u8p, _u8p, _f32p,
    ]
    lib.mountaincar_reset.argtypes = [_f64p, _f32p, ctypes.c_int, _u64p, _i32p]
    lib.mountaincar_step.argtypes = [
        _f64p, _f32p, ctypes.c_int, _u64p, _i32p, ctypes.c_int32,
        _f32p, _f32p, _u8p, _u8p, _f32p,
    ]
    lib.acrobot_reset.argtypes = [_f64p, _f32p, ctypes.c_int, _u64p, _i32p]
    lib.acrobot_step.argtypes = [
        _f64p, _i64p, ctypes.c_int, _u64p, _i32p, ctypes.c_int32,
        _f32p, _f32p, _u8p, _u8p, _f32p,
    ]
    lib.set_state.argtypes = [_f64p, _f64p, ctypes.c_int, ctypes.c_int]
    for fn in (
        lib.cartpole_reset, lib.cartpole_step,
        lib.pendulum_reset, lib.pendulum_step,
        lib.mountaincar_reset, lib.mountaincar_step,
        lib.acrobot_reset, lib.acrobot_step, lib.set_state,
    ):
        fn.restype = None
    return lib
