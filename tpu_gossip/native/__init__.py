"""Native (C++) fast paths for host-side setup work.

The reference has zero native components (SURVEY.md §2, 100% Python); this
package exists because the TPU build moves graph *construction* to the host
critical path at much larger N (1M-10M nodes), where the inherently
sequential preferential-attachment loop is worth a C++ implementation.

``pa_edges_native`` loads ``libtpugossip.so`` via ctypes. The library is
built from ``csrc/`` into this directory of the checkout the first time it
is needed (or when the source is newer), with portable flags: the checkout
may be copied to another machine. A library that cannot be built is an
error, never a silent switch to the numpy generator, which draws a
different graph.
"""

from __future__ import annotations

import ctypes
import os
import subprocess

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))
_LIB_PATH = os.path.join(_HERE, "libtpugossip.so")
_SRC = os.path.join(_HERE, "csrc", "pa_edges.cc")
# no -march=native: the library must run on whatever host loads the checkout
CXXFLAGS = ("-O3", "-fPIC", "-std=c++17", "-Wall", "-Wextra", "-shared")
_lib = None


def build_library() -> str:
    """Compile ``csrc/pa_edges.cc`` to ``libtpugossip.so``; returns its path.

    Writes a per-process temporary next to the target and renames it into
    place, so concurrent builders (test workers) never load a torn file.
    Raises ``RuntimeError`` with the compiler's output if the build fails.
    """
    cxx = os.environ.get("CXX", "g++")
    tmp = f"{_LIB_PATH[:-len('.so')]}.{os.getpid()}.tmp.so"  # git-ignored
    try:
        proc = subprocess.run(
            [cxx, *CXXFLAGS, "-o", tmp, _SRC],
            capture_output=True, text=True, timeout=300,
        )
    except (OSError, subprocess.TimeoutExpired) as e:
        raise RuntimeError(f"building {_LIB_PATH} with {cxx}: {e}") from e
    if proc.returncode != 0:
        raise RuntimeError(
            f"building {_LIB_PATH} failed (rc={proc.returncode}):\n"
            f"{proc.stderr.strip()[-2000:]}"
        )
    os.replace(tmp, _LIB_PATH)
    return _LIB_PATH


def _load():
    global _lib
    if _lib is None:
        if (not os.path.exists(_LIB_PATH)
                or os.path.getmtime(_LIB_PATH) < os.path.getmtime(_SRC)):
            build_library()
        lib = ctypes.CDLL(_LIB_PATH)
        lib.pa_edges.argtypes = [
            ctypes.c_int64,  # n
            ctypes.c_int64,  # m
            ctypes.c_uint64,  # seed
            ctypes.POINTER(ctypes.c_int64),  # out edges (2 * capacity)
            ctypes.c_int64,  # capacity (edge pairs)
        ]
        lib.pa_edges.restype = ctypes.c_int64  # number of edges written, <0 on error
        _lib = lib
    return _lib


def pa_edges_native(n: int, m: int, seed: int = 0) -> np.ndarray:
    """C++ Barabási–Albert generator; (E, 2) int64 edges."""
    lib = _load()
    cap = m * (m + 1) // 2 + (n - m - 1) * m + 16
    out = np.empty((cap, 2), dtype=np.int64)
    wrote = lib.pa_edges(
        n, m, seed, out.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)), cap
    )
    if wrote < 0:
        raise RuntimeError(f"pa_edges failed with code {wrote}")
    e = out[:wrote]
    lo = np.minimum(e[:, 0], e[:, 1])
    hi = np.maximum(e[:, 0], e[:, 1])
    return np.unique(np.stack([lo, hi], axis=1), axis=0)
