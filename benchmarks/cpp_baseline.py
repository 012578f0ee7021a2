"""ctypes driver for the C++ Dijkstra baseline (benchmarks/cpp/spf_baseline.cpp).

Compiles on demand with g++ -O3 — the baseline for `vs_baseline` is real
native sequential Dijkstra, not a Python oracle.  The built library is
named by a hash of the source, the flags and this machine's CPU
(-march=native), so a library copied from another checkout or machine
is never loaded."""

from __future__ import annotations

import ctypes
import hashlib
import os
import platform
import subprocess
from pathlib import Path

import numpy as np

_DIR = Path(__file__).resolve().parent
_SRC = _DIR / "cpp" / "spf_baseline.cpp"
_BUILD = _DIR / "cpp" / "build"
_FLAGS = ["-O3", "-march=native", "-std=c++17", "-shared", "-fPIC"]

_lib = None


def _cpu_identity() -> bytes:
    try:
        with open("/proc/cpuinfo", "rb") as f:
            lines = [
                ln for ln in f
                if ln.startswith((b"model name", b"flags"))
            ]
        return b"".join(sorted(set(lines)))
    except OSError:
        return platform.processor().encode()


def _ensure_built() -> Path:
    key = hashlib.sha256(
        _SRC.read_bytes() + " ".join(_FLAGS).encode() + _cpu_identity()
    ).hexdigest()[:16]
    so = _BUILD / f"libspf_baseline-{key}.so"
    if not so.exists():
        _BUILD.mkdir(parents=True, exist_ok=True)
        tmp = so.with_suffix(f".{os.getpid()}.tmp")
        subprocess.run(
            ["g++", *_FLAGS, str(_SRC), "-o", str(tmp)], check=True
        )
        os.replace(tmp, so)
    return so


def load():
    global _lib
    if _lib is None:
        lib = ctypes.CDLL(str(_ensure_built()))
        lib.spf_all_sources.restype = ctypes.c_double
        lib.spf_all_sources.argtypes = [
            ctypes.c_int,
            ctypes.c_int,
            np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS"),
            np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS"),
            np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS"),
            np.ctypeslib.ndpointer(np.uint8, flags="C_CONTIGUOUS"),
            np.ctypeslib.ndpointer(np.uint8, flags="C_CONTIGUOUS"),
            np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS"),
            ctypes.c_int,
            ctypes.c_void_p,
        ]
        _lib = lib
    return _lib


def spf_all_sources(
    n_nodes: int,
    edge_src: np.ndarray,
    edge_dst: np.ndarray,
    edge_metric: np.ndarray,
    edge_up: np.ndarray | None,
    node_overloaded: np.ndarray | None,
    sources: np.ndarray,
    want_dist: bool = False,
) -> tuple[float, np.ndarray | None]:
    """Returns (seconds, dist [S, n_nodes] or None)."""
    lib = load()
    n_edges = len(edge_src)
    edge_src = np.ascontiguousarray(edge_src, dtype=np.int32)
    edge_dst = np.ascontiguousarray(edge_dst, dtype=np.int32)
    edge_metric = np.ascontiguousarray(edge_metric, dtype=np.int32)
    if edge_up is None:
        edge_up = np.ones(n_edges, dtype=np.uint8)
    else:
        edge_up = np.ascontiguousarray(edge_up, dtype=np.uint8)
    if node_overloaded is None:
        node_overloaded = np.zeros(n_nodes, dtype=np.uint8)
    else:
        node_overloaded = np.ascontiguousarray(node_overloaded, dtype=np.uint8)
    sources = np.ascontiguousarray(sources, dtype=np.int32)
    out = (
        np.empty((len(sources), n_nodes), dtype=np.int32)
        if want_dist
        else None
    )
    secs = lib.spf_all_sources(
        n_nodes,
        n_edges,
        edge_src,
        edge_dst,
        edge_metric,
        edge_up,
        node_overloaded,
        sources,
        len(sources),
        out.ctypes.data_as(ctypes.c_void_p) if out is not None else None,
    )
    return float(secs), out
