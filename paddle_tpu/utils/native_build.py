"""Shared compile-on-first-use loader for the native C++ runtime pieces
(io loader, store server): rebuilt whenever the source's hash differs
from the one recorded beside the library, double-checked caching,
graceful None on a missing toolchain so callers can fall back to Python.
"""
from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading

_lock = threading.Lock()
_cache: dict = {}


def build_and_load(src: str, so: str, flags=("-O2",)):
    """Compile ``src`` -> ``so`` (if stale) and dlopen it; None when the
    toolchain is unavailable or the build fails. Results (including
    failure) are cached per ``so`` path."""
    if so in _cache:
        lib = _cache[so]
        return lib or None
    with _lock:
        if so in _cache:
            lib = _cache[so]
            return lib or None
        try:
            # the library is git-ignored and a copy of the tree can
            # carry a stale one with any file time: what decides is the
            # hash of the source (and flags) kept beside it
            with open(src, "rb") as f:
                want = hashlib.sha256(
                    f.read() + " ".join(flags).encode()).hexdigest()
            stamp = so + ".sha256"
            have = None
            if os.path.exists(so) and os.path.exists(stamp):
                with open(stamp) as f:
                    have = f.read().strip()
            if have != want:
                # build to a per-pid temp + atomic rename: concurrent
                # processes (test subprocesses) must not read a half-
                # written .so
                tmp = f"{so}.{os.getpid()}.tmp"
                subprocess.run(
                    ["g++", *flags, "-shared", "-fPIC", "-std=c++17",
                     "-pthread", src, "-o", tmp],
                    check=True, capture_output=True)
                os.replace(tmp, so)
                with open(tmp, "w") as f:   # the build's temp, reused
                    f.write(want)
                os.replace(tmp, stamp)
            lib = ctypes.CDLL(so)
        except Exception:
            lib = False
        _cache[so] = lib
        return lib or None
