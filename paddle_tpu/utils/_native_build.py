"""Shared build-on-first-use loader for the native (C++) runtime pieces
(io/_native batcher, distributed/ps/_native table — ONE copy of the
lock/latch/stamp/g++ convention, so fixes like compile-race handling or
flag changes apply everywhere).

Builds `src` into `so` with g++ when missing or stale; returns the
ctypes CDLL, or None when no toolchain is available (callers fall back
to their pure-Python paths). The `.so` files are git-ignored, so one
found on disk was built by someone else at some other time: it is
trusted only when the stamp beside it (`<so>.src`, the sha256 of the
source and flags it was built from) matches the source in the checkout.
An mtime proves nothing about a file a copy or a checkout has touched."""
from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading
from typing import Callable, Optional

_lock = threading.Lock()
_cache: dict = {}        # so path -> (lib | None)


def build_and_load(src: str, so: str,
                   configure: Optional[Callable] = None,
                   flags=("-O3", "-shared", "-fPIC", "-pthread")):
    """configure(lib) sets argtypes/restypes after a successful load.
    The result (including failure) is latched per `so` path."""
    with _lock:
        if so in _cache:
            return _cache[so]
        lib = None
        try:
            with open(src, "rb") as f:
                want = hashlib.sha256(
                    f.read() + " ".join(flags).encode()).hexdigest()
            stamp = so + ".src"
            try:
                with open(stamp) as f:
                    built_from = f.read().strip()
            except OSError:
                built_from = None
            if not os.path.exists(so) or built_from != want:
                # atomic install: a concurrent builder in another
                # process must never dlopen a half-written .so
                tmp = so + f".tmp.{os.getpid()}"
                # bounded: a wedged compiler must not pin every thread
                # that imports a native helper behind _lock forever —
                # TimeoutExpired lands in the except and latches failure
                subprocess.run(["g++", *flags, src, "-o", tmp],
                               check=True, capture_output=True,
                               timeout=600)
                os.replace(tmp, so)
                with open(tmp, "w") as f:    # tmp is free again
                    f.write(want + "\n")
                os.replace(tmp, stamp)
            lib = ctypes.CDLL(so)
            if configure is not None:
                configure(lib)
        except Exception:
            lib = None
        _cache[so] = lib
        return lib
