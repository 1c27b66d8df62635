"""Native (C++) runtime components.

≙ the reference's C++ runtime layer (SURVEY §2.1): here only the pieces
Python+JAX cannot express well get native code — currently the DataLoader
shared-memory ring (reader_py.cc BlockingQueue + mmap_allocator.cc analog).
Kernels stay Pallas (Python-authored, Mosaic-compiled), per SURVEY §7.

Build model: compiled on first use with g++ into ``_build/`` next to this
file (no pip; the image bans installs), keyed by a hash of the source: a copy
of the tree keeps whatever ``_build/`` and mtimes it was made with, so only
the source's content says whether a built library belongs to it.  Loading is
ctypes — no pybind11 in the image.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading

_HERE = os.path.dirname(os.path.abspath(__file__))
_BUILD = os.path.join(_HERE, "_build")
_LOCK = threading.Lock()
_LIBS = {}


class NativeBuildError(RuntimeError):
    pass


def load_library(name: str):
    """Compile (unless built from this very source) and dlopen
    csrc/<name>.cpp -> _build/lib<name>-<source hash>.so."""
    with _LOCK:
        if name in _LIBS:
            return _LIBS[name]
        src = os.path.join(_HERE, f"{name}.cpp")
        with open(src, "rb") as f:
            digest = hashlib.blake2b(f.read(), digest_size=8).hexdigest()
        out = os.path.join(_BUILD, f"lib{name}-{digest}.so")
        if not os.path.exists(out):
            os.makedirs(_BUILD, exist_ok=True)
            tmp = f"{out}.{os.getpid()}.tmp"
            cmd = ["g++", "-O2", "-shared", "-fPIC", "-std=c++17", src,
                   "-o", tmp, "-lpthread", "-lrt"]
            proc = subprocess.run(cmd, capture_output=True, text=True)
            if proc.returncode != 0:
                raise NativeBuildError(
                    f"building {name}: {' '.join(cmd)}\n{proc.stderr[-2000:]}")
            os.replace(tmp, out)
        lib = ctypes.CDLL(out)
        _LIBS[name] = lib
        return lib
