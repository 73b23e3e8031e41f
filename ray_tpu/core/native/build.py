"""Builds the native C++ components into shared libraries, lazily and cached.

The reference builds its native core with Bazel; here each component is a
single translation unit compiled with g++ at first use, which keeps the repo
hermetic with no install step. The built library is named after a hash of
its source and flags, so a tree that was copied with a library built from
other source (file times mean nothing after a copy) builds its own.
"""
from __future__ import annotations

import glob
import hashlib
import os
import subprocess
import threading

_DIR = os.path.dirname(os.path.abspath(__file__))
_lock = threading.Lock()


def build_lib(name: str, extra_flags: list[str] | None = None) -> str:
    """Compile ``<name>.cpp`` in this directory -> ``_<name>.<hash>.so``;
    return its path."""
    src = os.path.join(_DIR, f"{name}.cpp")
    flags = ["-O2", "-std=c++17", "-shared", "-fPIC"]
    tail = ["-lpthread", *(extra_flags or [])]
    with open(src, "rb") as f:
        digest = hashlib.sha256(f.read() + " ".join(flags + tail).encode()).hexdigest()[:16]
    out = os.path.join(_DIR, f"_{name}.{digest}.so")
    with _lock:
        if os.path.exists(out):
            return out
        tmp = out + f".tmp{os.getpid()}"
        subprocess.run(["g++", *flags, "-o", tmp, src, *tail],
                       check=True, capture_output=True, text=True)
        os.replace(tmp, out)
        for stale in glob.glob(os.path.join(_DIR, f"_{name}.*so")):
            if stale != out:
                try:
                    os.remove(stale)
                except OSError:
                    pass  # another process removed it, or still has it mapped
    return out
