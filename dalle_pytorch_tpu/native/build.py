"""Build the native loader shared library.

One translation unit, no CPython dependency (plain C ABI consumed via
ctypes — the sanctioned binding route in this image, no pybind11). The .so
lands next to this file; `python -m dalle_pytorch_tpu.native.build` builds
explicitly, and `native.load_library()` builds lazily on first use.
"""

from __future__ import annotations

import os
import shutil
import subprocess
import sys

_DIR = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(_DIR, "loader.cc")
LIB = os.path.join(_DIR, "_loader.so")


def build(force: bool = False, quiet: bool = False) -> str:
    """Compile loader.cc -> _loader.so if missing/stale. Returns the path.
    Raises RuntimeError when no toolchain or libs are available."""
    if (not force and os.path.exists(LIB)
            and os.path.getmtime(LIB) >= os.path.getmtime(SRC)):
        return LIB
    cxx = os.environ.get("CXX") or shutil.which("g++") or shutil.which("c++")
    if cxx is None:
        raise RuntimeError("no C++ compiler found (set CXX)")
    # a temporary name of this process's own: the test workers of a fresh
    # checkout all build at once, and one shared name let a worker install
    # (or find gone) the file another was still writing
    tmp = f"{LIB}.{os.getpid()}.tmp"
    cmd = [cxx, "-O3", "-std=c++17", "-shared", "-fPIC", SRC,
           "-o", tmp, "-ljpeg", "-lpng", "-pthread"]
    proc = subprocess.run(cmd, capture_output=True, text=True)
    if proc.returncode != 0:
        raise RuntimeError(
            f"native loader build failed:\n{' '.join(cmd)}\n{proc.stderr}")
    os.replace(tmp, LIB)
    if not quiet:
        print(f"built {LIB}")
    return LIB


if __name__ == "__main__":
    build(force="--force" in sys.argv)
