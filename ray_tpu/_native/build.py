"""Build the native C++ runtime library (libray_tpu_native.so).

Invoked lazily on first import of ray_tpu.core._native (and by `make native`).
The built .so is NOT tracked by git: a fresh checkout builds it from src/.
Staleness is keyed on the CONTENT of the sources and flags (a digest kept
in a stamp file next to the .so), never on mtimes — a copy of the tree
does not preserve those, and a binary that does not match src/ must not
load.
"""

from __future__ import annotations

import hashlib
import os
import subprocess
import sys

_THIS_DIR = os.path.dirname(os.path.abspath(__file__))
SRC_DIR = os.path.join(_THIS_DIR, "src")
LIB_PATH = os.path.join(_THIS_DIR, "libray_tpu_native.so")
STAMP_PATH = LIB_PATH + ".stamp"

SOURCES = [
    "shm_store.cc",
    "scheduler.cc",
    "transport.cc",
]

CXXFLAGS = [
    "-O2",
    "-g",
    "-std=c++17",
    "-fPIC",
    "-shared",
    "-Wall",
    "-pthread",
]


def source_digest() -> str:
    """sha256 over the compiler flags and every source file's bytes."""
    h = hashlib.sha256(" ".join(CXXFLAGS).encode())
    for name in SOURCES:
        with open(os.path.join(SRC_DIR, name), "rb") as f:
            h.update(name.encode() + b"\0" + f.read())
    return h.hexdigest()


def needs_build() -> bool:
    if not os.path.exists(LIB_PATH):
        return True
    try:
        with open(STAMP_PATH) as f:
            return f.read().strip() != source_digest()
    except OSError:
        return True


def build(verbose: bool = False) -> str:
    if not needs_build():
        return LIB_PATH
    base_cmd = ["g++"] + CXXFLAGS + [os.path.join(SRC_DIR, s) for s in SOURCES]
    if verbose:
        sys.stderr.write(
            " ".join(base_cmd + ["-o", LIB_PATH, "-lrt"]) + "\n")
    # Serialize concurrent builds (several workers may import simultaneously).
    lockfile = LIB_PATH + ".lock"
    import fcntl

    with open(lockfile, "w") as lf:
        fcntl.flock(lf, fcntl.LOCK_EX)
        try:
            if needs_build():
                tmp = LIB_PATH + f".tmp.{os.getpid()}"
                digest = source_digest()
                subprocess.run(base_cmd + ["-o", tmp, "-lrt"], check=True)
                os.replace(tmp, LIB_PATH)
                with open(STAMP_PATH + ".tmp", "w") as sf:
                    sf.write(digest + "\n")
                os.replace(STAMP_PATH + ".tmp", STAMP_PATH)
        finally:
            fcntl.flock(lf, fcntl.LOCK_UN)
    return LIB_PATH


if __name__ == "__main__":
    build(verbose=True)
    sys.stdout.write(LIB_PATH + "\n")
