"""Builds the native (C++) components into shared libraries.

Usage: python -m fluidframework_tpu.native.build [--force]

Each src/<name>.cpp compiles to lib/<name>-<sha12>.so with g++ (the
toolchain baked into the image; no external deps), where sha12 hashes the
source bytes and the build flags. A library built from any other version
of the source has another name and is never loaded, whatever its mtime
(a copied tree can carry a newer .so of an older source). Loaders in this
package call ensure_built() lazily, so an explicit build run is optional —
it just moves the compile cost out of first use.
"""

from __future__ import annotations

import hashlib
import os
import re
import subprocess
import sys
import threading
from typing import List, Optional

_HERE = os.path.dirname(os.path.abspath(__file__))
SRC_DIR = os.path.join(_HERE, "src")
LIB_DIR = os.path.join(_HERE, "lib")

_CXX = os.environ.get("CXX", "g++")
_FLAGS = ["-O2", "-std=c++17", "-shared", "-fPIC", "-pthread"]

_build_lock = threading.Lock()


class NativeBuildError(RuntimeError):
    pass


def sources() -> List[str]:
    if not os.path.isdir(SRC_DIR):
        return []
    return sorted(f[:-4] for f in os.listdir(SRC_DIR) if f.endswith(".cpp"))


def lib_path(name: str) -> str:
    """The .so path for the source as it is on disk now."""
    src = os.path.join(SRC_DIR, f"{name}.cpp")
    if not os.path.exists(src):
        raise NativeBuildError(f"no native source {src}")
    h = hashlib.sha256(" ".join([_CXX, *_FLAGS]).encode())
    with open(src, "rb") as f:
        h.update(f.read())
    return os.path.join(LIB_DIR, f"{name}-{h.hexdigest()[:12]}.so")


def ensure_built(name: str, force: bool = False) -> str:
    """Compile src/<name>.cpp unless a .so of exactly this source exists;
    returns the .so path. Raises NativeBuildError when the toolchain
    fails."""
    src = os.path.join(SRC_DIR, f"{name}.cpp")
    out = lib_path(name)
    with _build_lock:
        if not force and os.path.exists(out):
            return out
        os.makedirs(LIB_DIR, exist_ok=True)
        # CPython-C-API sources (loaded with ctypes.PyDLL) need the
        # interpreter headers; the include dir is harmless for the rest.
        import sysconfig
        # Build beside the target and rename: concurrent builders (test
        # workers) never load a half-written library.
        tmp = f"{out}.{os.getpid()}.tmp"
        cmd = [_CXX, *_FLAGS, "-I" + sysconfig.get_paths()["include"],
               src, "-o", tmp]
        proc = subprocess.run(cmd, capture_output=True, text=True)
        if proc.returncode != 0:
            raise NativeBuildError(
                f"{' '.join(cmd)} failed:\n{proc.stderr[-4000:]}")
        os.replace(tmp, out)
        # One library per source: drop the builds of older versions (a
        # process that mapped one keeps its mapping).
        stale = re.compile(re.escape(name) + r"-[0-9a-f]{12}\.so")
        for f in os.listdir(LIB_DIR):
            path = os.path.join(LIB_DIR, f)
            if stale.fullmatch(f) and path != out:
                os.remove(path)
        return out


def main(argv: Optional[List[str]] = None) -> int:
    force = "--force" in (argv or sys.argv[1:])
    names = sources()
    if not names:
        print("no native sources")
        return 0
    for name in names:
        out = ensure_built(name, force=force)
        print(f"built {out}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
