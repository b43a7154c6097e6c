"""Build the native comm library (codec + tokenizer) with g++.

No pybind11 in the image, so everything is a plain C ABI shared object
loaded via ctypes.  Build is on-demand and cached next to the sources
under a name that carries a hash of those sources and of the compile
command: a binary built from other sources has another name and cannot
be loaded by mistake, whatever its mtime says (a copied tree keeps the
ignored ``.so`` but not the clock it was built under).
``python -m distributed_inference_demo_tpu.comm.native.build`` forces a
rebuild.
"""

from __future__ import annotations

import hashlib
import os
import shutil
import subprocess
from pathlib import Path

_DIR = Path(__file__).resolve().parent
SOURCES = ["codec.cc", "tokenizer.cc"]
_CXX = "g++"
_FLAGS = ["-O2", "-std=c++17", "-fPIC", "-shared", "-Wall"]


class NativeUnavailable(RuntimeError):
    """This machine has no C++ compiler: callers use the pure-Python
    implementations (the one legitimate fallback)."""


class NativeBuildError(RuntimeError):
    """The compiler is there and the build failed — an error to fix,
    never a reason to fall back unseen."""


def _source_hash() -> str:
    h = hashlib.sha256(" ".join([_CXX] + _FLAGS).encode())
    for name in SOURCES:
        h.update(name.encode())
        h.update((_DIR / name).read_bytes())
    return h.hexdigest()[:16]


def lib_path() -> Path:
    return _DIR / f"libdwt_native.{_source_hash()}.so"


def build(force: bool = False) -> Path:
    """Compile the shared library unless the one for these exact sources
    exists.  Returns its path; raises :class:`NativeUnavailable` without
    a compiler and :class:`NativeBuildError` when the compile fails."""
    lib = lib_path()
    if lib.exists() and not force:
        return lib
    if shutil.which(_CXX) is None:
        raise NativeUnavailable(f"{_CXX} not found; native library "
                                "cannot be built on this machine")
    # compile beside the target and rename into place: two processes
    # building at once each publish a whole file
    tmp = lib.with_name(f"{lib.name}.{os.getpid()}.tmp")
    cmd = [_CXX, *_FLAGS, "-o", str(tmp)] + [str(_DIR / s) for s in SOURCES]
    try:
        subprocess.run(cmd, check=True, capture_output=True, text=True)
        os.replace(tmp, lib)
    except subprocess.CalledProcessError as e:
        raise NativeBuildError(
            f"{' '.join(cmd)} failed (rc={e.returncode}):\n"
            f"{e.stderr}") from e
    finally:
        tmp.unlink(missing_ok=True)
    for stale in _DIR.glob("libdwt_native*.so"):
        if stale != lib:
            stale.unlink(missing_ok=True)
    return lib


if __name__ == "__main__":
    path = build(force=True)
    print(f"built {path} ({os.path.getsize(path)} bytes)")
