"""Native components of the framework (C++, built lazily with g++).

The reference ships its data pipeline and runtime as C++
(REF:src/io/**, REF:src/engine/**); here the compute/scheduling side is
XLA's job, but the host-side input pipeline is genuinely CPU-bound
(SURVEY §7.3 hard-part 5), so it is native too: ``native/tpumx_io.cpp``
is compiled on first use into ``libtpumx_io.<key>.so`` next to this
package, where ``<key>`` hashes the source and the compiler command line.
"""
from __future__ import annotations

import hashlib
import os
import subprocess

_LIB_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_LIB_DIR, os.pardir, os.pardir, "native", "tpumx_io.cpp")
# no -march=native: the tree (built files included) is copied between
# machines, and the library must run on any of them
_CMD = ("g++", "-O3", "-funroll-loops", "-std=c++17", "-shared", "-fPIC")
_LIBS = ("-ljpeg", "-lpthread")


class NativeBuildError(RuntimeError):
    pass


def build_key(src):
    """Content key of the library built from ``src``: sha256 over the
    source bytes and the command line that compiles them."""
    h = hashlib.sha256("\0".join(_CMD + _LIBS).encode())
    with open(src, "rb") as f:
        h.update(f.read())
    return h.hexdigest()[:16]


def ensure_built():
    """Compile the native library unless the one keyed to this source and
    command line is already built; returns the .so path.  The key is in
    the file name, so a library built from other source, with other
    flags, or left in the tree under the old unkeyed name is never
    loaded."""
    src = os.path.abspath(_SRC)
    if not os.path.isfile(src):
        raise NativeBuildError(f"native source not found: {src}")
    so = os.path.join(_LIB_DIR, f"libtpumx_io.{build_key(src)}.so")
    if os.path.isfile(so):
        return so
    # build to a per-pid temp path then rename: atomic for concurrent
    # data-parallel processes racing to build on one machine
    tmp = f"{so}.build.{os.getpid()}"
    try:
        subprocess.run([*_CMD, src, "-o", tmp, *_LIBS], check=True,
                       capture_output=True, text=True, timeout=300)
        os.replace(tmp, so)
    except FileNotFoundError as e:
        raise NativeBuildError(f"g++ not available: {e}") from e
    except subprocess.CalledProcessError as e:
        raise NativeBuildError(
            f"native build failed:\n{e.stderr[-4000:]}") from e
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)
    return so
