"""Build the native libraries on demand (g++; named by source hash).

Reference contrast: the reference's cmake tree builds libpaddle_fluid; this
build keeps native components small, each a standalone .so with a C ABI
bound via ctypes (pybind11 is not available in this environment).

A binary is trusted only when its file name carries the hash of exactly
the sources, headers and command line that produce it: the .so files are
git-ignored, so a checkout copied from disk can hold one that no committed
source built, and an mtime comparison cannot tell.
"""

import glob
import hashlib
import os
import subprocess

_HERE = os.path.dirname(os.path.abspath(__file__))


def build_library(name, sources, extra_flags=(), deps=()):
    """Compile sources into lib<name>-<hash>.so next to this file; returns
    the path. The hash covers the sources, the header dependencies and the
    compiler flags, so any change to them is a rebuild and a binary of
    another origin is never loaded; older builds of the library are
    removed."""
    srcs = [os.path.join(_HERE, s) for s in sources]
    flags = ["-O2", "-fPIC", "-shared", "-std=c++17"]
    h = hashlib.sha256(" ".join([*flags, *extra_flags]).encode("utf-8"))
    for path in srcs + [os.path.join(_HERE, d) for d in deps]:
        with open(path, "rb") as f:
            h.update(b"\0" + os.path.basename(path).encode("utf-8") + b"\0")
            h.update(f.read())
    out = os.path.join(_HERE, f"lib{name}-{h.hexdigest()[:16]}.so")
    if os.path.exists(out):
        return out
    # build beside the target and rename: a concurrent builder (a test
    # subprocess, a decode worker) never loads a half-written file
    tmp = f"{out}.{os.getpid()}.tmp"
    cmd = ["g++", *flags, "-o", tmp, *srcs, *extra_flags]
    try:
        subprocess.run(cmd, check=True, capture_output=True, text=True)
        os.replace(tmp, out)
    except subprocess.CalledProcessError as e:
        raise RuntimeError(
            f"native build failed: {' '.join(cmd)}\n{e.stderr}") from e
    except FileNotFoundError:
        raise RuntimeError("g++ not found; native components unavailable")
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)
    for stale in glob.glob(os.path.join(_HERE, f"lib{name}*.so")):
        if stale != out:
            os.unlink(stale)
    return out


def recordio_lib():
    return build_library("recordio", ["recordio.cc"], ["-lz"])


def infer_lib():
    return build_library("ptinfer", ["infer.cc"], deps=["runtime.h"])


def train_lib():
    return build_library("pttrain", ["train.cc"], deps=["runtime.h"])
