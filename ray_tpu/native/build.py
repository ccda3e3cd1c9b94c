"""On-demand build of the native components.

The reference ships its native runtime prebuilt via bazel into the wheel
(reference: BUILD.bazel, python/ray/_raylet.so); here the C++ sources are
compiled at first use with g++ into the git-ignored `_build/`, under a name
that carries a hash of the sources' CONTENT. A copy of the working tree
(fresh mtimes, somebody else's binary) therefore runs what its own
`shm_pool.cc` produces, never a stale or foreign library.
"""

from __future__ import annotations

import glob
import hashlib
import os
import subprocess
import threading

_NATIVE_DIR = os.path.dirname(os.path.abspath(__file__))
_BUILD_DIR = os.path.join(_NATIVE_DIR, "_build")
_lock = threading.Lock()


def build_library(name: str, sources: list[str], extra_flags: list[str] | None = None) -> str:
    """Compiles `sources` into lib<name>-<content hash>.so unless that exact
    build exists; returns the .so path."""
    srcs = [os.path.join(_NATIVE_DIR, s) for s in sources]
    flags = ["-O2", "-std=c++17", "-shared", "-fPIC"]
    tail = ["-lpthread"] + (extra_flags or [])
    digest = hashlib.sha256(" ".join(flags + tail).encode())
    for src in srcs:
        with open(src, "rb") as f:
            digest.update(f.read())
    out = os.path.join(_BUILD_DIR, f"lib{name}-{digest.hexdigest()[:16]}.so")
    with _lock:
        if os.path.exists(out):
            return out
        os.makedirs(_BUILD_DIR, exist_ok=True)
        # Built beside the target and renamed: another process loading the
        # library never sees a half-written file.
        tmp = f"{out}.{os.getpid()}.tmp"
        cmd = ["g++", *flags, "-o", tmp, *srcs, *tail]
        try:
            # The lock exists precisely to serialize concurrent builders on the
            # one output file; nothing latency-sensitive contends on it.
            subprocess.run(cmd, check=True, capture_output=True, text=True, timeout=300)  # lint: disable=blocking-in-loop
        except FileNotFoundError as e:
            raise RuntimeError(
                f"cannot build lib{name}.so: no g++ on PATH (ray_tpu compiles "
                f"{', '.join(sources)} at first use and needs a C++17 compiler)"
            ) from e
        except subprocess.CalledProcessError as e:
            raise RuntimeError(f"building lib{name}.so failed:\n{e.stderr}") from e
        os.replace(tmp, out)
        for stale in glob.glob(os.path.join(_BUILD_DIR, f"lib{name}*.so")):
            if stale != out:
                try:
                    os.unlink(stale)
                except OSError:
                    pass  # another process beat us to it
    return out


def shm_pool_lib() -> str:
    return build_library("shm_pool", ["shm_pool.cc"])
