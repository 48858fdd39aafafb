"""Shared build-and-load helper for the C++ extensions.

Both native components (the porcupine DFS checker and the TCP
transport) ship as a single .cpp compiled with g++ on first use — no
pybind11 in this image, plain C ABI via ctypes.  This helper owns the
two tricky parts:

* the library that is loaded is always the one the committed source
  produces on THIS machine: the built file is named by a hash of the
  source text and the compile command, so a binary left behind by
  another checkout, another compiler line or a copy of the tree (which
  keeps no useful mtimes) is never picked up;
* concurrent processes (cluster children, parallel pytest) must never
  dlopen a half-written .so, so the compile goes to a process-unique
  temp name and is published with an atomic rename.
"""

from __future__ import annotations

import ctypes
import glob
import hashlib
import os
import subprocess
from typing import Sequence

__all__ = ["build_and_load"]


def build_and_load(src: str, so: str, extra_flags: Sequence[str] = ()) -> ctypes.CDLL:
    """Compile ``src`` and dlopen the result.  ``so`` gives the
    directory and stem (``libfoo.so`` → ``libfoo.<hash>.so``); the
    build is skipped when that exact file already exists.

    Raises on compile or load failure — callers decide whether to fall
    back to a Python implementation or to hard-fail.
    """
    cmd = ["g++", "-O2", "-shared", "-fPIC", "-std=c++17", *extra_flags]
    digest = hashlib.sha256()
    digest.update(" ".join(cmd).encode())
    with open(src, "rb") as f:
        digest.update(f.read())
    stem = so[: -len(".so")]
    built = f"{stem}.{digest.hexdigest()[:16]}.so"
    if not os.path.exists(built):
        tmp = f"{built}.{os.getpid()}.tmp"
        subprocess.run(
            [*cmd, src, "-o", tmp], check=True, capture_output=True,
        )
        os.replace(tmp, built)
        # Binaries of earlier source revisions are dead weight.
        for old in glob.glob(f"{glob.escape(stem)}.*.so") + [so]:
            if old != built:
                try:
                    os.unlink(old)
                except OSError:
                    pass
    return ctypes.CDLL(built)
