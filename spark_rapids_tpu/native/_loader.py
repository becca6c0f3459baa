"""Build-on-first-use logic for the native C++ host libraries.

The repository tracks only the C++ sources (``native/*.cpp`` in a
checkout, or next to this package in an installed layout).  Each library
is compiled with g++ next to its source under a content-tagged name
(``libsrt_native-<sha>.so``), so a binary built from older sources can
never shadow newer ones, and a second process finds the first one's build.

Callers keep a pure-Python fallback for hosts without a toolchain, but the
outcome is never silent: :data:`LOADED` records, per library, the path it
was loaded from or the reason it was not, and a failed build is logged.
"""

from __future__ import annotations

import ctypes
import logging
import os
import subprocess
from typing import Dict, Optional

_LOG = logging.getLogger(__name__)

#: libname -> {"path": str | None, "error": str | None}; what
#: ``chip_smoke.py`` and the doctor print about the native layer
LOADED: Dict[str, dict] = {}


def _candidate_dirs() -> list:
    pkg = os.path.dirname(os.path.abspath(__file__))
    repo = os.path.normpath(os.path.join(pkg, "..", "..", "native"))
    return [pkg, repo]


def _src_tag(src: str) -> str:
    """Short content hash carried in the compile output's filename."""
    import hashlib
    with open(src, "rb") as fh:
        return hashlib.sha256(fh.read()).hexdigest()[:10]


def find_or_build(libname: str, srcname: str,
                  extra_flags: tuple = ()) -> Optional[str]:
    """Path to the shared library built from ``srcname``, compiling it if
    this source revision has not been built yet."""
    stem, ext = os.path.splitext(libname)
    error = f"{srcname} not found"
    for d in _candidate_dirs():
        src = os.path.join(d, srcname)
        if not os.path.exists(src):
            continue
        so = os.path.join(d, f"{stem}-{_src_tag(src)}{ext}")
        if not os.path.exists(so):
            # build to a private name, then rename: a concurrent process
            # never dlopens a half-written library
            tmp = f"{so}.{os.getpid()}.tmp"
            try:
                subprocess.run(
                    ["g++", "-O3", "-fPIC", "-shared", "-std=c++17",
                     *extra_flags, "-o", tmp, src],
                    check=True, capture_output=True, timeout=300)
                os.replace(tmp, so)
            except Exception as e:
                detail = getattr(e, "stderr", b"") or b""
                error = (f"building {src} failed: {type(e).__name__}: {e} "
                         f"{detail.decode(errors='replace')[-500:]}").strip()
                _LOG.warning("%s; using the pure-Python fallback", error)
                if os.path.exists(tmp):
                    os.unlink(tmp)
                continue
        LOADED[libname] = {"path": so, "error": None}
        return so
    LOADED[libname] = {"path": None, "error": error}
    return None


def load(libname: str, srcname: str,
         extra_flags: tuple = ()) -> Optional[ctypes.CDLL]:
    """``find_or_build`` + ``dlopen``; None (with the reason recorded in
    :data:`LOADED`) when either step fails."""
    so = find_or_build(libname, srcname, extra_flags)
    if so is None:
        return None
    try:
        return ctypes.CDLL(so)
    except OSError as e:
        LOADED[libname] = {"path": None, "error": f"dlopen {so}: {e}"}
        return None
