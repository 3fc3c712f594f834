"""Lazy loader for the native (C++) pieces.

The shared objects are built by ``make -C cpp`` into this directory.  The
loader runs the (mtime-aware, atomic-rename) build on every first load so a
source change can't leave a stale binary silently diverging from the Python
fallback; if the build fails or no toolchain exists it returns None —
callers keep their pure-Python fallback, so the framework works (slower)
without a toolchain, and the log says why it is slower.  mtimes do not
survive a copy of the tree: a run that must not pass on the fallback
(``chip_smoke.py``) rebuilds unconditionally (``make -B``) first and
fails when :func:`load` returns None.
"""

from __future__ import annotations

import ctypes
import logging
import os
import subprocess
import threading

log = logging.getLogger("stpu.native")

_DIR = os.path.dirname(os.path.abspath(__file__))
_CPP_DIR = os.path.join(os.path.dirname(os.path.dirname(_DIR)), "cpp")

_lock = threading.Lock()
_cache: dict[str, "ctypes.CDLL | None"] = {}


def _try_build() -> None:
    if not os.path.isdir(_CPP_DIR):
        return
    try:
        proc = subprocess.run(
            ["make", "-C", _CPP_DIR],
            capture_output=True,
            timeout=120,
            check=False,
        )
    except (OSError, subprocess.TimeoutExpired) as e:
        log.warning("native build did not run (%s: %s); callers keep "
                    "their pure-Python fallback", type(e).__name__, e)
        return
    if proc.returncode != 0:
        log.warning("native build failed (make rc=%d): %s; callers keep "
                    "their pure-Python fallback", proc.returncode,
                    proc.stderr.decode(errors="replace").strip()[-400:])


def load(name: str) -> "ctypes.CDLL | None":
    """Load ``lib<name>.so`` from this directory, (re)building first."""
    with _lock:
        if name in _cache:
            return _cache[name]
        path = os.path.join(_DIR, f"lib{name}.so")
        # always run make, not just when the .so is missing: it is
        # mtime-aware (a fast no-op when fresh) and a stale binary from
        # older sources would silently break Python/native parity
        _try_build()
        lib: "ctypes.CDLL | None" = None
        if os.path.exists(path):
            try:
                lib = ctypes.CDLL(path)
            except OSError:
                lib = None
        _cache[name] = lib
        return lib
