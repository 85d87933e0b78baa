"""Loader for ray_tpu's native (C++) components.

Two CPython extensions are built in-place by the repo Makefile:

* ``_rtstore`` — shared-memory object store (src/store/)
* ``_rtpump``  — direct-plane frame pump: framed-channel I/O, call-frame
  codec, per-channel seq dispatch (src/pump/)

On first import, if a .so is missing and a toolchain is available, we build
on demand; callers fall back to the pure-Python implementations when a
native module is unavailable, so the framework works (slower) on machines
without g++ — a failed build says so on stderr. ``RAY_TPU_NO_NATIVE_BUILD=1`` suppresses the on-demand build;
``RTPU_NO_NATIVE=1`` makes the frame-pump callers ignore the extension even
when present (see core/frame_pump.py).
"""

from __future__ import annotations

import importlib
import os
import subprocess
import sys
import threading

_lock = threading.Lock()
_mods: dict = {}
_build_attempted = False

_PKG_DIR = os.path.dirname(os.path.abspath(__file__))
_REPO_ROOT = os.path.dirname(os.path.dirname(_PKG_DIR))


def _try_import(name: str):
    try:
        return importlib.import_module(f".{name}", __name__)
    except ImportError:
        return None


def build(timeout: float = 300.0) -> "subprocess.CompletedProcess":
    """``make native`` from the tracked sources (the Makefile rebuilds an
    extension whose sources are newer). Raises FileNotFoundError where
    there is no ``make``; the caller reads the return code."""
    return subprocess.run(
        ["make", "-C", _REPO_ROOT, "native", f"PY={sys.executable}"],
        capture_output=True,
        text=True,
        timeout=timeout,
    )


def _try_build() -> bool:
    if not os.path.exists(os.path.join(_REPO_ROOT, "Makefile")):
        return False
    try:
        proc = build(timeout=120)
    except (OSError, subprocess.TimeoutExpired) as e:
        detail = repr(e)
    else:
        if proc.returncode == 0:
            return True
        detail = (proc.stderr or proc.stdout)[-400:]
    # The pure-Python store and pump are slower, not wrong, so this is
    # not fatal; it must not be silent either.
    print(
        f"[ray_tpu._native] WARNING: `make native` failed, falling back "
        f"to the pure-Python store and pump: {detail}",
        file=sys.stderr,
    )
    return False


def _load(name: str):
    """Return the named extension module, building once if needed."""
    global _build_attempted
    with _lock:
        mod = _mods.get(name)
        if mod is not None:
            return mod
        mod = _try_import(name)
        if mod is None and not _build_attempted:
            _build_attempted = True
            if os.environ.get("RAY_TPU_NO_NATIVE_BUILD") != "1" and _try_build():
                mod = _try_import(name)
        if mod is not None:
            _mods[name] = mod
        return mod


def load_rtstore():
    """The _rtstore extension module, building it if needed, or None."""
    return _load("_rtstore")


def load_rtpump():
    """The _rtpump extension module, building it if needed, or None."""
    return _load("_rtpump")


def native_store_available() -> bool:
    return load_rtstore() is not None
