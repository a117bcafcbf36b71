"""Native fast paths with build-on-first-use and pure-Python fallback.

``get_framing()`` returns the compiled ``_framing`` extension module
(frame pack/drain for the socket transports) and ``get_ring()`` the
``_ring`` extension (SPSC shared-memory ring ops) — or ``None``.  The
first call for each may invoke the C compiler (a few seconds, cached as
a ``.so`` next to the source); any failure — no compiler, no headers,
sandbox — silently falls back to the Python implementations in
``transport/``.  Set ``TRACEML_NO_NATIVE=1`` to skip both.

The built file's name carries a hash of its ``.c`` source, so only a
binary built from the source as it is now is ever loaded: an edited
source builds anew, and binaries of other sources are removed.
"""

from __future__ import annotations

import hashlib
import importlib.util
import os
import subprocess
import sysconfig
import threading
from pathlib import Path
from typing import Dict, Optional

from traceml_tpu.config import flags

_lock = threading.Lock()
_cached: Dict[str, Optional[object]] = {}

_HERE = Path(__file__).resolve().parent


def _artifact(src_name: str, mod_name: str) -> Path:
    """``<mod>.<source hash><EXT_SUFFIX>`` next to the source."""
    digest = hashlib.sha256((_HERE / src_name).read_bytes()).hexdigest()[:16]
    ext = sysconfig.get_config_var("EXT_SUFFIX") or ".so"
    return _HERE / f"{mod_name}.{digest}{ext}"


def _try_import(mod_name: str, so: Path) -> Optional[object]:
    if not so.is_file():
        return None
    try:
        # the name must match PyInit_<mod_name>
        spec = importlib.util.spec_from_file_location(mod_name, so)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)  # type: ignore[union-attr]
        return mod
    except Exception:
        return None


def _build(src_name: str, mod_name: str, out: Path) -> bool:
    """Compile one source file to ``out``; True on success.  Builds to
    a private name and renames, so a concurrent loader never sees a
    half-written file; then removes binaries of older sources."""
    tmp = out.with_name(f"{out.name}.{os.getpid()}.tmp")
    try:
        include = sysconfig.get_paths()["include"]
        cmd = [
            os.environ.get("CC", "cc"),
            "-O2",
            "-shared",
            "-fPIC",
            f"-I{include}",
            str(_HERE / src_name),
            "-o",
            str(tmp),
        ]
        proc = subprocess.run(
            cmd, capture_output=True, text=True, timeout=120
        )
        if proc.returncode != 0 or not tmp.exists():
            return False
        os.replace(tmp, out)
    except Exception:
        return False
    finally:
        tmp.unlink(missing_ok=True)
    for stale in _HERE.glob(f"{mod_name}*.so"):
        if stale != out:
            stale.unlink(missing_ok=True)
    return True


def _get(src_name: str, mod_name: str) -> Optional[object]:
    if mod_name in _cached:
        return _cached[mod_name]
    with _lock:
        if mod_name in _cached:
            return _cached[mod_name]
        if flags.NO_NATIVE.truthy():
            _cached[mod_name] = None
            return None
        try:
            so = _artifact(src_name, mod_name)
        except OSError:  # no source shipped: nothing may be loaded
            _cached[mod_name] = None
            return None
        mod = _try_import(mod_name, so)
        if mod is None and _build(src_name, mod_name, so):
            mod = _try_import(mod_name, so)
        _cached[mod_name] = mod
        return mod


def get_framing() -> Optional[object]:
    """The compiled framing extension, built on first use; None on failure."""
    return _get("framing.c", "_framing")


def get_ring() -> Optional[object]:
    """The compiled SPSC ring extension, built on first use; None on failure."""
    return _get("ring.c", "_ring")
