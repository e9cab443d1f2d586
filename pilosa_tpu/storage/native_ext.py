"""Builder/loader for the real CPython extension (native/fastmutate.c).

The per-op mutate hot path needs a compiled crossing with no ctypes
per-call floor: this module compiles
``pilosa_tpu/native/fastmutate.c`` against the running interpreter's
headers + numpy's C API on first use, caches the .so keyed by source
hash and machine (same scheme as storage.native), and loads it as a
genuine extension module. Everything degrades gracefully:

- ``PILOSA_TPU_NATIVE_EXT=0`` — escape hatch, never build or load;
- no toolchain / headers / build failure — fall back to the
  pure-Python mutate paths (the extension itself bails per-op on
  anything unusual), log the compiler's error once and report
  ``available() == False`` (``/status`` ``build.nativeExt``);
- big-endian hosts — disabled (the extension builds little-endian wire
  records and reads ``<u2``/``<u4``/``<u8`` buffers as host ints).

``EXT`` is the loaded module or None; the roaring hot paths read it as
one module-attribute load per op. ``load()`` triggers the build (called
from Fragment.open and the test session's conftest hook).
"""

from __future__ import annotations

import hashlib
import importlib.machinery
import importlib.util
import os
import subprocess
import sys
import sysconfig
import threading

_NATIVE_DIR = os.path.join(os.path.dirname(os.path.dirname(__file__)),
                           "native")
_SRC = os.path.join(_NATIVE_DIR, "fastmutate.c")
_MOD_NAME = "pilosa_fastmutate"

EXT = None
_tried = False
_lock = threading.Lock()


def _so_path() -> str:
    # Keyed by source hash + interpreter tag + machine: the module links
    # against this exact CPython ABI, and -march=native makes it
    # per-machine (same rationale as storage.native._so_path).
    with open(_SRC, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    tag = sysconfig.get_config_var("SOABI") or "abi"
    from ..utils import cache_dir, machine_tag
    cache = cache_dir()
    os.makedirs(cache, exist_ok=True)
    return os.path.join(
        cache, f"{_MOD_NAME}-{digest}-{tag}-{machine_tag()}.so")


def _build(so: str) -> None:
    import numpy as np
    py_inc = sysconfig.get_paths()["include"]
    cmd = ["g++", "-O2", "-march=native", "-shared", "-fPIC",
           "-I" + py_inc, "-I" + np.get_include(),
           "-o", so + ".tmp", _SRC]
    subprocess.run(cmd, check=True, capture_output=True)
    os.replace(so + ".tmp", so)


def load():
    """Build (cached) + load the extension; returns the module or
    None. Idempotent and thread-safe; failures latch to None."""
    global EXT, _tried
    if _tried:
        return EXT
    with _lock:
        if _tried:
            return EXT
        try:
            if (os.environ.get("PILOSA_TPU_NATIVE_EXT", "1") == "0"
                    or sys.byteorder != "little"):
                EXT = None
            else:
                so = _so_path()
                if not os.path.exists(so):
                    _build(so)
                loader = importlib.machinery.ExtensionFileLoader(
                    _MOD_NAME, so)
                spec = importlib.util.spec_from_file_location(
                    _MOD_NAME, so, loader=loader)
                mod = importlib.util.module_from_spec(spec)
                loader.exec_module(mod)
                EXT = mod
        except Exception as e:  # noqa: BLE001 - Python mutate paths serve
            EXT = None
            from .native import _log_build_failure
            _log_build_failure(_MOD_NAME, e)
        _tried = True
        return EXT


def available() -> bool:
    return load() is not None
