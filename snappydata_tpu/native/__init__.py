"""Native (C++) kernels, built on first use.

Compiles native/_fastingest.cpp with the system compiler into
native/build/, under a name keyed on a hash of the source and the compile
line, so a stale binary or one built some other way is never loaded.
Without a compiler `fast_encode_strings` uses a vectorized pandas
implementation, several times slower on the load path: the failed build
is reported on stderr, and `build_info()` says which encoder is in use.
"""

from __future__ import annotations

import hashlib
import importlib.util
import os
import subprocess
import sys
import sysconfig
import time
from snappydata_tpu.utils import locks
from typing import Optional, Tuple

import numpy as np

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_SRC = os.path.join(_REPO_ROOT, "native", "_fastingest.cpp")
_BUILD_DIR = os.path.join(_REPO_ROOT, "native", "build")

_lock = locks.named_lock("native.loader")
_native = None
_tried = False
_info = {"available": False, "so_path": None, "build_s": None,
         "error": None}


def _build() -> str:
    """Path of the artefact for this source + compile line, compiling it
    when absent. Raises on a failed build."""
    cmd = [
        os.environ.get("CXX", "g++"), "-O3", "-shared", "-fPIC",
        "-std=c++17",
        f"-I{sysconfig.get_paths()['include']}",
        f"-I{np.get_include()}",
        _SRC,
    ]
    with open(_SRC, "rb") as fh:
        key = hashlib.sha256(fh.read() + "\0".join(cmd).encode()).hexdigest()
    so_path = os.path.join(_BUILD_DIR, f"_fastingest-{key[:16]}.so")
    if os.path.exists(so_path):
        return so_path
    os.makedirs(_BUILD_DIR, exist_ok=True)
    tmp = f"{so_path}.{os.getpid()}.tmp"
    t0 = time.time()
    try:
        subprocess.run(cmd + ["-o", tmp], check=True, capture_output=True,
                       timeout=120)
    except subprocess.CalledProcessError as e:
        raise RuntimeError(e.stderr.decode(errors="replace")[-2000:]) from e
    os.replace(tmp, so_path)   # atomic: a concurrent loader never sees half
    _info["build_s"] = round(time.time() - t0, 2)
    return so_path


def _load():
    global _native, _tried
    with _lock:
        if _tried:
            return _native
        _tried = True
        try:
            so_path = _build()
            spec = importlib.util.spec_from_file_location("_fastingest",
                                                          so_path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
        except (OSError, RuntimeError, ImportError,
                subprocess.TimeoutExpired) as e:
            _info["error"] = f"{type(e).__name__}: {e}"
            print("snappydata_tpu.native: no native encoder, string ingest "
                  f"uses the slower pandas path ({_info['error']})",
                  file=sys.stderr)
            return None
        _native = mod
        _info.update(available=True, so_path=so_path)
        return _native


def build_info() -> dict:
    """{available, so_path, build_s (None when the artefact was already
    there), error} after the one load attempt."""
    _load()
    return dict(_info)


def native_available() -> bool:
    return _load() is not None


def fast_encode_strings(values: np.ndarray, lookup: dict, store: list
                        ) -> Tuple[np.ndarray, Optional[np.ndarray]]:
    """One pass: intern `values` into (lookup, store) and return
    (int32 codes, null mask | None)."""
    values = np.ascontiguousarray(np.asarray(values, dtype=object))
    # normalize pandas-style missing markers (float NaN, pd.NA) to None so
    # native and fallback paths agree (NaN != NaN would otherwise mint one
    # dictionary entry per NaN object in the C kernel)
    import pandas as pd

    na = pd.isna(values)
    if na.any():
        values = values.copy()
        values[na] = None
    mod = _load()
    if mod is not None:
        return mod.encode_strings(values, lookup, store)
    # vectorized fallback: factorize in C, walk only the uniques in Python
    import pandas as pd

    inverse, uniques = pd.factorize(values, use_na_sentinel=True)
    trans = np.empty(max(1, len(uniques)), dtype=np.int32)
    for j, v in enumerate(uniques.tolist()):
        code = lookup.get(v)
        if code is None:
            code = len(store)
            lookup[v] = code
            store.append(v)
        trans[j] = code
    nulls = inverse < 0
    codes = trans[np.maximum(inverse, 0)].astype(np.int32)
    if nulls.any():
        codes = np.where(nulls, 0, codes)
        return codes, nulls
    return codes, None
