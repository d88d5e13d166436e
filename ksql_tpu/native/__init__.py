"""Native (C++) ingest tier: batch payloads -> columnar arrays.

The runtime-native component prescribed by SURVEY §2.2 — the reference's
hot host path is native (Kafka client codecs, RocksDB JNI); ours is a
columnar batch decoder (ingest.cc) that turns a micro-batch of payloads
into device-ready arrays in one call, including stable-hash64 string
codes bit-identical to the Python dictionary encoder.  Three payload
modes are supported (MODE_JSON / MODE_JSON_SINGLE / MODE_DELIMITED);
rows the native grammar cannot decode bit-identically to the Python
serde come back with ``row_ok`` False and the caller replays them.

The shared library builds on first use with g++ (no external deps) from
ingest.cc and is cached next to it under a name that carries the source's
content hash, so a library on disk is always the build of the source on
disk (file times say nothing after a tree copy).  Every consumer falls back
to the pure-Python decode path when the toolchain or build is unavailable;
``build_error()`` says why.
"""

from __future__ import annotations

import ctypes
import glob
import hashlib
import logging
import os
import subprocess
import threading
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "ingest.cc")
#: every build of the library, whatever source it came from (git-ignored)
LIB_GLOB = os.path.join(_DIR, "_libingest*.so")

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_error: Optional[str] = None  # why the library is unavailable, once known

# field type codes (mirror ingest.cc FieldType)
FT_BIGINT, FT_INT, FT_DOUBLE, FT_BOOLEAN, FT_STRING = 0, 1, 2, 3, 4

# payload modes (mirror ingest.cc ParseMode)
MODE_JSON = 0         # one JSON object per payload (wrapped values)
MODE_JSON_SINGLE = 1  # one bare JSON scalar per payload (unwrapped single)
MODE_DELIMITED = 2    # commons-csv minimal-quote row per payload

_NP_OF = {
    FT_BIGINT: np.int64,
    FT_INT: np.int32,
    FT_DOUBLE: np.float64,
    FT_BOOLEAN: np.uint8,
    FT_STRING: np.int64,
}


def lib_path() -> str:
    """The library built from ingest.cc as it is on disk now."""
    with open(_SRC, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    return os.path.join(_DIR, f"_libingest.{digest}.so")


def _build() -> ctypes.CDLL:
    path = lib_path()
    if not os.path.exists(path):
        # build beside the target and rename: a concurrent process (test
        # workers, a second entry point) sees a whole library or none
        tmp = f"{path}.{os.getpid()}.tmp"
        cmd = ["g++", "-O3", "-shared", "-fPIC", "-std=c++17", _SRC, "-o", tmp]
        try:
            subprocess.run(cmd, check=True, capture_output=True, timeout=120)
            os.replace(tmp, path)
        finally:
            if os.path.exists(tmp):
                os.unlink(tmp)
        for stale in glob.glob(LIB_GLOB):
            if stale != path:
                try:
                    os.unlink(stale)
                except OSError:
                    pass  # another process still maps it; harmless
    lib = ctypes.CDLL(path)
    lib.ingest_parse_batch.restype = ctypes.c_void_p
    lib.ingest_parse_batch.argtypes = [
        ctypes.c_char_p, ctypes.POINTER(ctypes.c_int64), ctypes.c_int,
        ctypes.c_int, ctypes.c_char_p, ctypes.POINTER(ctypes.c_int64),
        ctypes.POINTER(ctypes.c_int32),
        ctypes.POINTER(ctypes.c_void_p), ctypes.POINTER(ctypes.c_void_p),
        ctypes.POINTER(ctypes.c_uint8),
    ]
    lib.ingest_parse_batch2.restype = ctypes.c_void_p
    lib.ingest_parse_batch2.argtypes = lib.ingest_parse_batch.argtypes + [
        ctypes.c_int32, ctypes.c_char,
    ]
    lib.ingest_arena_count.restype = ctypes.c_int64
    lib.ingest_arena_count.argtypes = [ctypes.c_void_p]
    lib.ingest_arena_bytes_len.restype = ctypes.c_int64
    lib.ingest_arena_bytes_len.argtypes = [ctypes.c_void_p]
    lib.ingest_arena_fetch.restype = None
    lib.ingest_arena_fetch.argtypes = [
        ctypes.c_void_p, ctypes.POINTER(ctypes.c_int64),
        ctypes.POINTER(ctypes.c_int64), ctypes.c_char_p,
    ]
    lib.ingest_free_arena.restype = None
    lib.ingest_free_arena.argtypes = [ctypes.c_void_p]
    lib.ingest_hash_string.restype = ctypes.c_int64
    lib.ingest_hash_string.argtypes = [ctypes.c_char_p, ctypes.c_int64]
    return lib


def get_lib() -> Optional[ctypes.CDLL]:
    """The loaded native library, building it on first use; None when the
    toolchain is unavailable (callers use the Python path)."""
    global _lib, _error
    if _lib is not None or _error is not None:
        return _lib
    with _lock:
        if _lib is not None or _error is not None:
            return _lib
        try:
            _lib = _build()
        except Exception as e:  # noqa: BLE001 — no compiler / bad env: fall back
            stderr = (getattr(e, "stderr", None) or b"")[-500:]
            _error = (
                f"{type(e).__name__}: {e} "
                + stderr.decode("utf-8", "replace")
            ).strip()
            logging.getLogger(__name__).warning(
                "native ingest unavailable, sources decode per record in "
                "Python: %s", _error,
            )
    return _lib


def available() -> bool:
    return get_lib() is not None


def build_error() -> Optional[str]:
    """Why ``available()`` is False (None while it is True or untried)."""
    return _error


def parse_json_batch(
    payloads: Sequence[Any],
    fields: Sequence[Tuple[str, int]],
    mode: int = MODE_JSON,
    delimiter: str = ",",
) -> Optional[Tuple[Dict[str, np.ndarray], Dict[str, np.ndarray],
                    np.ndarray, List[Tuple[int, str]]]]:
    """Parse a batch of payloads into columns.

    Returns (data, valid, row_ok, learned) — ``learned`` is this batch's
    unique (hash, string) pairs for dictionary learning — or None when the
    native library is unavailable.  Rows with ``row_ok`` False must be
    decoded by the Python fallback.
    """
    lib = get_lib()
    if lib is None:
        return None
    n = len(payloads)
    enc: List[bytes] = []
    offs = np.zeros(n + 1, np.int64)
    for i, p in enumerate(payloads):
        b = p if isinstance(p, bytes) else str(p).encode("utf-8")
        enc.append(b)
        offs[i + 1] = offs[i] + len(b)
    buf = b"".join(enc)
    names = b""
    name_offs = np.zeros(len(fields) + 1, np.int64)
    types = np.zeros(len(fields), np.int32)
    for f, (name, code) in enumerate(fields):
        nb = name.encode("utf-8")
        names += nb
        name_offs[f + 1] = name_offs[f] + len(nb)
        types[f] = code
    data: Dict[str, np.ndarray] = {}
    valid: Dict[str, np.ndarray] = {}
    dptrs = (ctypes.c_void_p * len(fields))()
    vptrs = (ctypes.c_void_p * len(fields))()
    for f, (name, code) in enumerate(fields):
        d = np.zeros(n, _NP_OF[code])
        v = np.zeros(n, np.uint8)
        data[name] = d
        valid[name] = v
        dptrs[f] = d.ctypes.data_as(ctypes.c_void_p)
        vptrs[f] = v.ctypes.data_as(ctypes.c_void_p)
    row_ok = np.zeros(n, np.uint8)
    arena = lib.ingest_parse_batch2(
        buf,
        offs.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        n,
        len(fields),
        names,
        name_offs.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        types.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        ctypes.cast(dptrs, ctypes.POINTER(ctypes.c_void_p)),
        ctypes.cast(vptrs, ctypes.POINTER(ctypes.c_void_p)),
        row_ok.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        mode,
        delimiter.encode("ascii"),
    )
    learned: List[Tuple[int, str]] = []
    if arena:
        try:  # a failed fetch/decode must still free the arena
            cnt = lib.ingest_arena_count(arena)
            blen = lib.ingest_arena_bytes_len(arena)
            if cnt:
                hashes = np.zeros(cnt, np.int64)
                ends = np.zeros(cnt, np.int64)
                bbuf = ctypes.create_string_buffer(int(blen))
                lib.ingest_arena_fetch(
                    arena,
                    hashes.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
                    ends.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
                    bbuf,
                )
                raw = bbuf.raw
                start = 0
                for h, end in zip(hashes.tolist(), ends.tolist()):
                    learned.append((h, raw[start:end].decode("utf-8")))
                    start = end
        finally:
            lib.ingest_free_arena(arena)
    return data, {k: v.astype(bool) for k, v in valid.items()}, row_ok.astype(bool), learned


def parse_batch(payloads: Sequence[Any], spec: Dict[str, Any]):
    """Parse a batch against a ``native_ingest_fields`` spec dict
    ({"mode", "fields", "delimiter", ...})."""
    return parse_json_batch(
        payloads,
        spec["fields"],
        mode=spec.get("mode", MODE_JSON),
        delimiter=spec.get("delimiter", ","),
    )
