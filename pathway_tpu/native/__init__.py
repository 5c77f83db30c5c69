"""ctypes bindings for the native runtime tier (src/pw_native.cpp).

Builds on first use with g++; falls back to pure-Python implementations
when no compiler is available.  The library is compiled ``-march=native``,
so it belongs to the machine that built it: its file name carries a key of
the source AND this host's CPU flags, and a library under any other key
(an older source, or a tree copied from another machine) is never loaded.
The native hash is the canonical row-key hash whenever the library is
active — it must stay bit-stable across versions (persisted state depends
on it).
"""

from __future__ import annotations

import ctypes
import glob
import hashlib
import os
import platform
import subprocess
import threading

import numpy as np

_HERE = os.path.dirname(__file__)
_SRC = os.path.join(_HERE, "src", "pw_native.cpp")

_lib = None
_lock = threading.Lock()
_build_failed = False


def _cpu_identity() -> str:
    """What ``-march=native`` keys on: the CPU model and its feature flags."""
    try:
        with open("/proc/cpuinfo") as f:
            lines = f.read().splitlines()
        keep = sorted({
            ln for ln in lines
            if ln.startswith(("flags", "Features", "model name"))
        })
        if keep:
            return "\n".join(keep)
    except OSError:
        pass
    return f"{platform.machine()} {platform.processor()}"


def _so_path() -> str:
    h = hashlib.sha256()
    with open(_SRC, "rb") as f:
        h.update(f.read())
    h.update(_cpu_identity().encode())
    return os.path.join(
        _HERE, "src", f"libpw_native.{h.hexdigest()[:16]}.so"
    )


def _build() -> str | None:
    so = _so_path()
    if os.path.exists(so):
        return so
    tmp = f"{so}.{os.getpid()}.tmp"
    try:
        subprocess.run(
            ["g++", "-O3", "-march=native", "-shared", "-fPIC", "-std=c++17",
             _SRC, "-o", tmp],
            check=True, capture_output=True, timeout=120,
        )
        os.replace(tmp, so)  # atomic: concurrent builders never dlopen a torn file
    except Exception:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        return None
    # libraries under other keys (older source, another machine) are dead
    for stale in glob.glob(os.path.join(_HERE, "src", "libpw_native*.so")):
        if stale != so:
            try:
                os.unlink(stale)
            except OSError:
                pass
    return so


def get_lib():
    global _lib, _build_failed
    if _lib is not None or _build_failed:
        return _lib
    with _lock:
        if _lib is not None or _build_failed:
            return _lib
        so = _build()
        if so is None:
            _build_failed = True
            return None
        lib = ctypes.CDLL(so)
        lib.pw_hash128.argtypes = [
            ctypes.c_char_p, ctypes.c_uint64, ctypes.c_uint64,
            ctypes.POINTER(ctypes.c_uint64), ctypes.POINTER(ctypes.c_uint64),
        ]
        # self-test against the Python mirror before adopting the native
        # tier: a miscompiled library must never become the canonical
        # row-key hash
        hi = ctypes.c_uint64()
        lo = ctypes.c_uint64()
        probe = b"pw-native-selftest\x00\x01\x02"
        lib.pw_hash128(probe, len(probe), 12345,
                       ctypes.byref(hi), ctypes.byref(lo))
        if ((hi.value << 64) | lo.value) != _py_hash128(probe, 12345):
            _build_failed = True
            return None
        lib.pw_hash_rows.restype = None
        lib.pw_hash_rows.argtypes = [
            ctypes.c_uint64, ctypes.c_uint64,
            ctypes.POINTER(ctypes.c_int32),
            ctypes.POINTER(ctypes.c_void_p),
            ctypes.POINTER(ctypes.POINTER(ctypes.c_int64)),
            ctypes.c_uint64,
            ctypes.POINTER(ctypes.c_uint64), ctypes.POINTER(ctypes.c_uint64),
        ]
        lib.pw_consolidate.restype = ctypes.c_int64
        lib.pw_consolidate.argtypes = [
            ctypes.c_uint64,
            ctypes.POINTER(ctypes.c_uint64), ctypes.POINTER(ctypes.c_uint64),
            ctypes.POINTER(ctypes.c_uint64), ctypes.POINTER(ctypes.c_int64),
            ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_int64),
        ]
        lib.pw_auto_row_keys.restype = None
        lib.pw_auto_row_keys.argtypes = [
            ctypes.c_int64, ctypes.c_uint64,
            ctypes.POINTER(ctypes.c_uint64), ctypes.POINTER(ctypes.c_uint64),
        ]
        lib.pw_ref_scalar_rows.restype = None
        lib.pw_ref_scalar_rows.argtypes = [
            ctypes.c_uint64, ctypes.c_uint64,
            ctypes.POINTER(ctypes.c_int32),
            ctypes.POINTER(ctypes.c_void_p),
            ctypes.POINTER(ctypes.POINTER(ctypes.c_int64)),
            ctypes.POINTER(ctypes.c_uint64), ctypes.POINTER(ctypes.c_uint64),
        ]
        _lib = lib
    return _lib


def available() -> bool:
    return get_lib() is not None


# ---------------------------------------------------------------------------
# Pure-Python mirror of pw_native.cpp's hash — bit-identical, so keys are
# stable whether or not the compiled library is present.
# ---------------------------------------------------------------------------

_M64 = (1 << 64) - 1


def _mix64(k: int) -> int:
    k ^= k >> 33
    k = (k * 0xFF51AFD7ED558CCD) & _M64
    k ^= k >> 33
    k = (k * 0xC4CEB9FE1A85EC53) & _M64
    k ^= k >> 33
    return k


class _PyHashState:
    __slots__ = ("a", "b")

    def __init__(self, seed: int):
        self.a = 0x9E3779B97F4A7C15 ^ seed
        self.b = 0xBF58476D1CE4E5B9 ^ ((seed * 0x94D049BB133111EB + 1) & _M64)

    def update_u64(self, v: int) -> None:
        self.a = (_mix64(self.a ^ v) * 0x2545F4914F6CDD1D) & _M64
        self.b = _mix64((self.b + v + 0x165667B19E3779F9) & _M64)

    def update_bytes(self, data: bytes) -> None:
        i, ln = 0, len(data)
        while i + 8 <= ln:
            self.update_u64(int.from_bytes(data[i : i + 8], "little"))
            i += 8
        rem = ln - i
        if rem:
            tail = int.from_bytes(data[i:] + b"\0" * (8 - rem), "little")
            self.update_u64(tail ^ ((rem << 56) & _M64))
        self.update_u64(ln ^ 0xA5A5A5A5A5A5A5A5)

    def final(self) -> tuple[int, int]:
        hi = _mix64(self.a ^ (self.b >> 32))
        lo = _mix64(self.b ^ ((self.a << 17) & _M64) ^ 0x27D4EB2F165667C5)
        return hi, lo


def _py_hash128(data: bytes, seed: int = 0) -> int:
    s = _PyHashState(seed & _M64)
    s.update_bytes(data)
    hi, lo = s.final()
    return (hi << 64) | lo


def hash128(data: bytes, seed: int = 0) -> int:
    lib = get_lib()
    if lib is None:
        return _py_hash128(data, seed)
    hi = ctypes.c_uint64()
    lo = ctypes.c_uint64()
    lib.pw_hash128(data, len(data), seed & 0xFFFFFFFFFFFFFFFF,
                   ctypes.byref(hi), ctypes.byref(lo))
    return (hi.value << 64) | lo.value


def _py_hash_rows(columns: list, seed: int) -> np.ndarray:
    """Bit-identical Python mirror of pw_hash_rows."""
    import struct

    n = len(columns[0]) if columns else 0
    prepared = []
    for col in columns:
        if isinstance(col, np.ndarray) and col.dtype == np.int64:
            prepared.append((0, col))
        elif isinstance(col, np.ndarray) and col.dtype == np.float64:
            prepared.append((1, col))
        else:
            prepared.append(
                (2, [v.encode() if isinstance(v, str) else bytes(v) for v in col])
            )
    out = np.empty(n, dtype=object)
    for i in range(n):
        s = _PyHashState(seed & _M64)
        for kind, col in prepared:
            s.update_u64(0x1000 + kind)
            if kind == 0:
                s.update_u64(int(col[i]) & _M64)
            elif kind == 1:
                s.update_u64(
                    int.from_bytes(struct.pack("<d", float(col[i])), "little")
                )
            else:
                s.update_bytes(col[i])
        hi, lo = s.final()
        out[i] = (hi << 64) | lo
    return out


def hash_rows(columns: list[np.ndarray | list], seed: int = 0) -> np.ndarray:
    """Batch-hash rows from typed columns -> uint128 as (n,) object array of ints.

    Columns: int64 arrays, float64 arrays, or lists of bytes/str.  The native
    and Python paths produce identical hashes.
    """
    n = len(columns[0]) if columns else 0
    lib = get_lib()
    out_hi = np.empty(n, np.uint64)
    out_lo = np.empty(n, np.uint64)
    if lib is None or n == 0:
        return _py_hash_rows(columns, seed)
    kinds = []
    values = []
    offsets = []
    keepalive = []
    for col in columns:
        if isinstance(col, np.ndarray) and col.dtype == np.int64:
            kinds.append(0)
            c = np.ascontiguousarray(col)
            keepalive.append(c)
            values.append(c.ctypes.data_as(ctypes.c_void_p))
            offsets.append(None)
        elif isinstance(col, np.ndarray) and col.dtype == np.float64:
            kinds.append(1)
            c = np.ascontiguousarray(col)
            keepalive.append(c)
            values.append(c.ctypes.data_as(ctypes.c_void_p))
            offsets.append(None)
        else:
            kinds.append(2)
            bufs = [v.encode() if isinstance(v, str) else bytes(v) for v in col]
            off = np.zeros(n + 1, np.int64)
            for i, b in enumerate(bufs):
                off[i + 1] = off[i] + len(b)
            buf = b"".join(bufs)
            cbuf = ctypes.create_string_buffer(buf, len(buf) or 1)
            keepalive.extend([cbuf, off])
            values.append(ctypes.cast(cbuf, ctypes.c_void_p))
            offsets.append(off.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)))
    k = len(columns)
    kinds_arr = (ctypes.c_int32 * k)(*kinds)
    values_arr = (ctypes.c_void_p * k)(*[v.value if isinstance(v, ctypes.c_void_p) else v for v in values])
    OffPtr = ctypes.POINTER(ctypes.c_int64)
    offsets_arr = (OffPtr * k)(*[o if o is not None else OffPtr() for o in offsets])
    lib.pw_hash_rows(
        n, k, kinds_arr,
        ctypes.cast(values_arr, ctypes.POINTER(ctypes.c_void_p)),
        offsets_arr, seed,
        out_hi.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
        out_lo.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
    )
    return np.array(
        [(int(h) << 64) | int(l) for h, l in zip(out_hi, out_lo)], dtype=object
    )


def auto_row_keys_hashes(start: int, n: int):
    """(hi, lo) uint64 arrays of blake2b16(_ser("#row") + _ser(i)) for
    i in [start, start+n) — the native tier of value.auto_row_keys (None
    when the library is unavailable; the caller keeps its Python loop)."""
    lib = get_lib()
    if lib is None or n <= 0:
        return None
    hi = np.empty(n, np.uint64)
    lo = np.empty(n, np.uint64)
    lib.pw_auto_row_keys(
        start, n,
        hi.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
        lo.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
    )
    return hi, lo


def ref_scalar_rows_hashes(columns: list):
    """(hi, lo) uint64 arrays of the CANONICAL key hash (blake2b16 over
    _ser of each row's values) for typed columns: int64 ndarray, float64
    ndarray, or list[str].  None when unavailable or a column type is
    outside the supported set (caller falls back to per-row ref_scalar)."""
    lib = get_lib()
    if lib is None or not columns:
        return None
    n = len(columns[0])
    if n == 0:
        return None
    kinds, values, offsets, keepalive = [], [], [], []
    for col in columns:
        if isinstance(col, np.ndarray) and col.dtype == np.int64:
            kinds.append(0)
            c = np.ascontiguousarray(col)
            keepalive.append(c)
            values.append(c.ctypes.data_as(ctypes.c_void_p))
            offsets.append(None)
        elif isinstance(col, np.ndarray) and col.dtype == np.float64:
            kinds.append(1)
            c = np.ascontiguousarray(col)
            keepalive.append(c)
            values.append(c.ctypes.data_as(ctypes.c_void_p))
            offsets.append(None)
        elif isinstance(col, list) and all(isinstance(v, str) for v in col):
            kinds.append(2)
            bufs = [v.encode() for v in col]
            off = np.zeros(n + 1, np.int64)
            for i, b in enumerate(bufs):
                off[i + 1] = off[i] + len(b)
            raw = b"".join(bufs)
            cbuf = ctypes.create_string_buffer(raw, len(raw) or 1)
            keepalive.extend([cbuf, off])
            values.append(ctypes.cast(cbuf, ctypes.c_void_p))
            offsets.append(off.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)))
        else:
            return None
    k = len(columns)
    kinds_arr = (ctypes.c_int32 * k)(*kinds)
    values_arr = (ctypes.c_void_p * k)(
        *[v.value if isinstance(v, ctypes.c_void_p) else v for v in values]
    )
    OffPtr = ctypes.POINTER(ctypes.c_int64)
    offsets_arr = (OffPtr * k)(
        *[o if o is not None else OffPtr() for o in offsets]
    )
    hi = np.empty(n, np.uint64)
    lo = np.empty(n, np.uint64)
    lib.pw_ref_scalar_rows(
        n, k, kinds_arr,
        ctypes.cast(values_arr, ctypes.POINTER(ctypes.c_void_p)),
        offsets_arr,
        hi.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
        lo.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
    )
    return hi, lo


def _py_col_val(col, i):
    v = col[i]
    if isinstance(v, np.generic):
        return v.item()
    return v


def consolidate_hashed(key_hi: np.ndarray, key_lo: np.ndarray,
                       row_tag: np.ndarray, diffs: np.ndarray):
    """Returns (surviving first-occurrence indices, net diffs)."""
    n = len(diffs)
    lib = get_lib()
    if lib is None:
        acc: dict = {}
        for i in range(n):
            k = (int(key_hi[i]), int(key_lo[i]), int(row_tag[i]))
            if k in acc:
                acc[k][1] += int(diffs[i])
            else:
                acc[k] = [i, int(diffs[i])]
        pairs = sorted((v for v in acc.values() if v[1] != 0), key=lambda p: p[0])
        return (np.array([p[0] for p in pairs], np.int64),
                np.array([p[1] for p in pairs], np.int64))
    out_index = np.empty(n, np.int64)
    out_diff = np.empty(n, np.int64)
    m = lib.pw_consolidate(
        n,
        np.ascontiguousarray(key_hi, np.uint64).ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
        np.ascontiguousarray(key_lo, np.uint64).ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
        np.ascontiguousarray(row_tag, np.uint64).ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
        np.ascontiguousarray(diffs, np.int64).ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        out_index.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        out_diff.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
    )
    return out_index[:m].copy(), out_diff[:m].copy()
