"""Build-on-demand loader for the native (C) helpers.

The runtime around the TPU compute path is native where it matters
(checksums, codecs, IO) — mirroring the reference's C runtime — but built
lazily with the system toolchain so the package stays pip-less.
"""
from __future__ import annotations

import ctypes
import glob
import hashlib
import os
import subprocess
import threading

import numpy as np

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_SRC_DIR = os.path.join(_ROOT, "native")
_SOURCES = ["crc32c.c", "gossip_native.c"]
_lock = threading.Lock()
_lib = None


class NativeBuildError(RuntimeError):
    """The C helpers did not compile; carries the compiler's stderr."""


def _build(src_dir: str = _SRC_DIR) -> str:
    """Compile the helpers unless a library built from exactly these
    sources is already there.  The library's name records the hash of
    its sources (``_lightning_native-<hash>.so``, git-ignored), so a
    fresh checkout builds on first use and an edited ``.c`` rebuilds —
    mtimes say nothing after a checkout."""
    srcs = [os.path.join(src_dir, s) for s in _SOURCES]
    h = hashlib.sha256()
    for s in srcs:
        with open(s, "rb") as f:
            h.update(f.read())
    lib_path = os.path.join(src_dir,
                            f"_lightning_native-{h.hexdigest()[:16]}.so")
    if os.path.exists(lib_path):
        return lib_path
    # build beside the target and rename: concurrent first users
    # (xdist workers, subdaemons) never load a half-written file
    tmp = f"{lib_path}.{os.getpid()}.tmp"
    cmd = ["cc", "-O3", "-shared", "-fPIC", "-o", tmp, *srcs]
    try:
        proc = subprocess.run(cmd, capture_output=True, text=True)
    except OSError as e:
        raise NativeBuildError(f"cannot run {cmd[0]!r}: {e}") from e
    if proc.returncode != 0:
        if os.path.exists(tmp):
            os.unlink(tmp)
        raise NativeBuildError(
            f"{' '.join(cmd)} exited {proc.returncode}:\n{proc.stderr}")
    os.replace(tmp, lib_path)
    for old in glob.glob(os.path.join(src_dir, "_lightning_native*.so")):
        if old != lib_path:
            try:
                os.unlink(old)
            except OSError:
                pass
    return lib_path


def get_lib() -> ctypes.CDLL:
    global _lib
    with _lock:
        if _lib is None:
            lib = ctypes.CDLL(_build())
            lib.crc32c.restype = ctypes.c_uint32
            lib.crc32c.argtypes = [ctypes.c_uint32, ctypes.c_char_p, ctypes.c_size_t]
            lib.crc32c_batch.restype = None
            lib.crc32c_batch.argtypes = [
                ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
                ctypes.c_void_p, ctypes.c_void_p, ctypes.c_size_t,
            ]
            lib.gossip_store_scan.restype = ctypes.c_int64
            lib.gossip_store_scan.argtypes = [
                ctypes.c_void_p, ctypes.c_uint64, ctypes.c_uint64,
                ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
                ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
            ]
            lib.sha256_pack.restype = ctypes.c_int64
            lib.sha256_pack.argtypes = [
                ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
                ctypes.c_size_t, ctypes.c_void_p, ctypes.c_uint64,
                ctypes.c_void_p,
            ]
            lib.gather_fields.restype = None
            lib.gather_fields.argtypes = [
                ctypes.c_void_p, ctypes.c_void_p, ctypes.c_size_t,
                ctypes.c_uint64, ctypes.c_uint32, ctypes.c_void_p,
            ]
            _lib = lib
    return _lib


def crc32c(seed: int, data: bytes) -> int:
    return get_lib().crc32c(seed & 0xFFFFFFFF, data, len(data))


def crc32c_batch(buf: np.ndarray, offsets: np.ndarray, lengths: np.ndarray,
                 seeds: np.ndarray) -> np.ndarray:
    """Vectorized crc32c over records inside one contiguous uint8 buffer."""
    buf = np.ascontiguousarray(buf, dtype=np.uint8)
    offsets = np.ascontiguousarray(offsets, dtype=np.uint64)
    lengths = np.ascontiguousarray(lengths, dtype=np.uint32)
    seeds = np.ascontiguousarray(seeds, dtype=np.uint32)
    out = np.empty(len(offsets), dtype=np.uint32)
    get_lib().crc32c_batch(
        buf.ctypes.data, offsets.ctypes.data, lengths.ctypes.data,
        seeds.ctypes.data, out.ctypes.data, len(offsets),
    )
    return out


def gossip_store_scan(buf: np.ndarray, start_off: int = 1):
    """Scan store records. Returns dict of numpy arrays (offsets point at
    each record's message body; lengths exclude the 12-byte header)."""
    buf = np.ascontiguousarray(buf, dtype=np.uint8)
    cap = max(1, (len(buf) - start_off) // 12 + 1)
    offsets = np.empty(cap, np.uint64)
    lengths = np.empty(cap, np.uint32)
    flags = np.empty(cap, np.uint16)
    timestamps = np.empty(cap, np.uint32)
    crcs = np.empty(cap, np.uint32)
    types = np.empty(cap, np.uint16)
    n = get_lib().gossip_store_scan(
        buf.ctypes.data, len(buf), start_off,
        offsets.ctypes.data, lengths.ctypes.data, flags.ctypes.data,
        timestamps.ctypes.data, crcs.ctypes.data, types.ctypes.data,
    )
    if n < 0:
        raise ValueError("truncated gossip store")
    sl = slice(0, n)
    return {
        "offsets": offsets[sl], "lengths": lengths[sl], "flags": flags[sl],
        "timestamps": timestamps[sl], "crcs": crcs[sl], "types": types[sl],
    }


def sha256_pack(buf: np.ndarray, offsets: np.ndarray, lengths: np.ndarray,
                max_blocks: int):
    """Pack signed regions into pre-padded SHA256 rows.
    Returns (rows (n, max_blocks*64) uint8, n_blocks (n,) uint32).
    Oversized regions (legal per BOLT#7, up to 64 KiB) get n_blocks == 0
    and a zeroed row — callers route those to a host-side hash."""
    buf = np.ascontiguousarray(buf, dtype=np.uint8)
    offsets = np.ascontiguousarray(offsets, dtype=np.uint64)
    lengths = np.ascontiguousarray(lengths, dtype=np.uint32)
    n = len(offsets)
    row_bytes = max_blocks * 64
    out = np.empty((n, row_bytes), np.uint8)
    n_blocks = np.empty(n, np.uint32)
    get_lib().sha256_pack(
        buf.ctypes.data, offsets.ctypes.data, lengths.ctypes.data, n,
        out.ctypes.data, row_bytes, n_blocks.ctypes.data,
    )
    return out, n_blocks


def gather_fields(buf: np.ndarray, offsets: np.ndarray, field_off: int,
                  field_len: int) -> np.ndarray:
    """out[i] = buf[offsets[i]+field_off : +field_len] as (n, field_len)."""
    buf = np.ascontiguousarray(buf, dtype=np.uint8)
    offsets = np.ascontiguousarray(offsets, dtype=np.uint64)
    out = np.empty((len(offsets), field_len), np.uint8)
    get_lib().gather_fields(
        buf.ctypes.data, offsets.ctypes.data, len(offsets),
        field_off, field_len, out.ctypes.data,
    )
    return out
