"""ctypes binding for the C++ data loader (native/dataloader.cpp).

The .so is built on demand with the system g++ (no pip deps, per the
environment contract) into a path keyed by the source's content hash, so a
binary on disk is always the one this source builds — never one that a
copy left behind. On a host without a compiler the pure-numpy loader
serves the same interface; it says so in the log, and every loader names
itself (``loader_name``) for the worker summary.

Why native: a training step is sub-second, so batch assembly must never
appear on the critical path. The C++ loader memory-maps the token file and
keeps a ring of pre-assembled batches filled by background threads; Python
only wraps the filled buffer in a numpy array.
"""

from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import subprocess
import threading
from pathlib import Path
from typing import Iterator, Optional

import numpy as np

log = logging.getLogger("kubedl_tpu.data.native")

_SRC = Path(__file__).resolve().parents[2] / "native" / "dataloader.cpp"
_lib_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_lib_tried = False


def _build_lib() -> Optional[Path]:
    digest = hashlib.sha256(_SRC.read_bytes()).hexdigest()[:16]
    out = _SRC.parent / f"libkdl_data-{digest}.so"
    if out.exists():
        return out
    tmp = out.with_suffix(f".{os.getpid()}.tmp")
    try:
        subprocess.run(
            ["g++", "-O3", "-shared", "-fPIC", "-pthread",
             "-o", str(tmp), str(_SRC)],
            check=True, capture_output=True, timeout=120,
        )
        os.replace(tmp, out)  # atomic: concurrent builders never load a torn file
        return out
    except (OSError, subprocess.SubprocessError) as e:
        log.warning(
            "native data loader not built (%s); the numpy loader serves", e
        )
        return None


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _lib_tried
    with _lib_lock:
        if _lib is not None or _lib_tried:
            return _lib
        _lib_tried = True
        if not _SRC.exists():
            return None
        path = _build_lib()
        if path is None:
            return None
        lib = ctypes.CDLL(str(path))
        lib.kdl_loader_open.restype = ctypes.c_void_p
        lib.kdl_loader_open.argtypes = [
            ctypes.c_char_p, ctypes.c_int, ctypes.c_int,
            ctypes.c_uint64, ctypes.c_int, ctypes.c_int,
        ]
        lib.kdl_loader_next.restype = ctypes.c_int
        lib.kdl_loader_next.argtypes = [ctypes.c_void_p, ctypes.POINTER(ctypes.c_int32)]
        lib.kdl_loader_tokens.restype = ctypes.c_long
        lib.kdl_loader_tokens.argtypes = [ctypes.c_void_p]
        lib.kdl_loader_close.restype = None
        lib.kdl_loader_close.argtypes = [ctypes.c_void_p]
        _lib = lib
        return _lib


def native_available() -> bool:
    return _load() is not None


class NativeTokenLoader:
    """Batches from a binary token file via the C++ prefetch ring."""

    loader_name = "native"

    def __init__(self, path: str, batch: int, seq: int, seed: int = 0,
                 prefetch: int = 4, token_bytes: int = 4) -> None:
        lib = _load()
        if lib is None:
            raise RuntimeError("native data loader not available")
        self._lib = lib
        self.batch, self.seq = batch, seq
        self._h = lib.kdl_loader_open(
            os.fsencode(path), batch, seq, seed, prefetch, token_bytes
        )
        if not self._h:
            raise FileNotFoundError(
                f"cannot open token file {path!r} (need >= {seq} tokens)"
            )

    @property
    def n_tokens(self) -> int:
        return int(self._lib.kdl_loader_tokens(self._h))

    def next(self) -> np.ndarray:
        out = np.empty((self.batch, self.seq), np.int32)
        rc = self._lib.kdl_loader_next(
            self._h, out.ctypes.data_as(ctypes.POINTER(ctypes.c_int32))
        )
        if rc != 0:
            raise RuntimeError("native loader stopped")
        return out

    def close(self) -> None:
        if self._h:
            self._lib.kdl_loader_close(self._h)
            self._h = None

    def __del__(self):  # best-effort; close() is the real API
        try:
            self.close()
        except Exception:
            pass

    def __iter__(self) -> Iterator[np.ndarray]:
        while True:
            yield self.next()


class _NumpyTokenLoader:
    """Same sampling contract, pure numpy (no compiler needed)."""

    loader_name = "numpy"

    def __init__(self, path: str, batch: int, seq: int, seed: int = 0,
                 token_bytes: int = 4) -> None:
        dtype = np.uint16 if token_bytes == 2 else np.int32
        self._tokens = np.memmap(path, dtype=dtype, mode="r")
        if len(self._tokens) < seq:
            raise FileNotFoundError(f"token file {path!r} too small")
        self.batch, self.seq = batch, seq
        self._rng = np.random.default_rng(seed or 0x9E3779B9)

    @property
    def n_tokens(self) -> int:
        return len(self._tokens)

    def next(self) -> np.ndarray:
        span = len(self._tokens) - self.seq
        starts = (
            self._rng.integers(0, span, self.batch) if span > 0
            else np.zeros(self.batch, np.int64)
        )
        return np.stack(
            [self._tokens[s:s + self.seq] for s in starts]
        ).astype(np.int32)

    def close(self) -> None:
        pass

    def __iter__(self) -> Iterator[np.ndarray]:
        while True:
            yield self.next()


def TokenFileDataset(path: str, batch: int, seq: int, seed: int = 0,
                     prefetch: int = 4, token_bytes: int = 4):
    """Dataset over a binary token file: the native prefetch loader when a
    compiler is available, numpy otherwise (logged by ``_build_lib``) —
    identical interface, ``loader_name`` says which."""
    if native_available():
        return NativeTokenLoader(path, batch, seq, seed, prefetch, token_bytes)
    return _NumpyTokenLoader(path, batch, seq, seed, token_bytes)
