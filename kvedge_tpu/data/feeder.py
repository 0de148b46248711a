"""Token-batch feeder: the training input pipeline.

The training driver (models/training.py) consumes an iterator of
``[batch, seq+1]`` int32 arrays. This module provides that iterator from
a binary corpus file on the state volume, backed by the **native
prefetching feeder** (``native/kvedge-feed.cc``: mmap + worker thread +
bounded ring buffer, so host IO and slicing overlap the device step
instead of serializing with it), with a pure-Python fallback of
identical semantics for environments without a C++ toolchain.

The reference has no data path at all (its payload is the external IoT
Edge daemon, SURVEY.md §0); this is payload-side runtime IO, native
where it matters, like the rest of the runtime around the JAX compute
path.

Corpus format (``write_corpus``): magic ``KVFEED01``, uint64 little-
endian token count, int32 tokens. Batch order is deterministic — batch
``b`` row ``r`` covers tokens ``[(b*batch + r) * seq, ... + seq + 1)``
wrapping modulo the corpus — so a training run resumed at step ``k``
(``start_batch=k``) sees exactly the batches it would have seen without
the restart: the feeder's half of the checkpoint/resume contract.

Multi-host sharding: a logical batch may span ``global_batch`` rows of
which this feeder produces ``batch`` rows starting at global row
``shard_offset`` (host p of P passes ``batch=global//P,
shard_offset=p*global//P``). ``start_batch`` stays a GLOBAL batch index,
so every host resumes with the same arithmetic, and concatenating the P
hosts' outputs row-wise reconstructs the single-host batch exactly —
pinned by tests/test_feeder.py.
"""

from __future__ import annotations

import ctypes
import os
import pathlib
import struct
import subprocess
import threading
import warnings

import numpy as np

MAGIC = b"KVFEED01"
_HEADER = struct.Struct("<8sQ")

_NATIVE_DIR = pathlib.Path(__file__).resolve().parent.parent.parent / "native"
_LIB_PATH = _NATIVE_DIR / "build" / "libkvedge-feed.so"

_lib = None
_lib_lock = threading.Lock()


def write_corpus(path: str | os.PathLike, tokens) -> None:
    """Write an int32 token corpus in the feeder's format."""
    arr = np.asarray(tokens, dtype=np.int32)
    if arr.ndim != 1:
        raise ValueError("corpus tokens must be a 1-D sequence")
    with open(path, "wb") as fh:
        fh.write(_HEADER.pack(MAGIC, arr.size))
        fh.write(arr.tobytes())


def read_corpus_header(path: str | os.PathLike) -> int:
    """Validate the header; return the token count."""
    with open(path, "rb") as fh:
        raw = fh.read(_HEADER.size)
    if len(raw) < _HEADER.size:
        raise ValueError("corpus file too small for header")
    magic, n_tokens = _HEADER.unpack(raw)
    if magic != MAGIC:
        raise ValueError(f"bad corpus magic {magic!r} (expected {MAGIC!r})")
    return n_tokens


def _load_native():
    """Build (if needed) and load the native library; None if unavailable."""
    global _lib
    with _lib_lock:
        if _lib is not None:
            return _lib or None
        try:
            # Run `make` even when the .so already exists: the build is
            # dependency-checked (a no-op when current), and skipping it
            # would load a STALE library after an in-place source update —
            # dlopen caches by path, so a missing symbol discovered at
            # bind time is too late to rebuild. A library `make` could
            # not rebuild is never loaded: native/build/ is git-ignored,
            # so a leftover .so says nothing about the committed sources.
            # locklint: allow[io-under-lock] one-time lazy init — the module lock exists precisely to serialize the native build+dlopen; waiters need the finished library anyway, and no request-path lock is held here
            subprocess.run(
                ["make", "-C", str(_NATIVE_DIR)],
                check=True, capture_output=True,
            )
            lib = ctypes.CDLL(str(_LIB_PATH))
            # Symbol binding stays inside the try: a prebuilt library from
            # an older source revision lacks newer symbols, and that must
            # surface as the loud Python fallback (AttributeError), not an
            # uncaught crash in open_feeder.
            lib.kvf_open_sharded.restype = ctypes.c_void_p
            lib.kvf_open_sharded.argtypes = [
                ctypes.c_char_p, ctypes.c_int, ctypes.c_int, ctypes.c_int,
                ctypes.c_ulonglong, ctypes.c_int, ctypes.c_int,
            ]
            lib.kvf_next.restype = ctypes.c_int
            lib.kvf_next.argtypes = [ctypes.c_void_p, ctypes.c_void_p]
            lib.kvf_tokens.restype = ctypes.c_ulonglong
            lib.kvf_tokens.argtypes = [ctypes.c_void_p]
            lib.kvf_close.argtypes = [ctypes.c_void_p]
            lib.kvf_last_error.restype = ctypes.c_char_p
        except (OSError, subprocess.SubprocessError, AttributeError) as e:
            # Loud fallback: a silently-degraded input pipeline is the
            # exact stall the native feeder exists to prevent, so say
            # why (a missing toolchain reads very differently from a
            # broken build).
            detail = ""
            if isinstance(e, subprocess.CalledProcessError) and e.stderr:
                detail = ": " + e.stderr.decode(errors="replace").strip()
            warnings.warn(
                "native feeder unavailable, using the pure-Python "
                f"fallback ({type(e).__name__}{detail})",
                RuntimeWarning, stacklevel=3,
            )
            _lib = False  # cached negative: no toolchain / broken build
            return None
        _lib = lib
        return lib


class TokenFeeder:
    """Iterator of [batch, seq+1] int32 batches via the native feeder."""

    def __init__(self, path: str | os.PathLike, batch: int, seq: int,
                 depth: int = 4, start_batch: int = 0,
                 global_batch: int = 0, shard_offset: int = 0):
        lib = _load_native()
        if lib is None:
            raise RuntimeError(
                "native feeder library unavailable (no C++ toolchain?); "
                "use PyTokenFeeder or open_feeder() for the fallback"
            )
        self._lib = lib
        self._batch, self._seq = batch, seq
        self._handle = lib.kvf_open_sharded(
            str(path).encode(), batch, seq, depth, start_batch,
            global_batch or batch, shard_offset,
        )
        if not self._handle:
            raise ValueError(lib.kvf_last_error().decode())
        self.n_tokens = int(lib.kvf_tokens(self._handle))

    def __iter__(self):
        return self

    def __next__(self) -> np.ndarray:
        if self._handle is None:
            raise StopIteration
        out = np.empty((self._batch, self._seq + 1), np.int32)
        rc = self._lib.kvf_next(
            self._handle, out.ctypes.data_as(ctypes.c_void_p)
        )
        if rc != 0:
            raise StopIteration
        return out

    def close(self) -> None:
        if self._handle is not None:
            self._lib.kvf_close(self._handle)
            self._handle = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


class PyTokenFeeder:
    """Pure-Python feeder with byte-identical output order.

    The no-toolchain fallback AND the parity oracle for the native
    implementation's tests.
    """

    def __init__(self, path: str | os.PathLike, batch: int, seq: int,
                 depth: int = 4, start_batch: int = 0,
                 global_batch: int = 0, shard_offset: int = 0):
        del depth  # no prefetching; signature parity with TokenFeeder
        global_batch = global_batch or batch
        if not (0 <= shard_offset and shard_offset + batch <= global_batch):
            # Same open-time rejection as the native feeder.
            raise ValueError(
                "shard must satisfy 0 <= shard_offset and "
                "shard_offset + batch <= global_batch"
            )
        self.n_tokens = read_corpus_header(path)
        if self.n_tokens < seq + 1:
            raise ValueError("corpus smaller than one sequence")
        self._tokens = np.fromfile(
            path, dtype=np.int32, offset=_HEADER.size
        )[: self.n_tokens]
        if self._tokens.size < self.n_tokens:
            # Same open-time rejection as the native feeder — a truncated
            # body must not surface as an IndexError mid-training.
            raise ValueError(
                "corpus header claims more tokens than the file holds"
            )
        self._batch, self._seq = batch, seq
        self._global_batch, self._shard_offset = global_batch, shard_offset
        self._index = start_batch

    def __iter__(self):
        return self

    def __next__(self) -> np.ndarray:
        out = np.empty((self._batch, self._seq + 1), np.int32)
        for r in range(self._batch):
            start = (
                (self._index * self._global_batch + self._shard_offset + r)
                * self._seq % self.n_tokens
            )
            idx = (start + np.arange(self._seq + 1)) % self.n_tokens
            out[r] = self._tokens[idx]
        self._index += 1
        return out

    def close(self) -> None:
        pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


def open_feeder(path: str | os.PathLike, batch: int, seq: int,
                depth: int = 4, start_batch: int = 0,
                global_batch: int = 0, shard_offset: int = 0):
    """The native feeder when buildable, the Python fallback otherwise."""
    cls = TokenFeeder if _load_native() is not None else PyTokenFeeder
    return cls(path, batch, seq, depth, start_batch,
               global_batch=global_batch, shard_offset=shard_offset)
