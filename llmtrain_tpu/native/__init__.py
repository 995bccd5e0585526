"""Native (C) runtime components, built on demand with graceful fallback.

``fastbpe`` accelerates the BPE tokenizer's cold-word merge loop
(data/bpe.py) — the dominant cost when tokenizing high-entropy corpora
(source code) where the Python per-word memo rarely hits. The shared
object is compiled once per source hash with the host C compiler, from
the tracked source into the checkout's git-ignored ``.cache/native/``,
and loaded via ctypes; any failure
(no compiler, sandboxed filesystem) silently falls back to the pure
Python implementation, so nothing here is load-bearing for correctness.

Set ``LLMTRAIN_NO_NATIVE=1`` to force the Python paths (the equivalence
tests use it to compare both).
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import shutil
import subprocess
from pathlib import Path

_SRC = Path(__file__).with_name("fastbpe.c")
_lib: ctypes.CDLL | None = None
_lib_tried = False


def _cache_dir() -> Path:
    # Beside the compile cache (distributed.DEFAULT_COMPILATION_CACHE_DIR):
    # nothing of this repo writes outside its checkout.
    return _SRC.resolve().parents[2] / ".cache" / "native"


def _compiler() -> str | None:
    for cc in (os.environ.get("CC"), "cc", "gcc", "clang"):
        if cc and shutil.which(cc):
            return cc
    return None


def _build() -> Path | None:
    # Everything inside the try: the module contract is that ANY failure
    # (missing source in a stripped install, read-only cache dir, broken
    # compiler) means "no native encoder", never an exception.
    tmp: Path | None = None
    try:
        src = _SRC.read_bytes()
        tag = hashlib.sha256(src).hexdigest()[:16]
        out = _cache_dir() / f"fastbpe-{tag}.so"
        if out.exists():
            return out
        cc = _compiler()
        if cc is None:
            return None
        out.parent.mkdir(parents=True, exist_ok=True)
        # Per-process tmp: concurrent builders (pytest-xdist, simultaneous
        # jobs on a fresh host) must not interleave writes into one file
        # and promote a corrupt .so into the content-addressed cache.
        tmp = out.with_suffix(f".so.tmp.{os.getpid()}")
        subprocess.run(
            [cc, "-O2", "-shared", "-fPIC", "-o", str(tmp), str(_SRC)],
            check=True,
            capture_output=True,
            timeout=120,
        )
        tmp.replace(out)
        return out
    except Exception:
        if tmp is not None:
            tmp.unlink(missing_ok=True)
        return None


def _load() -> ctypes.CDLL | None:
    global _lib, _lib_tried
    if _lib_tried:
        return _lib
    _lib_tried = True
    if os.environ.get("LLMTRAIN_NO_NATIVE") == "1":
        return None
    path = _build()
    if path is None:
        return None
    try:
        lib = ctypes.CDLL(str(path))
        lib.fastbpe_new.restype = ctypes.c_void_p
        lib.fastbpe_new.argtypes = [ctypes.POINTER(ctypes.c_int32), ctypes.c_int32]
        lib.fastbpe_free.argtypes = [ctypes.c_void_p]
        lib.fastbpe_encode_word.restype = ctypes.c_int32
        lib.fastbpe_encode_word.argtypes = [
            ctypes.c_void_p,
            ctypes.POINTER(ctypes.c_uint8),
            ctypes.c_int32,
            ctypes.POINTER(ctypes.c_int32),
        ]
    except OSError:
        return None
    _lib = lib
    return _lib


class FastBpeEncoder:
    """ctypes wrapper over one vocabulary's native merge table."""

    def __init__(self, lib: ctypes.CDLL, merges: list[tuple[int, int]]) -> None:
        flat = (ctypes.c_int32 * (2 * len(merges)))()
        for i, (a, b) in enumerate(merges):
            flat[2 * i] = a
            flat[2 * i + 1] = b
        self._lib = lib
        self._ctx = lib.fastbpe_new(flat, len(merges))
        if not self._ctx:
            raise MemoryError("fastbpe_new failed")

    def encode_word(self, word: str) -> list[int]:
        raw = word.encode("utf-8")
        n = len(raw)
        if n == 0:
            return []
        buf_in = (ctypes.c_uint8 * n).from_buffer_copy(raw)
        buf_out = (ctypes.c_int32 * n)()
        count = self._lib.fastbpe_encode_word(self._ctx, buf_in, n, buf_out)
        return list(buf_out[:count])

    def __del__(self) -> None:  # pragma: no cover - interpreter teardown
        lib = getattr(self, "_lib", None)
        ctx = getattr(self, "_ctx", None)
        if lib is not None and ctx:
            lib.fastbpe_free(ctx)


def fastbpe_encoder(merges: list[tuple[int, int]]) -> FastBpeEncoder | None:
    """A native encoder for this merge list, or None (fallback to Python)."""
    lib = _load()
    if lib is None:
        return None
    try:
        return FastBpeEncoder(lib, merges)
    except MemoryError:
        return None


__all__ = ["fastbpe_encoder", "FastBpeEncoder"]
