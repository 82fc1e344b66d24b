"""ctypes bindings for the C++ tooling hot paths (native/hbnlp_native.cc).

Lazily builds the shared library with ``make -C native`` on first use (the
reference ships equivalent compile_*.sh scripts for its Cython components)
and falls back to the pure-Python implementations when no toolchain is
available, logging once which of the two is in use.  The binary's file name
carries a hash of its source, so a library built from another
``hbnlp_native.cc`` is never loaded in its place.
"""
from __future__ import annotations

import ctypes
import glob
import hashlib
import logging
import os
import subprocess
import typing

import numpy as np

from ..sync import make_lock

LOG = logging.getLogger("homebrewnlp_tpu.native")

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
_NATIVE_DIR = os.path.join(_REPO, "native")
_SOURCE = os.path.join(_NATIVE_DIR, "hbnlp_native.cc")
_lock = make_lock("native._lock")
_lib: typing.Optional[ctypes.CDLL] = None
_build_failed = False


def lib_path() -> str:
    """``native/libhbnlp_native.<sha256(source)[:12]>.so``: the binary is
    tied to the source it was built from (the ``.so`` is git-ignored, so a
    checkout can hold one built from an older ``.cc``)."""
    with open(_SOURCE, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:12]
    return os.path.join(_NATIVE_DIR, f"libhbnlp_native.{digest}.so")


def _load() -> typing.Optional[ctypes.CDLL]:
    global _lib, _build_failed
    with _lock:
        if _lib is not None or _build_failed:
            return _lib
        path = lib_path()
        if not os.path.exists(path):
            # build to a process-unique name then atomically rename, so
            # concurrent workers (tools/text2tfrecord.py pool) never load a
            # partially-written .so
            tmp = f"{path}.{os.getpid()}"
            try:
                subprocess.run(
                    ["make", "-C", _NATIVE_DIR,
                     f"TARGET={os.path.basename(tmp)}"],
                    check=True, capture_output=True)
                os.replace(tmp, path)
            except (OSError, subprocess.CalledProcessError) as e:
                _build_failed = True
                stderr = (getattr(e, "stderr", None) or b"").decode(
                    errors="replace")
                LOG.warning("native library build failed (%s) %s; using the "
                            "pure-Python implementations", e, stderr[-300:])
                return None
            for stale in glob.glob(os.path.join(_NATIVE_DIR,
                                                "libhbnlp_native*.so")):
                if stale != path:  # built from a source that is gone
                    os.remove(stale)
        try:
            lib = ctypes.CDLL(path)
        except OSError as e:
            _build_failed = True
            LOG.warning("native library %s failed to load (%s); using the "
                        "pure-Python implementations", path, e)
            return None
        LOG.info("native library in use: %s", path)
        lib.hb_crc32c.restype = ctypes.c_uint32
        lib.hb_crc32c.argtypes = [ctypes.c_char_p, ctypes.c_size_t]
        lib.hb_masked_crc.restype = ctypes.c_uint32
        lib.hb_masked_crc.argtypes = [ctypes.c_char_p, ctypes.c_size_t]
        lib.hb_write_records.restype = ctypes.c_int
        lib.hb_write_records.argtypes = [
            ctypes.c_char_p, ctypes.c_char_p,
            ctypes.POINTER(ctypes.c_uint64), ctypes.c_uint64, ctypes.c_int]
        lib.hb_clean_text.restype = ctypes.c_size_t
        lib.hb_clean_text.argtypes = [ctypes.c_char_p, ctypes.c_size_t,
                                      ctypes.c_char_p]
        lib.hb_bpe_train_words.restype = ctypes.c_int
        lib.hb_bpe_train_words.argtypes = [
            np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS"),
            np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS"),
            np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS"),
            ctypes.c_int64, ctypes.c_int32, ctypes.c_int32,
            np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS")]
        lib.hb_bpe_encode.restype = ctypes.c_int64
        lib.hb_bpe_encode.argtypes = [
            np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS"),
            ctypes.c_int64,
            np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS"),
            ctypes.c_int32, ctypes.c_int32]
        _lib = lib
        return _lib


def available() -> bool:
    return _load() is not None


# -- crc ---------------------------------------------------------------------

def crc32c(data: bytes) -> int:
    lib = _load()
    if lib is None:
        from ..data.tfrecord import crc32c as py
        return py(data)
    return int(lib.hb_crc32c(data, len(data)))


def masked_crc(data: bytes) -> int:
    lib = _load()
    if lib is None:
        from ..data.tfrecord import masked_crc as py
        return py(data)
    return int(lib.hb_masked_crc(data, len(data)))


# -- tfrecord ----------------------------------------------------------------

def write_records(path: str, payloads: typing.Sequence[bytes],
                  append: bool = False) -> None:
    """Write framed TFRecords via the native path (falls back to the Python
    RecordWriter)."""
    lib = _load()
    if lib is None:
        from ..data.tfrecord import RecordWriter
        with RecordWriter(path, append=append) as w:
            for p in payloads:
                w.write(p)
        return
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    blob = b"".join(payloads)
    lengths = (ctypes.c_uint64 * len(payloads))(*[len(p) for p in payloads])
    rc = lib.hb_write_records(path.encode(), blob, lengths, len(payloads),
                              int(append))
    if rc != 0:
        raise IOError(f"hb_write_records({path}) failed: {rc}")


# -- text cleaning -----------------------------------------------------------

def clean_text(data: bytes) -> bytes:
    lib = _load()
    if lib is None:
        return _clean_text_py(data)
    out = ctypes.create_string_buffer(len(data))
    n = lib.hb_clean_text(data, len(data), out)
    return out.raw[:n]


def _clean_text_py(data: bytes) -> bytes:
    """Byte-exact port of hb_clean_text (same state machine, so shards built
    without the toolchain are identical to native-built ones)."""
    out = bytearray()
    newlines = 0
    n = len(data)
    i = 0
    while i < n:
        c = data[i]
        if c == 0x0D:  # \r
            if i + 1 < n and data[i + 1] == 0x0A:
                i += 1
                continue
            c = 0x0A
        if c == 0x0A:
            newlines += 1
            if newlines > 2:
                i += 1
                continue
        else:
            newlines = 0
            if c < 0x20 and c != 0x09:
                i += 1
                continue
        out.append(c)
        i += 1
    return bytes(out)


# -- BPE ---------------------------------------------------------------------

def _stream_to_words(corpus: np.ndarray) -> typing.Dict[bytes, int]:
    """int32 stream with -1 boundaries -> {word token-bytes: count}."""
    corpus = np.ascontiguousarray(corpus, np.int32)
    counts: typing.Dict[bytes, int] = {}
    for seg in np.split(corpus, np.nonzero(corpus < 0)[0]):
        seg = seg[seg >= 0]
        if len(seg):
            key = seg.tobytes()
            counts[key] = counts.get(key, 0) + 1
    return counts


def bpe_train_words(word_counts: typing.Dict[bytes, int], n_merges: int,
                    first_new_id: int = 256) -> np.ndarray:
    """Greedy BPE merges over a word-frequency table ({int32-token-bytes:
    count}, the HF-BpeTrainer-style structure).  Returns [n_done, 2]
    (left, right) pairs; merge i creates id first_new_id + i."""
    lib = _load()
    if lib is None:
        return _bpe_train_py(word_counts, n_merges, first_new_id)
    words = [np.frombuffer(k, np.int32) for k in word_counts]
    flat = (np.concatenate(words) if words else np.zeros(0, np.int32))
    flat = np.ascontiguousarray(flat, np.int32)
    offsets = np.zeros(len(words) + 1, np.int64)
    np.cumsum([len(w) for w in words], out=offsets[1:])
    counts = np.asarray(list(word_counts.values()), np.int64)
    out = np.zeros((max(n_merges, 1), 2), np.int32)
    done = lib.hb_bpe_train_words(flat, offsets, counts, len(words),
                                  n_merges, first_new_id, out.reshape(-1))
    return out[:done]


def bpe_train(corpus: np.ndarray, n_merges: int, first_new_id: int = 256
              ) -> np.ndarray:
    """Greedy BPE merges over an int32 token stream (-1 = boundary);
    convenience wrapper deduplicating into the word-frequency form."""
    return bpe_train_words(_stream_to_words(corpus), n_merges, first_new_id)


def bpe_encode(tokens: np.ndarray, pairs: np.ndarray,
               first_new_id: int = 256) -> np.ndarray:
    lib = _load()
    tokens = np.ascontiguousarray(tokens, np.int32).copy()
    pairs = np.ascontiguousarray(pairs, np.int32)
    if lib is None:
        return _bpe_encode_py(tokens, pairs, first_new_id)
    n = lib.hb_bpe_encode(tokens, len(tokens), pairs.reshape(-1),
                          len(pairs), first_new_id)
    return tokens[:n]


def _bpe_train_py(word_counts: typing.Dict[bytes, int], n_merges: int,
                  first_new_id: int) -> np.ndarray:
    """Word-frequency BPE, same tie-break as the native version (largest
    count, then smallest (left<<32)|right key)."""
    words = [list(np.frombuffer(k, np.int32)) for k in word_counts]
    wcounts = list(word_counts.values())
    merges = []
    for m in range(n_merges):
        counts: typing.Dict[tuple, int] = {}
        for word, c in zip(words, wcounts):
            for a, b in zip(word, word[1:]):
                counts[(int(a), int(b))] = counts.get((int(a), int(b)), 0) + c
        if not counts:
            break
        (left, right), count = min(counts.items(),
                                   key=lambda kv: (-kv[1], kv[0]))
        if count < 2:
            break
        new_id = first_new_id + m
        merges.append((left, right))
        for word in words:
            o, i = [], 0
            while i < len(word):
                if i + 1 < len(word) and word[i] == left and word[i + 1] == right:
                    o.append(new_id)
                    i += 2
                else:
                    o.append(word[i])
                    i += 1
            word[:] = o
    return np.asarray(merges, np.int32).reshape(-1, 2)


def _bpe_encode_py(tokens: np.ndarray, pairs: np.ndarray, first_new_id: int
                   ) -> np.ndarray:
    """Heap-driven greedy BPE (merge the globally lowest-(rank, pos)
    occurrence each step) — the same order the native encoder applies,
    O(n log n)."""
    import heapq
    rank = {(int(l), int(r)): i for i, (l, r) in enumerate(pairs)}
    n = len(tokens)
    buf = [int(t) for t in tokens]
    nxt = list(range(1, n + 1))
    prv = list(range(-1, n - 1))
    # negative INPUT tokens (word-boundary sentinels) are preserved and
    # never pair; consumption is tracked separately (same contract as the
    # native encoder)
    dead = [False] * n
    none = len(pairs)
    heap = [(rank[(a, b)], i)
            for i, (a, b) in enumerate(zip(buf, buf[1:]))
            if (a, b) in rank]
    heapq.heapify(heap)
    while heap:
        r, i = heapq.heappop(heap)
        if dead[i]:
            continue
        j = nxt[i]
        if j >= n or dead[j] or rank.get((buf[i], buf[j]), none) != r:
            continue  # stale entry: the pair at i changed since the push
        buf[i] = first_new_id + r
        dead[j] = True
        nxt[i] = nxt[j]
        if nxt[j] < n:
            prv[nxt[j]] = i
        if prv[i] >= 0:
            pr = rank.get((buf[prv[i]], buf[i]), none)
            if pr < none:
                heapq.heappush(heap, (pr, prv[i]))
        if nxt[i] < n:
            nr = rank.get((buf[i], buf[nxt[i]]), none)
            if nr < none:
                heapq.heappush(heap, (nr, i))
    return np.asarray([t for i, t in enumerate(buf) if not dead[i]],
                      np.int32)
