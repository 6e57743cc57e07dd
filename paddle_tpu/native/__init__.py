"""Native runtime bindings — the pybind/core_avx analog over ctypes.

Reference: paddle/fluid/pybind/pybind.cc:353 exposes the C++ runtime to
Python; here the C++ data-feed pipeline (native/src/data_feed.cc, the
data_feed.cc + channel.h analog) is compiled on first use — keyed on a hash
of its sources — with the baked-in g++ toolchain and bound through ctypes (no pybind11 in the image; the C ABI
is the `framework/c/c_api.cc` pattern).  A pure-Python fallback keeps the
package importable where no compiler exists.
"""
from __future__ import annotations

import ctypes
import glob
import hashlib
import os
import subprocess
import threading
from typing import List, Optional, Sequence, Tuple

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, "src", "data_feed.cc")
_SRCS = [_SRC, os.path.join(_HERE, "src", "memory.cc"),
         os.path.join(_HERE, "src", "pad_pack.cc")]
_DEPS = _SRCS + [os.path.join(_HERE, "src", "channel.h")]
_lib = None
_lib_lock = threading.Lock()


def _lib_path() -> str:
    """The library's name carries a hash of its sources: a binary is reused
    only for the exact sources it was built from.  (File times say nothing
    — a copy or a checkout resets them — so a stale binary could outlive
    its sources under an mtime comparison.)"""
    h = hashlib.sha256()
    for dep in _DEPS:
        with open(dep, "rb") as f:
            h.update(f.read())
    return os.path.join(_HERE, f"libptnative-{h.hexdigest()[:16]}.so")


def _build() -> Optional[str]:
    """Compile the native library unless the binary for these exact
    sources is already there.

    Compiles to a process-unique temp path and os.replace()s into place so a
    concurrent process never dlopens a half-written .so (rename is atomic on
    POSIX); binaries of other source versions are removed."""
    try:
        path = _lib_path()
        if os.path.exists(path):
            return path
        tmp = f"{path}.{os.getpid()}.tmp"
        cmd = ["g++", "-O3", "-std=c++17", "-shared", "-fPIC", "-pthread",
               "-o", tmp] + _SRCS
        try:
            subprocess.run(cmd, check=True, capture_output=True, timeout=300)
            os.replace(tmp, path)
        finally:
            if os.path.exists(tmp):
                os.remove(tmp)
        for old in glob.glob(os.path.join(_HERE, "libptnative*.so")):
            if old != path:
                os.remove(old)
        return path
    except (OSError, subprocess.SubprocessError):
        return None


def _load():
    global _lib
    with _lib_lock:
        if _lib is not None:
            return _lib
        path = _build()
        if path is None:
            return None
        try:
            lib = ctypes.CDLL(path)
        except OSError:
            # stale/foreign-arch artifact: force a rebuild, then give up
            # cleanly so make_data_feed falls back to PyDataFeed
            try:
                os.remove(path)
                path = _build()
                lib = ctypes.CDLL(path) if path else None
            except (OSError, TypeError):
                lib = None
            if lib is None:
                return None
        lib.pt_feed_create.restype = ctypes.c_void_p
        lib.pt_feed_create.argtypes = [ctypes.c_char_p, ctypes.c_int,
                                       ctypes.c_int]
        lib.pt_feed_add_file.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
        lib.pt_feed_start.argtypes = [ctypes.c_void_p]
        lib.pt_feed_load_into_memory.restype = ctypes.c_int64
        lib.pt_feed_load_into_memory.argtypes = [ctypes.c_void_p]
        lib.pt_feed_local_shuffle.argtypes = [ctypes.c_void_p,
                                              ctypes.c_uint64]
        lib.pt_feed_start_from_memory.argtypes = [ctypes.c_void_p]
        lib.pt_feed_next.restype = ctypes.c_int
        lib.pt_feed_next.argtypes = [ctypes.c_void_p]
        for fn in (lib.pt_feed_sparse_ids, lib.pt_feed_sparse_lod):
            fn.restype = ctypes.POINTER(ctypes.c_int64)
            fn.argtypes = [ctypes.c_void_p, ctypes.c_int,
                           ctypes.POINTER(ctypes.c_int64)]
        lib.pt_feed_dense.restype = ctypes.POINTER(ctypes.c_float)
        lib.pt_feed_dense.argtypes = [ctypes.c_void_p, ctypes.c_int,
                                      ctypes.POINTER(ctypes.c_int64)]
        lib.pt_feed_memory_size.restype = ctypes.c_int64
        lib.pt_feed_memory_size.argtypes = [ctypes.c_void_p]
        lib.pt_feed_destroy.argtypes = [ctypes.c_void_p]
        lib.pt_feed_global_shuffle.argtypes = [
            ctypes.POINTER(ctypes.c_void_p), ctypes.c_int, ctypes.c_uint64]
        lib.pt_feed_extract_shard.restype = ctypes.POINTER(ctypes.c_uint8)
        lib.pt_feed_extract_shard.argtypes = [
            ctypes.c_void_p, ctypes.c_int, ctypes.c_int,
            ctypes.POINTER(ctypes.c_int64)]
        lib.pt_feed_extract_shards.argtypes = [
            ctypes.c_void_p, ctypes.c_int, ctypes.c_int,
            ctypes.POINTER(ctypes.POINTER(ctypes.c_uint8)),
            ctypes.POINTER(ctypes.c_int64)]
        lib.pt_feed_free_blob.argtypes = [ctypes.POINTER(ctypes.c_uint8)]
        lib.pt_feed_ingest.restype = ctypes.c_int64
        lib.pt_feed_ingest.argtypes = [ctypes.c_void_p,
                                       ctypes.POINTER(ctypes.c_uint8),
                                       ctypes.c_int64]
        lib.pt_arena_create.restype = ctypes.c_void_p
        lib.pt_arena_create.argtypes = [ctypes.c_int64]
        lib.pt_arena_alloc.restype = ctypes.c_void_p
        lib.pt_arena_alloc.argtypes = [ctypes.c_void_p, ctypes.c_int64]
        lib.pt_arena_free.restype = ctypes.c_int
        lib.pt_arena_free.argtypes = [ctypes.c_void_p, ctypes.c_void_p]
        lib.pt_arena_stats.argtypes = [ctypes.c_void_p] + \
            [ctypes.POINTER(ctypes.c_int64)] * 3
        lib.pt_arena_destroy.argtypes = [ctypes.c_void_p]
        lib.pt_pack_padded_i64.argtypes = [
            ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_int64),
            ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
            ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_int64),
            ctypes.c_int]
        lib.pt_pack_padded_f32.argtypes = [
            ctypes.POINTER(ctypes.c_float), ctypes.POINTER(ctypes.c_int64),
            ctypes.c_int64, ctypes.c_int64, ctypes.c_float,
            ctypes.POINTER(ctypes.c_float), ctypes.POINTER(ctypes.c_int64),
            ctypes.c_int]
        _lib = lib
        return _lib


def native_available() -> bool:
    return _load() is not None


class SlotDesc:
    """One slot of the MultiSlot schema (data_feed.proto analog)."""

    def __init__(self, name: str, is_dense: bool = False, dim: int = 1):
        self.name = name
        self.is_dense = is_dense
        self.dim = dim

    def _fmt(self):
        return f"{self.name}:{'dense' if self.is_dense else 'sparse'}:{self.dim}"


class NativeDataFeed:
    """Multi-threaded MultiSlot feed over the C++ pipeline.

    Batches come back as:
      sparse slot -> (ids int64 [total], lod int64 [batch+1])   (CSR)
      dense slot  -> float32 [batch, dim]
    """

    def __init__(self, slots: Sequence[SlotDesc], batch_size: int,
                 num_threads: int = 4):
        lib = _load()
        if lib is None:
            raise RuntimeError("native library unavailable (no g++?)")
        self._lib = lib
        self.slots = list(slots)
        self.sparse_slots = [s for s in self.slots if not s.is_dense]
        self.dense_slots = [s for s in self.slots if s.is_dense]
        schema = ",".join(s._fmt() for s in self.slots).encode()
        self._h = lib.pt_feed_create(schema, batch_size, num_threads)
        if not self._h:
            raise ValueError("bad slot schema")

    def add_file(self, path: str):
        self._lib.pt_feed_add_file(self._h, str(path).encode())

    def set_filelist(self, paths: Sequence[str]):
        for p in paths:
            self.add_file(p)

    def start(self):
        self._lib.pt_feed_start(self._h)

    def load_into_memory(self) -> int:
        return int(self._lib.pt_feed_load_into_memory(self._h))

    def local_shuffle(self, seed: int = 0):
        self._lib.pt_feed_local_shuffle(self._h, seed)

    def start_from_memory(self):
        self._lib.pt_feed_start_from_memory(self._h)

    @property
    def memory_size(self) -> int:
        return int(self._lib.pt_feed_memory_size(self._h))

    def extract_shard(self, dest: int, world: int) -> bytes:
        """Remove and serialize the in-memory records content-hash-routed to
        rank `dest` of `world` (the node-local half of the cross-process
        GlobalShuffle, data_set.h:118)."""
        ln = ctypes.c_int64()
        ptr = self._lib.pt_feed_extract_shard(self._h, dest, world,
                                              ctypes.byref(ln))
        try:
            return ctypes.string_at(ptr, ln.value)
        finally:
            self._lib.pt_feed_free_blob(ptr)

    def extract_shards(self, world: int, self_rank: int) -> list:
        """Single-pass bucketing: one pool traversal yields the blob for
        every remote rank (entry self_rank is empty; those records stay)."""
        ptrs = (ctypes.POINTER(ctypes.c_uint8) * world)()
        lens = (ctypes.c_int64 * world)()
        self._lib.pt_feed_extract_shards(self._h, world, self_rank,
                                         ptrs, lens)
        out = []
        for d in range(world):
            out.append(ctypes.string_at(ptrs[d], lens[d]))
            self._lib.pt_feed_free_blob(ptrs[d])
        return out

    def ingest(self, blob: bytes) -> int:
        """Append records serialized by extract_shard (any process) to the
        in-memory pool; returns the record count."""
        if not blob:
            return 0
        buf = (ctypes.c_uint8 * len(blob)).from_buffer_copy(blob)
        n = int(self._lib.pt_feed_ingest(self._h, buf, len(blob)))
        if n < 0:
            raise ValueError("corrupt global-shuffle blob")
        return n

    def next(self):
        """Returns dict name->array(s) or None at end of pass."""
        n = self._lib.pt_feed_next(self._h)
        if n < 0:
            raise RuntimeError("next() called before start()/"
                               "start_from_memory()")
        if n == 0:
            return None
        out = {}
        ln = ctypes.c_int64()
        for i, s in enumerate(self.sparse_slots):
            ptr = self._lib.pt_feed_sparse_ids(self._h, i, ctypes.byref(ln))
            ids = np.ctypeslib.as_array(ptr, (ln.value,)).copy() \
                if ln.value else np.zeros((0,), np.int64)
            ptr = self._lib.pt_feed_sparse_lod(self._h, i, ctypes.byref(ln))
            lod = np.ctypeslib.as_array(ptr, (ln.value,)).copy()
            out[s.name] = (ids, lod)
        for i, s in enumerate(self.dense_slots):
            ptr = self._lib.pt_feed_dense(self._h, i, ctypes.byref(ln))
            arr = np.ctypeslib.as_array(ptr, (ln.value,)).copy()
            out[s.name] = arr.reshape(n, s.dim)
        return out

    def __iter__(self):
        while True:
            b = self.next()
            if b is None:
                return
            yield b

    def __del__(self):
        h, self._h = getattr(self, "_h", None), None
        lib = getattr(self, "_lib", None)
        if h and lib is not None:
            lib.pt_feed_destroy(h)


_U64 = (1 << 64) - 1


def _route_hash(sparse, dense) -> int:
    """Record→rank routing hash, bit-identical to the C++ RouteHash
    (FNV-1a over sparse ids, dense float bits for dense-only records,
    murmur3 finalizer) so native and Python-fallback processes in one
    cluster route records consistently."""
    import struct
    h = 1469598103934665603
    mixed = False
    for slot in sparse:
        for v in slot:
            h = ((h ^ (int(v) & _U64)) * 1099511628211) & _U64
            mixed = True
    if not mixed:
        for slot in dense:
            for f in slot:
                (bits,) = struct.unpack("<I", struct.pack("<f", f))
                h = ((h ^ bits) * 1099511628211) & _U64
    h ^= h >> 33
    h = (h * 0xFF51AFD7ED558CCD) & _U64
    h ^= h >> 33
    h = (h * 0xC4CEB9FE1A85EC53) & _U64
    h ^= h >> 33
    return h


class PyDataFeed:
    """Pure-Python fallback with the same surface (single-threaded)."""

    def __init__(self, slots: Sequence[SlotDesc], batch_size: int,
                 num_threads: int = 1):
        self.slots = list(slots)
        self.sparse_slots = [s for s in self.slots if not s.is_dense]
        self.dense_slots = [s for s in self.slots if s.is_dense]
        self.batch_size = batch_size
        self._files: List[str] = []
        self._pool: List[Tuple] = []
        self._iter = None

    def add_file(self, path):
        self._files.append(str(path))

    def set_filelist(self, paths):
        self._files.extend(str(p) for p in paths)

    def _parse(self, line):
        toks = line.split()
        pos = 0
        sparse, dense = [], []
        for s in self.slots:
            n = int(toks[pos]); pos += 1
            vals = toks[pos:pos + n]; pos += n
            if s.is_dense:
                v = [float(x) for x in vals][:s.dim]
                v += [0.0] * (s.dim - len(v))
                dense.append(v)
            else:
                sparse.append([int(x) for x in vals])
        return sparse, dense

    def _records(self):
        for f in self._files:
            with open(f) as fh:
                for line in fh:
                    line = line.strip()
                    if line:
                        yield self._parse(line)

    def load_into_memory(self):
        self._pool = list(self._records())
        return len(self._pool)

    def local_shuffle(self, seed=0):
        np.random.RandomState(seed).shuffle(self._pool)

    def start(self):
        self._iter = self._records()

    def start_from_memory(self):
        self._iter = iter(self._pool)

    @property
    def memory_size(self):
        return len(self._pool)

    @staticmethod
    def _serialize(records) -> bytes:
        import struct
        parts = [struct.pack("<Q", len(records))]
        for sparse, dense in records:
            parts.append(struct.pack("<I", len(sparse)))
            for slot in sparse:
                a = np.asarray(slot, "<u8")
                parts.append(struct.pack("<Q", a.size))
                parts.append(a.tobytes())
            parts.append(struct.pack("<I", len(dense)))
            for slot in dense:
                a = np.asarray(slot, "<f4")
                parts.append(struct.pack("<Q", a.size))
                parts.append(a.tobytes())
        return b"".join(parts)

    def extract_shard(self, dest: int, world: int) -> bytes:
        """Same wire format as NativeDataFeed.extract_shard (see
        data_feed.cc pt_feed_extract_shard) — the two interoperate."""
        keep, out = [], []
        for rec in self._pool:
            (out if _route_hash(rec[0], rec[1]) % world == dest
             else keep).append(rec)
        self._pool = keep
        return self._serialize(out)

    def extract_shards(self, world: int, self_rank: int) -> list:
        """Single-pass bucketing across all ranks (self_rank stays local)."""
        buckets = [[] for _ in range(world)]
        keep = []
        for rec in self._pool:
            d = _route_hash(rec[0], rec[1]) % world
            (keep if d == self_rank else buckets[d]).append(rec)
        self._pool = keep
        return [self._serialize(b) for b in buckets]

    def ingest(self, blob: bytes) -> int:
        """Raises ValueError on corrupt blobs (native-parity) and stages
        records so a mid-stream failure never leaves a partial shard."""
        import struct
        if not blob:
            return 0
        staged = []
        try:
            pos = 8
            (n,) = struct.unpack_from("<Q", blob, 0)
            for _ in range(n):
                (ns,) = struct.unpack_from("<I", blob, pos)
                pos += 4
                sparse = []
                for _s in range(ns):
                    (ln,) = struct.unpack_from("<Q", blob, pos)
                    pos += 8
                    vals = np.frombuffer(blob, "<u8", ln, pos)
                    sparse.append([int(v) for v in vals])
                    pos += 8 * ln
                (nd,) = struct.unpack_from("<I", blob, pos)
                pos += 4
                dense = []
                for _d in range(nd):
                    (ln,) = struct.unpack_from("<Q", blob, pos)
                    pos += 8
                    vals = np.frombuffer(blob, "<f4", ln, pos)
                    dense.append([float(v) for v in vals])
                    pos += 4 * ln
                staged.append((sparse, dense))
        except (struct.error, ValueError) as e:
            raise ValueError(f"corrupt global-shuffle blob: {e}") from e
        self._pool.extend(staged)
        return len(staged)

    def next(self):
        recs = []
        for r in self._iter:
            recs.append(r)
            if len(recs) >= self.batch_size:
                break
        if not recs:
            return None
        out = {}
        for i, s in enumerate(self.sparse_slots):
            ids, lod = [], [0]
            for sp, _ in recs:
                ids.extend(sp[i])
                lod.append(len(ids))
            out[s.name] = (np.asarray(ids, np.int64),
                           np.asarray(lod, np.int64))
        for i, s in enumerate(self.dense_slots):
            out[s.name] = np.asarray([d[i] for _, d in recs], np.float32)
        return out

    def __iter__(self):
        while True:
            b = self.next()
            if b is None:
                return
            yield b


def global_shuffle(feeds, seed=0):
    """GlobalShuffle across a list of feeds (data_set.h:118 analog): records
    are re-routed to feed hash(ids) % n then shuffled locally.  Works for
    native feeds in one call; Python feeds are shuffled with the same
    routing in numpy."""
    natives = [f for f in feeds if isinstance(f, NativeDataFeed)]
    if len(natives) == len(feeds) and natives:
        arr = (ctypes.c_void_p * len(feeds))(
            *[f._h for f in feeds])
        natives[0]._lib.pt_feed_global_shuffle(arr, len(feeds), seed)
        return
    if natives:
        raise ValueError(
            "global_shuffle: mixed native/python feed lists are not "
            "supported — pass all-native or all-python feeds")
    # python fallback: identical content-hash routing to the native path
    pools = [f._pool for f in feeds]
    dest = [[] for _ in feeds]
    for pool in pools:
        for rec in pool:
            dest[_route_hash(rec[0], rec[1]) % len(feeds)].append(rec)
    for i, (f, d) in enumerate(zip(feeds, dest)):
        # per-feed seed offset matches the native path's seed+i
        rng = np.random.RandomState(seed + i)
        rng.shuffle(d)
        f._pool = d


class _ArenaView(np.ndarray):
    """ndarray view that pins its owning Arena (prevents use-after-free)."""
    _arena = None


class Arena:
    """Host staging arena (auto_growth_best_fit_allocator.cc analog)."""

    def __init__(self, chunk_size=64 << 20):
        lib = _load()
        if lib is None:
            raise RuntimeError("native library unavailable")
        self._lib = lib
        self._h = lib.pt_arena_create(chunk_size)

    def alloc(self, size) -> int:
        p = self._lib.pt_arena_alloc(self._h, int(size))
        if not p:
            raise MemoryError(f"arena alloc of {size} failed")
        return p

    def free(self, ptr) -> bool:
        return bool(self._lib.pt_arena_free(self._h, ptr))

    def buffer(self, size):
        """numpy uint8 view over a fresh allocation (zero-copy staging).
        The view keeps the Arena alive (ndarray subclass holds a ref), so
        dropping the Arena while views exist cannot scribble freed memory;
        the caller must still not use the view after free(ptr)."""
        p = self.alloc(size)
        arr = np.ctypeslib.as_array(
            ctypes.cast(p, ctypes.POINTER(ctypes.c_uint8)),
            (size,)).view(_ArenaView)
        arr._arena = self
        return p, arr

    @property
    def stats(self):
        a, r, c = ctypes.c_int64(), ctypes.c_int64(), ctypes.c_int64()
        self._lib.pt_arena_stats(self._h, ctypes.byref(a), ctypes.byref(r),
                                 ctypes.byref(c))
        return {"allocated": a.value, "reserved": r.value, "chunks": c.value}

    def __del__(self):
        h, self._h = getattr(self, "_h", None), None
        lib = getattr(self, "_lib", None)
        if h and lib is not None:
            lib.pt_arena_destroy(h)


def make_data_feed(slots, batch_size, num_threads=4):
    """Factory: native feed when the toolchain exists, Python otherwise."""
    if native_available():
        return NativeDataFeed(slots, batch_size, num_threads)
    return PyDataFeed(slots, batch_size, num_threads)


__all__ = ["SlotDesc", "NativeDataFeed", "PyDataFeed", "make_data_feed",
           "native_available", "global_shuffle", "Arena", "pack_padded", "pack_padded_csr"]


def pack_padded_csr(vals, offs, pad_value=0, max_len=None,
                    n_threads=None):
    """CSR (concatenated values + [n+1] offsets) -> (padded [N, T],
    lengths [N]) in one native call — zero per-row Python objects.  This
    is the layout the native DataFeed's sparse slots and tokenized
    dataset storage already use, which is where batch packing is hot.
    n == 0 returns an empty [0, max_len or 0] batch."""
    vals = np.ascontiguousarray(vals)
    offs = np.ascontiguousarray(offs, dtype=np.int64)
    if offs.ndim != 1 or offs.shape[0] < 1:
        raise ValueError("offsets must be a 1-D [n+1] array")
    n = offs.shape[0] - 1
    row_lens = np.diff(offs)
    if n and (row_lens < 0).any():
        raise ValueError("offsets must be non-decreasing")
    if n and int(offs[0]) < 0:
        raise ValueError("offsets must start at a non-negative index")
    if n and int(offs[-1]) > vals.size:
        raise ValueError(
            f"offsets end at {int(offs[-1])} but values has {vals.size} "
            f"entries")
    T = int(max_len if max_len is not None
            else (row_lens.max() if n else 0))
    lens = np.empty(n, np.int64)
    if n == 0:
        return np.empty((0, T), vals.dtype), lens
    lib = _load()
    if lib is not None and vals.dtype in (np.dtype(np.int64),
                                          np.dtype(np.float32)):
        out = np.empty((n, T), vals.dtype)
        nt = n_threads or min(8, os.cpu_count() or 1)
        if vals.dtype == np.dtype(np.int64):
            lib.pt_pack_padded_i64(
                vals.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
                offs.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
                n, T, int(pad_value),
                out.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
                lens.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)), nt)
        else:
            lib.pt_pack_padded_f32(
                vals.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
                offs.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
                n, T, float(pad_value),
                out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
                lens.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)), nt)
        return out, lens
    # numpy fallback: vectorized scatter through a [N, T] mask
    keep = np.minimum(row_lens, T)
    out = np.full((n, T), pad_value, vals.dtype)
    col = np.arange(T)[None, :]
    mask = col < keep[:, None]
    src_idx = offs[:-1, None] + col
    out[mask] = vals[src_idx[mask]]
    lens[:] = keep
    return out, lens


def pack_padded(seqs, pad_value=0, max_len=None, n_threads=None):
    """Pack a list of 1-D variable-length sequences into (padded [N, T],
    lengths [N]).  Convenience wrapper: builds the CSR form and delegates
    to pack_padded_csr (use the CSR entry point directly when data is
    already values+offsets — per-row Python objects dominate here).
    Sequences must share one dtype; mixed dtypes are rejected rather than
    silently coerced."""
    if not seqs:
        raise ValueError("pack_padded needs at least one sequence")
    arrs = [np.asarray(s).reshape(-1) for s in seqs]
    kind = arrs[0].dtype
    if any(a.dtype != kind for a in arrs):
        raise TypeError(
            f"pack_padded got mixed dtypes "
            f"{sorted({str(a.dtype) for a in arrs})}; cast upstream")
    vals = np.concatenate(arrs) if len(arrs) > 1 else arrs[0]
    offs = np.zeros(len(arrs) + 1, np.int64)
    np.cumsum([a.shape[0] for a in arrs], out=offs[1:])
    return pack_padded_csr(vals, offs, pad_value=pad_value,
                           max_len=max_len, n_threads=n_threads)
