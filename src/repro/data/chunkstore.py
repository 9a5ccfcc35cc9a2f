"""Chunked raw-byte store — the paper's "raw file" abstraction.

A :class:`ChunkStore` is a sequence of raw chunks (each holding many records
in their on-disk byte format) plus the per-chunk metadata the estimators need
(``M_j`` — Section 4.3 notes textual formats get it from ``wc -l``-style
preprocessing and binary formats from file headers; here it is recorded at
ingest).

Two residency modes:

* in-memory (default): chunks are numpy uint8 arrays — the NoDB-style cache.
* disk-backed (``directory=...``): chunks are spilled to ``<name>.chunkNNN.bin``
  files and read back on demand, giving the benchmarks a real READ stage with
  measurable I/O time (and letting tests exercise restart-from-metadata).

Two device-facing residency modes (selected by ``EngineConfig.residency``):

* ``"packed"`` — :meth:`packed_device_view`: a padded
  ``(N, max_record_count, record_bytes)`` uint8 tensor for the jitted
  engine.  O(dataset) device memory; right for stores that fit.  Under
  ``"sharded"`` residency the same view is built one chunk range per
  device, so each device (and the host, at a time) holds only its range.
* ``"stream"`` — the engine pulls bounded per-round ``(W, rows_max, rec)``
  slabs through :class:`repro.data.pipeline.SlabPrefetcher`: chunks are read
  (and, when disk-backed, evicted) on the fly by a background reader thread,
  so host and device residency are O(slab), not O(dataset).
"""

from __future__ import annotations

import dataclasses
import json
import os
import zlib
from typing import Optional

import numpy as np

from repro.data.faults import CorruptChunkError


@dataclasses.dataclass
class ChunkMeta:
    num_tuples: int
    num_bytes: int
    path: Optional[str] = None  # set iff disk-backed
    # CRC32 of the chunk's raw bytes, recorded at ingest and checked on
    # every disk re-read; None for stores ingested before checksums
    # existed (legacy manifests open fine, they just skip verification)
    crc32: Optional[int] = None


class ChunkStore:
    def __init__(self, name: str, codec, directory: Optional[str] = None):
        self.name = name
        self.codec = codec
        self.directory = directory
        self.meta: list[ChunkMeta] = []
        self._chunks: list[Optional[np.ndarray]] = []
        self._finalized = False
        self._content_version = 0

    # ------------------------------------------------------------- create --
    @classmethod
    def create(cls, name: str, codec, directory: Optional[str] = None) -> "ChunkStore":
        if directory is not None:
            os.makedirs(directory, exist_ok=True)
        return cls(name=name, codec=codec, directory=directory)

    def append_chunk(self, raw: np.ndarray, num_tuples: int) -> None:
        assert not self._finalized
        raw = np.ascontiguousarray(raw, dtype=np.uint8).reshape(num_tuples, -1)
        assert raw.shape[1] == self.codec.record_bytes, (
            raw.shape, self.codec.record_bytes)
        j = len(self.meta)
        crc = zlib.crc32(raw.tobytes()) & 0xFFFFFFFF
        if self.directory is not None:
            path = os.path.join(self.directory, f"{self.name}.chunk{j:05d}.bin")
            raw.tofile(path)
            self.meta.append(ChunkMeta(num_tuples, raw.nbytes, path, crc))
            self._chunks.append(None)  # not resident
        else:
            self.meta.append(ChunkMeta(num_tuples, raw.nbytes, None, crc))
            self._chunks.append(raw)
        self._content_version += 1

    def finalize(self) -> None:
        self._finalized = True
        if self.directory is not None:
            manifest = {
                "name": self.name,
                "codec": type(self.codec).__name__,
                "num_cols": self.codec.num_cols,
                "chunks": [dataclasses.asdict(m) for m in self.meta],
            }
            with open(os.path.join(self.directory, f"{self.name}.manifest.json"), "w") as f:
                json.dump(manifest, f)

    @classmethod
    def open(cls, directory: str, name: str) -> "ChunkStore":
        """Re-open a disk-backed store from its manifest (restart path)."""
        from repro.data.formats import AsciiFixedFormat, BinaryBigEndianFormat

        with open(os.path.join(directory, f"{name}.manifest.json")) as f:
            manifest = json.load(f)
        codec_cls = {"AsciiFixedFormat": AsciiFixedFormat,
                     "BinaryBigEndianFormat": BinaryBigEndianFormat}[manifest["codec"]]
        store = cls(name=name, codec=codec_cls(manifest["num_cols"]), directory=directory)
        for m in manifest["chunks"]:
            store.meta.append(ChunkMeta(**m))
            store._chunks.append(None)
        store._finalized = True
        return store

    # -------------------------------------------------------------- access --
    @property
    def content_version(self) -> int:
        """Monotone counter over the store's raw content: bumped per
        ingested chunk and by :meth:`mark_content_changed`.  Derived
        artifacts that cache *answers* over the bytes (the rollup tier's
        cells, see ``repro.serve.rollup``) pin the version they were built
        over and invalidate on mismatch."""
        return self._content_version

    def mark_content_changed(self) -> None:
        """Signal an out-of-band mutation of the raw bytes (a re-ingest,
        an external writer touching the backing files): bumps
        :attr:`content_version` so version-pinned caches drop their
        state.  The store itself holds no derived aggregates — this is a
        pure version bump."""
        self._content_version += 1

    @property
    def num_chunks(self) -> int:
        return len(self.meta)

    @property
    def num_tuples(self) -> int:
        return sum(m.num_tuples for m in self.meta)

    @property
    def chunk_sizes(self) -> np.ndarray:
        """The M_j vector (Table 1)."""
        return np.asarray([m.num_tuples for m in self.meta], np.int32)

    @property
    def max_chunk_tuples(self) -> int:
        return int(self.chunk_sizes.max())

    def chunk_bytes(self, j: int) -> np.ndarray:
        """READ stage for one chunk: resident copy or a disk read.

        Disk re-reads are CRC-verified against the manifest; a mismatch
        raises :class:`CorruptChunkError` (which feeds the retry/quarantine
        path) instead of handing corrupt bytes to the extractor.
        """
        raw = self._chunks[j]
        if raw is None:
            m = self.meta[j]
            data = np.fromfile(m.path, dtype=np.uint8)
            if data.size != m.num_tuples * self.codec.record_bytes:
                raise CorruptChunkError(
                    f"chunk {j}: short read ({data.size} bytes, expected "
                    f"{m.num_tuples * self.codec.record_bytes})", chunk_id=j)
            raw = data.reshape(m.num_tuples, self.codec.record_bytes)
            self.verify_chunk(j, raw)
        return raw

    def read_chunk_into(self, j: int, out: np.ndarray) -> np.ndarray:
        """READ one chunk directly into a caller-provided buffer.

        ``out`` is a C-contiguous uint8 array of at least
        ``(num_tuples, record_bytes)``; the chunk's rows land at
        ``out[:num_tuples]`` and the filled view is returned.  Disk-backed
        chunks ``readinto()`` the file — the zero-copy slab-assembly path:
        file bytes go straight into the target slab slice with no
        intermediate numpy staging buffer.  Short reads and CRC mismatches
        raise :class:`CorruptChunkError` exactly like :meth:`chunk_bytes`.

        Note for wrappers: :class:`~repro.data.faults.FaultInjector` and
        other store proxies intercept :meth:`chunk_bytes` only, so callers
        that must honor injection (the :class:`SlabPrefetcher`) take this
        fast path only when the store's *own class* provides it.
        """
        m = self.meta[j]
        view = out[: m.num_tuples]
        raw = self._chunks[j]
        if raw is not None:
            np.copyto(view, raw)
            return view
        nbytes = m.num_tuples * self.codec.record_bytes
        with open(m.path, "rb") as f:
            got = f.readinto(memoryview(view.reshape(-1)[:nbytes]))
        if got != nbytes:
            raise CorruptChunkError(
                f"chunk {j}: short read ({got} bytes, expected {nbytes})",
                chunk_id=j)
        self.verify_chunk(j, view)
        return view

    def verify_chunk(self, j: int, raw: np.ndarray) -> None:
        """Check ``raw`` against chunk ``j``'s manifest CRC32.

        No-op for legacy manifests without checksums.  Consumers that
        receive chunk bytes through an intermediary (the
        :class:`~repro.data.pipeline.SlabPrefetcher`, possibly via a
        :class:`~repro.data.faults.FaultInjector`) call this to verify
        end-to-end, not just at the disk boundary.
        """
        crc = self.meta[j].crc32
        if crc is None:
            return
        got = zlib.crc32(np.ascontiguousarray(raw).tobytes()) & 0xFFFFFFFF
        if got != crc:
            raise CorruptChunkError(
                f"chunk {j}: CRC32 mismatch (manifest {crc:#010x}, "
                f"read {got:#010x})", chunk_id=j)

    def evict(self, j: int) -> None:
        """Drop a resident chunk (only meaningful for disk-backed stores)."""
        if self.directory is not None:
            self._chunks[j] = None

    def cache(self, j: int) -> None:
        if self._chunks[j] is None:
            self._chunks[j] = self.chunk_bytes(j)

    def packed_device_view(self, row_multiple: int = 1, chunks=None
                           ) -> tuple[np.ndarray, np.ndarray]:
        """Padded ``(N, M_pad, record_bytes)`` uint8 + ``(N,)`` sizes, where
        ``M_pad`` is ``M_max`` rounded up to ``row_multiple``.  ``chunks``
        (a range of chunk ids) packs only those, as ``(len(chunks), M_pad,
        record_bytes)``: one device's share of a sharded table.

        Padding rows are zero; the engine masks by ``M_j`` so they are never
        included in estimation.
        """
        rb = self.codec.record_bytes
        chunks = range(self.num_chunks) if chunks is None else chunks
        mx = -(-self.max_chunk_tuples // row_multiple) * row_multiple
        out = np.zeros((len(chunks), mx, rb), np.uint8)
        for i, j in enumerate(chunks):
            raw = self.chunk_bytes(j)
            out[i, : raw.shape[0]] = raw
            # a disk-backed store must not end up resident twice (raw chunks
            # cached by an earlier pass + this packed copy)
            self.evict(j)
        return out, self.chunk_sizes

    def decode_all(self) -> np.ndarray:
        """Ground-truth full EXTRACT (tests/benchmarks only): (T, C) float32."""
        import jax.numpy as jnp

        parts = [np.asarray(self.codec.decode_ref(jnp.asarray(self.chunk_bytes(j))))
                 for j in range(self.num_chunks)]
        return np.concatenate(parts, axis=0)
