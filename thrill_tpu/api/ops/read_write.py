"""File sources and sinks: ReadLines, ReadBinary, WriteLines*, WriteBinary.

Reference: thrill/api/read_lines.hpp:41 (byte-range split via size
prefix sums, scan to next newline :181-199, whole-file granularity for
compressed inputs), read_binary.hpp:45 (fixed-size records mapped to
blocks), write_lines.hpp:33 / write_lines_one.hpp:31 / write_binary.hpp:36
(per-worker chunked files with pattern substitution, or one file).
"""

from __future__ import annotations

import os
from typing import List

import numpy as np

from ...data.shards import DeviceShards, HostShards
from ...vfs import file_io
from ..dia import DIA
from ..dia_base import DIABase, staged_action
from ...common.partition import dense_range_bounds


class ReadLinesNode(DIABase):
    def __init__(self, ctx, path_or_glob: str) -> None:
        super().__init__(ctx, "ReadLines")
        self.pattern = path_or_glob

    def compute(self):
        W = self.context.num_workers
        fl = file_io.Glob(self.pattern)
        if len(fl) == 0:
            raise FileNotFoundError(f"ReadLines: no files match "
                                    f"{self.pattern!r}")
        if fl.contains_compressed:
            return self._compute_whole_files(fl)
        return self._compute_ranges(fl)

    def _compute_whole_files(self, fl: file_io.FileList):
        """Compressed: whole-file granularity round-robin by size psum."""
        W = self.context.num_workers
        total = fl.total_size
        from ...data.multiplexer import local_worker_set
        local = local_worker_set(self.context.mesh_exec)
        lists: List[List[str]] = [[] for _ in range(W)]
        for fi in fl.files:
            # assign file to the worker owning its start offset
            w = min(W - 1, (fi.size_ex_psum * W) // max(total, 1))
            if w not in local:
                continue          # another controller reads this file
            with file_io.OpenReadStream(fi.path) as f:
                data = f.read()
            lists[w].extend(data.decode("utf-8").splitlines())
        return HostShards(W, lists)

    def _compute_ranges(self, fl: file_io.FileList):
        """Uncompressed: split the global byte range evenly; each worker
        starts after the first newline past its range start (the item
        owned by the worker containing its START). Multi-controller:
        each process reads ONLY its own workers' byte ranges — the I/O
        scales out with processes (reference: read_lines.hpp:41 splits
        by worker the same way)."""
        W = self.context.num_workers
        total = fl.total_size
        from ...data.multiplexer import local_worker_set
        local = local_worker_set(self.context.mesh_exec)
        bounds = dense_range_bounds(total, W).tolist()
        lists: List[List[str]] = []
        for w in range(W):
            if w not in local:
                lists.append([])
                continue
            lo, hi = bounds[w], bounds[w + 1]
            lists.append(_read_lines_range(fl, lo, hi))
        return HostShards(W, lists)


def _read_delimited_range(fl: file_io.FileList, lo: int, hi: int,
                          is_delim, find_delim,
                          include_delim: bool) -> List[bytes]:
    """Byte chunks covering every delimited item whose FIRST byte lies
    in [lo, hi) of the global stream (one chunk per overlapping file;
    file boundaries always terminate an item).

    The one boundary scanner behind both ReadLines (delimiter = '\\n',
    kept in the chunk) and ReadWordsPacked (delimiter = any whitespace,
    dropped): ``is_delim(byte) -> bool`` probes the byte before the
    range, ``find_delim(bytes) -> offset|-1`` scans forward, and
    ``include_delim`` controls whether the final delimiter is part of
    the last item."""
    out: List[bytes] = []
    if lo >= hi:
        return out
    for fi in fl.files:
        f_lo, f_hi = fi.size_ex_psum, fi.size_ex_psum + fi.size
        if f_hi <= lo or f_lo >= hi:
            continue
        start = max(lo, f_lo) - f_lo
        end = min(hi, f_hi) - f_lo
        # readahead horizon = the range end: the background reader must
        # not stream blocks past the bytes this worker will consume
        # (the tail extension past ``end`` legitimately continues on
        # demand reads — a horizon is a hint, not EOF)
        with file_io.OpenReadStream(fi.path, readahead_to=end) as f:
            if start > 0:
                f.seek(start - 1)
                if is_delim(f.read(1)):
                    chunk_start = start
                else:
                    # mid-item: the item containing byte ``start``
                    # began earlier and belongs to the previous range
                    chunk_start = None
                    pos = start
                    while True:
                        b = f.read(1 << 16)
                        if not b:
                            chunk_start = f_hi - f_lo
                            break
                        d = find_delim(b)
                        if d >= 0:
                            chunk_start = pos + d + 1
                            break
                        pos += len(b)
            else:
                chunk_start = 0
            if chunk_start >= end:
                continue
            f.seek(chunk_start)
            data = f.read(end - chunk_start)
            # extend to finish the last item (it starts in-range)
            if data and not is_delim(data[-1:]):
                while True:
                    b = f.read(1 << 16)
                    if not b:
                        break
                    d = find_delim(b)
                    if d >= 0:
                        data += b[:d + 1] if include_delim else b[:d]
                        break
                    data += b
            out.append(data)
    return out


def _read_lines_range(fl: file_io.FileList, lo: int, hi: int) -> List[str]:
    """All lines whose first byte lies in [lo, hi) of the global stream."""
    out: List[str] = []
    for data in _read_delimited_range(
            fl, lo, hi, lambda b: b == b"\n",
            lambda b: b.find(b"\n"), include_delim=True):
        # str.splitlines is already a C-level loop and handles CRLF
        # etc.; the native scanner (data/block_pool.scan_line_offsets)
        # is reserved for the raw-bytes -> device packing path where
        # no Python string objects are materialized
        out.extend(data.decode("utf-8").splitlines())
    return out


class ReadWordsPackedNode(DIABase):
    """Text -> device DIA of fixed-width packed words.

    The device-native text source (reference text pipelines start from
    ReadLines + a per-item FlatMap split, read_lines.hpp:41 +
    word_count.hpp:35-44; here tokenization is one vectorized pass and
    the words land directly in device columns as {"w": [max_word] u8}
    rows, ready for byte-key ReduceByKey/Sort). A word is owned by the
    worker whose byte range contains its FIRST byte — the same
    ownership rule ReadLines uses for lines."""

    def __init__(self, ctx, path_or_glob: str, max_word: int) -> None:
        super().__init__(ctx, "ReadWordsPacked")
        self.pattern = path_or_glob
        self.max_word = int(max_word)

    def compute(self):
        from ...core import text as textmod
        from ...data import multiplexer

        W = self.context.num_workers
        mex = self.context.mesh_exec
        fl = file_io.Glob(self.pattern)
        if len(fl) == 0:
            raise FileNotFoundError(f"ReadWordsPacked: no files match "
                                    f"{self.pattern!r}")
        local = multiplexer.local_worker_set(mex)
        total = fl.total_size
        empty = np.zeros((0, self.max_word), dtype=np.uint8)
        per_worker = []
        if fl.contains_compressed:
            # whole-file granularity (same placement rule as ReadLines)
            chunks: List[List[bytes]] = [[] for _ in range(W)]
            for fi in fl.files:
                w = min(W - 1, (fi.size_ex_psum * W) // max(total, 1))
                if w not in local:
                    continue
                with file_io.OpenReadStream(fi.path) as f:
                    chunks[w].append(f.read())
            for w in range(W):
                per_worker.append(np.concatenate(
                    [textmod.tokenize_packed(c, self.max_word)
                     for c in chunks[w]], axis=0)
                    if chunks[w] else empty)
        else:
            bounds = dense_range_bounds(total, W).tolist()
            for w in range(W):
                if w not in local:
                    per_worker.append(empty)
                    continue
                parts = [textmod.tokenize_packed(c, self.max_word)
                         for c in _read_word_bytes_range(
                             fl, bounds[w], bounds[w + 1])]
                per_worker.append(np.concatenate(parts, axis=0)
                                  if parts else empty)

        counts = np.array([len(a) for a in per_worker], dtype=np.int64)
        if multiplexer.multiprocess(mex):
            # counts are data-dependent: agree on the global vector
            mine = {w: int(counts[w]) for w in mex.local_workers}
            for msg in multiplexer._net(mex).all_gather(mine):
                for w, c in msg.items():
                    counts[int(w)] = c
        return DeviceShards.from_worker_arrays(
            mex, [{"w": a} for a in per_worker], counts=counts)


def _read_word_bytes_range(fl: file_io.FileList, lo: int,
                           hi: int) -> List[bytes]:
    """Byte chunks covering every word whose first byte lies in
    [lo, hi) of the global stream (file boundaries count as
    separators, like ReadLines treats them as line breaks)."""
    from ...core import text as textmod
    return _read_delimited_range(
        fl, lo, hi,
        lambda b: bool(textmod.sep_mask(np.frombuffer(b, np.uint8))[0]),
        textmod.find_first_sep, include_delim=False)


class ReadBinaryNode(DIABase):
    """Fixed-size records -> device columnar storage directly."""

    def __init__(self, ctx, path_or_glob: str, dtype, record_shape) -> None:
        super().__init__(ctx, "ReadBinary")
        self.pattern = path_or_glob
        self.dtype = np.dtype(dtype)
        self.record_shape = tuple(record_shape)

    def compute(self):
        W = self.context.num_workers
        fl = file_io.Glob(self.pattern)
        rec_items = int(np.prod(self.record_shape)) if self.record_shape \
            else 1
        rec_bytes = rec_items * self.dtype.itemsize
        total_recs = fl.total_size // rec_bytes
        bounds = dense_range_bounds(total_recs, W).tolist()
        # multi-controller: read only this process's workers' ranges;
        # counts derive from bounds, so no agreement round is needed
        from ...data.multiplexer import local_worker_set
        local = local_worker_set(self.context.mesh_exec)
        empty = np.empty((0,) + self.record_shape, dtype=self.dtype)
        per_worker = []
        for w in range(W):
            if w not in local:
                per_worker.append(empty)
                continue
            lo, hi = bounds[w], bounds[w + 1]
            arr = _read_records(fl, lo, hi, rec_bytes, self.dtype)
            per_worker.append(arr.reshape((-1,) + self.record_shape))
        counts = np.array([bounds[w + 1] - bounds[w] for w in range(W)],
                          dtype=np.int64)
        return DeviceShards.from_worker_arrays(
            self.context.mesh_exec, per_worker, counts=counts)


def _read_records(fl, lo_rec, hi_rec, rec_bytes, dtype) -> np.ndarray:
    lo, hi = lo_rec * rec_bytes, hi_rec * rec_bytes
    chunks = []
    for fi in fl.files:
        f_lo, f_hi = fi.size_ex_psum, fi.size_ex_psum + fi.size
        if f_hi <= lo or f_lo >= hi:
            continue
        start = max(lo, f_lo) - f_lo
        end = min(hi, f_hi) - f_lo
        with file_io.OpenReadStream(fi.path, offset=start,
                                    readahead_to=end) as f:
            chunks.append(f.read(end - start))
    buf = b"".join(chunks)
    return np.frombuffer(buf, dtype=dtype)


# ----------------------------------------------------------------------
# sinks
# ----------------------------------------------------------------------

def _worker_path(pattern: str, w: int) -> str:
    if "$$$$$" in pattern:        # reference's wildcard (api/dia.hpp:813)
        return pattern.replace("$$$$$", f"{w:05d}")
    if "{}" in pattern:
        return pattern.format(w)
    base, ext = os.path.splitext(pattern)
    return f"{base}-{w:05d}{ext}"


def _host_lists(dia) -> HostShards:
    shards = dia._link().pull()
    if isinstance(shards, DeviceShards):
        shards = shards.to_host_shards("writelines")
    return shards


def _local_worker_ids(dia):
    mex = dia.context.mesh_exec
    from ...data import multiplexer
    if multiplexer.multiprocess(mex):
        return set(mex.local_workers)
    return set(range(mex.num_workers))


@staged_action
def WriteLines(dia, path_pattern: str) -> None:
    """One text file per worker (reference: api/write_lines.hpp:33).
    Multi-controller: each process writes only its own workers' files."""
    shards = _host_lists(dia)
    owned = _local_worker_ids(dia)
    for w, items in enumerate(shards.lists):
        if w not in owned:
            continue
        with file_io.OpenWriteStream(_worker_path(path_pattern, w)) as f:
            for it in items:
                f.write(str(it).encode("utf-8"))
                f.write(b"\n")


@staged_action
def WriteLinesOne(dia, path: str) -> None:
    """Single coordinated output file (reference: write_lines_one.hpp:31).
    Multi-controller: items gather to process 0, which writes the file
    alone (worker-rank order is preserved)."""
    shards = _host_lists(dia)
    mex = dia.context.mesh_exec
    from ...data import multiplexer
    if multiplexer.multiprocess(mex):
        items = multiplexer.all_items(mex, shards)
        if mex.process_index != 0:
            return
        with file_io.OpenWriteStream(path) as f:
            for it in items:
                f.write(str(it).encode("utf-8"))
                f.write(b"\n")
        return
    with file_io.OpenWriteStream(path) as f:
        for items in shards.lists:
            for it in items:
                f.write(str(it).encode("utf-8"))
                f.write(b"\n")


@staged_action
def WriteBinary(dia, path_pattern: str) -> None:
    """Raw fixed-size records, one file per worker
    (reference: api/write_binary.hpp:36)."""
    shards = dia._link().pull()
    owned = _local_worker_ids(dia)
    if isinstance(shards, DeviceShards):
        per_worker = shards.to_worker_arrays(local_only=True)
        import jax
        for w, tree in enumerate(per_worker):
            if tree is None or w not in owned:
                continue
            leaves = jax.tree.leaves(tree)
            with file_io.OpenWriteStream(_worker_path(path_pattern, w)) as f:
                for leaf in leaves:
                    f.write(np.ascontiguousarray(leaf).tobytes())
        return
    for w, items in enumerate(shards.lists):
        if w not in owned:
            continue
        with file_io.OpenWriteStream(_worker_path(path_pattern, w)) as f:
            for it in items:
                f.write(np.asarray(it).tobytes())


def ReadLines(ctx, path_or_glob: str) -> DIA:
    return DIA(ReadLinesNode(ctx, path_or_glob))


def ReadWordsPacked(ctx, path_or_glob: str, max_word: int = 16) -> DIA:
    return DIA(ReadWordsPackedNode(ctx, path_or_glob, max_word))


def ReadBinary(ctx, path_or_glob: str, dtype, record_shape=()) -> DIA:
    return DIA(ReadBinaryNode(ctx, path_or_glob, dtype, record_shape))
