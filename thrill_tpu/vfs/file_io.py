"""Virtual file system: glob, ranged reads, compressed streams.

Reference: thrill/vfs/file_io.hpp:79-164 — scheme dispatch (file://,
s3://), ``Glob`` returning a FileList with exclusive size prefix sums
(used to split byte ranges over workers), Read/WriteStream interfaces,
gzip/bzip2/xz filters (sys_file.cpp pipes through external binaries; we
use Python's codecs). S3/HDFS backends are gated stubs until their SDKs
are available in the image.
"""

from __future__ import annotations

import bz2
import collections
import dataclasses
import glob as _glob
import gzip
import lzma
import os
import threading
import time
from typing import IO, List, Optional

from ..common import faults
from ..common.iostats import IO as _IOSTATS
from ..common.retry import default_policy

COMPRESSED_SUFFIXES = (".gz", ".bz2", ".xz")

# ranged reads are idempotent — every stream here can be reopened at
# an absolute offset (posix seek, s3 ranged GET, hdfs seek; compressed
# streams re-skip decompressed bytes) — so transient storage faults
# retry with a fresh handle under the shared backoff policy instead of
# failing a whole pipeline for one flaky read
_F_OPEN = faults.declare("vfs.open_read")
_F_READ = faults.declare("vfs.read")
# latency-injection twin of vfs.read: arm with :delay=<dur> to make
# THIS process's reads deterministically slow (straggler/IO-wait tests)
_F_READ_DELAY = faults.declare("vfs.read.delay")
# background-readahead failure (fires on the reader THREAD): the
# prefetching layer degrades to demand reads at the exact consumed
# position — slower, never wrong data. Bytes already queued before the
# failure were produced by the same retrying reader and stay valid.
_F_PREFETCH = faults.declare("vfs.prefetch")


def prefetch_depth() -> int:
    """THRILL_TPU_PREFETCH: how many blocks the background readahead
    keeps in flight ahead of the consumer. 0 restores today's demand
    reads byte-identically (OpenReadStream returns the plain retrying
    reader); the THRILL_TPU_OVERLAP=0 master switch also disables it."""
    from ..common.config import overlap_enabled
    if not overlap_enabled():
        return 0
    try:
        return max(0, int(os.environ.get("THRILL_TPU_PREFETCH",
                                         "4") or 4))
    except ValueError:
        return 4


def _prefetch_block_bytes() -> int:
    """THRILL_TPU_PREFETCH_BLOCK: readahead block size (default 1 MiB
    — big enough that queue handoff is noise, small enough that depth
    blocks bound RAM)."""
    try:
        return max(1 << 12, int(os.environ.get(
            "THRILL_TPU_PREFETCH_BLOCK", "") or (1 << 20)))
    except ValueError:
        return 1 << 20


@dataclasses.dataclass
class FileInfo:
    path: str
    size: int              # uncompressed size unknown for compressed
    size_ex_psum: int      # exclusive prefix sum of sizes
    is_compressed: bool


@dataclasses.dataclass
class FileList:
    files: List[FileInfo]

    @property
    def total_size(self) -> int:
        if not self.files:
            return 0
        last = self.files[-1]
        return last.size_ex_psum + last.size

    @property
    def contains_compressed(self) -> bool:
        return any(f.is_compressed for f in self.files)

    def __len__(self) -> int:
        return len(self.files)

    def __getitem__(self, i: int) -> FileInfo:
        return self.files[i]


def _scheme(path: str) -> str:
    if "://" in path:
        return path.split("://", 1)[0]
    return "file"


def Glob(path_or_glob: str) -> FileList:
    """Expand a path/glob into a FileList with size prefix sums.

    Reference: vfs::Glob, file_io.hpp:105; FileList::size_ex_psum :79-99.
    """
    scheme = _scheme(path_or_glob)
    if scheme == "s3":
        from . import s3_file
        files: List[FileInfo] = []
        psum = 0
        for p, sz in s3_file.s3_glob(path_or_glob):
            files.append(FileInfo(p, sz, psum,
                                  p.endswith(COMPRESSED_SUFFIXES)))
            psum += sz
        return FileList(files)
    if scheme == "hdfs":
        from . import hdfs_file
        files = []
        psum = 0
        for p, sz in hdfs_file.hdfs_glob(path_or_glob):
            files.append(FileInfo(p, sz, psum,
                                  p.endswith(COMPRESSED_SUFFIXES)))
            psum += sz
        return FileList(files)
    if scheme in ("http", "https"):
        from . import object_store
        files = []
        psum = 0
        for p, sz in object_store.http_glob(path_or_glob):
            files.append(FileInfo(p, sz, psum,
                                  p.endswith(COMPRESSED_SUFFIXES)))
            psum += sz
        return FileList(files)
    if scheme != "file":
        raise NotImplementedError(
            f"vfs scheme '{scheme}' is not implemented; file://, s3://, "
            f"hdfs:// and http(s):// are")
    pat = path_or_glob[len("file://"):] if path_or_glob.startswith("file://") \
        else path_or_glob
    if os.path.isdir(pat):
        paths = sorted(
            os.path.join(pat, p) for p in os.listdir(pat)
            if os.path.isfile(os.path.join(pat, p)))
    else:
        paths = sorted(p for p in _glob.glob(pat) if os.path.isfile(p))
    files: List[FileInfo] = []
    psum = 0
    for p in paths:
        sz = os.path.getsize(p)
        files.append(FileInfo(p, sz, psum, p.endswith(COMPRESSED_SUFFIXES)))
        psum += sz
    return FileList(files)


def _open_at(path: str, offset: int) -> IO[bytes]:
    """One stream positioned at ``offset``, any scheme (the reopenable
    primitive the retrying reader is built on)."""
    faults.check(_F_OPEN, path=path, offset=offset)
    scheme = _scheme(path)
    if scheme == "s3":
        if path.endswith(COMPRESSED_SUFFIXES):
            raise ValueError("compressed s3 objects are read whole-file")
        from . import s3_file
        return s3_file.s3_open_read(path, offset)
    if scheme == "hdfs":
        from . import hdfs_file
        return hdfs_file.hdfs_open_read(path, offset)
    if scheme in ("http", "https"):
        if path.endswith(COMPRESSED_SUFFIXES):
            raise ValueError(
                "compressed http objects are read whole-file")
        from . import object_store
        return object_store.http_open_read(path, offset)
    f = _open_filtered(path, "rb")
    if offset:
        if path.endswith(COMPRESSED_SUFFIXES):
            # whole-file granularity on disk, but the RETRY reopen may
            # legitimately land mid-stream: skip decompressed bytes
            skipped = 0
            while skipped < offset:
                b = f.read(min(offset - skipped, 1 << 20))
                if not b:
                    break
                skipped += len(b)
        else:
            f.seek(offset)
    return f


class RetryingReader:
    """Self-healing read stream: tracks the absolute (decompressed)
    position and, when a read or open fails transiently, reopens the
    source at that position and resumes — the vfs-level recovery the
    reference cannot express (its ReadStream dies with the job,
    vfs/file_io.hpp:140).

    A thin proxy, not an io subclass. Every CONSUMING read
    (``read``/``readinto``/``readline``/``readlines``/``read1``/
    iteration) and ``seek`` are implemented here so ``_pos`` stays
    exact — a delegated consuming read would advance the stream behind
    the tracker and make a post-fault reopen replay bytes.
    Non-consuming attributes delegate to the wrapped stream so
    existing callers (ReadLines' delimiter probing does seek+read on
    posix files) see unchanged behavior."""

    def __init__(self, path: str, offset: int = 0) -> None:
        self._path = path
        self._pos = offset
        self._closed = False
        # one policy per reader, not per read: the env knobs are fixed
        # for a stream's lifetime, and ReadLines drives this per line
        self._policy = default_policy()
        self._f = self._policy.run(
            lambda: _open_at(path, offset), what="vfs.open_read")

    def _consume(self, read_fn) -> bytes:
        """THE retry-and-reopen invariant, in one place: run one
        consuming read under the policy (injection gate, reopen at the
        tracked offset after any failure, advance ``_pos`` by what was
        actually returned). Every consuming method routes here so the
        byte-replay protection cannot silently diverge between them."""
        if self._closed:
            raise ValueError("I/O operation on closed file")

        def op():
            faults.check(_F_READ, path=self._path, pos=self._pos)
            # latency injection (``vfs.read.delay:delay=50ms``): a
            # deterministic slow disk for straggler/IO-wait tests —
            # armed WITHOUT delay= it raises inside the same retry
            # scope as vfs.read (nothing consumed yet)
            faults.check(_F_READ_DELAY, path=self._path, pos=self._pos)
            if self._f is None:       # previous attempt lost the handle
                self._f = _open_at(self._path, self._pos)
            try:
                return read_fn(self._f)
            except Exception:
                # the handle is suspect after ANY failure: drop it so a
                # retry resumes from a fresh stream at self._pos
                self._drop()
                raise
        data = self._policy.run(op, what="vfs.read")
        self._pos += len(data)
        return data

    def read(self, n: int = -1) -> bytes:
        # read-to-EOF is spelled read() for pyarrow streams
        # (read(-1) trips their size check)
        if n is None or n < 0:
            return self._consume(lambda f: f.read())
        return self._consume(lambda f: f.read(n))

    def readinto(self, b) -> int:
        data = self.read(len(b))
        b[:len(data)] = data
        return len(data)

    def readline(self, n: int = -1) -> bytes:
        return self._consume(lambda f: f.readline(n))

    def readlines(self, hint: int = -1) -> list:
        out = []
        total = 0
        while True:
            line = self.readline()
            if not line:
                return out
            out.append(line)
            total += len(line)
            if 0 < hint <= total:     # io semantics: hint<=0 = no cap
                return out

    def read1(self, n: int = -1) -> bytes:
        return self.read(n if n is not None and n >= 0 else 1 << 16)

    def __iter__(self) -> "RetryingReader":
        return self

    def __next__(self) -> bytes:
        line = self.readline()
        if not line:
            raise StopIteration
        return line

    def seek(self, pos: int, whence: int = os.SEEK_SET) -> int:
        if self._closed:
            raise ValueError("I/O operation on closed file")
        if whence == os.SEEK_CUR:
            pos, whence = self._pos + pos, os.SEEK_SET
        if whence == os.SEEK_SET:
            if pos == self._pos:
                return pos                  # no-op probe, keep handle
            if self._f is not None and self._f.seekable():
                self._pos = self._f.seek(pos)
            else:
                # no live handle, or a ranged-transport stream (http)
                # that cannot seek: reposition the tracker and drop —
                # the next read opens a fresh stream at the target
                # (for http, one ranged GET)
                self._drop()
                self._pos = pos
            return self._pos
        # size-relative (SEEK_END) needs a real handle
        if self._f is None:
            self._f = _open_at(self._path, self._pos)
        out = self._f.seek(pos, whence)
        self._pos = out
        return out

    def tell(self) -> int:
        return self._pos

    def _drop(self) -> None:
        f, self._f = self._f, None
        if f is not None:
            try:
                f.close()
            except Exception:
                pass

    def close(self) -> None:
        self._closed = True
        self._drop()

    @property
    def closed(self) -> bool:
        return self._closed

    def __enter__(self) -> "RetryingReader":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def __getattr__(self, name):
        # private names never delegate (and must not recurse through
        # __getattr__ during __init__/unpickling)
        if name.startswith("_"):
            raise AttributeError(name)
        # no handle (closed, or dropped after a fault): AttributeError,
        # not ValueError — hasattr/getattr-with-default probes on a
        # closed reader must behave like on any other object, and a
        # mere attribute probe must never reopen the stream
        f = self.__dict__.get("_f")
        if f is None:
            raise AttributeError(name)
        return getattr(f, name)


class _FillState:
    """One readahead generation: the queue, its lock, and the thread
    that owns them. A reader seek/teardown abandons the whole
    generation atomically — a fill thread stuck in a hung read past
    the join timeout still references only ITS state and can never
    deliver stale bytes into a successor's queue."""

    __slots__ = ("chunks", "cv", "stop", "err", "thread")

    def __init__(self) -> None:
        self.chunks: collections.deque = collections.deque()
        self.cv = threading.Condition()
        self.stop = False
        self.err: Optional[BaseException] = None
        self.thread: Optional[threading.Thread] = None


class PrefetchingReader:
    """Bounded background readahead over a :class:`RetryingReader`.

    A dedicated reader thread streams fixed-size blocks into an
    N-deep queue (``THRILL_TPU_PREFETCH``) so sequential consumers —
    ReadLines byte ranges, ReadBinary record ranges, checkpoint shard
    files — overlap disk/object-store latency with their own decode
    work, the vfs analog of foxxll's async block prefetch (reference:
    thrill/data/block_pool.hpp:177 MaxMergeDegreePrefetch). Contract:

    * bytes delivered are IDENTICAL to demand reads — the thread runs
      the same retrying reader, in order, from the same offset;
    * a background failure (``vfs.prefetch`` site) DEGRADES to demand
      reads at the exact consumed position — never wrong data;
    * ``seek`` outside the buffered window restarts the readahead at
      the target (the delimiter-probe pattern pays two restarts per
      range, then streams).

    Consumption accounting feeds the overlap ledger
    (common/iostats.py): a refill served from the queue is a
    ``prefetch_hit``; blocking on the reader thread is a miss plus
    ``io_wait_s``.
    """

    def __init__(self, path: str, offset: int = 0,
                 depth: Optional[int] = None,
                 readahead_to: Optional[int] = None) -> None:
        self._path = path
        self._pos = offset          # absolute offset of _buf[0]
        self._closed = False
        self._depth = prefetch_depth() if depth is None else depth
        self._block = _prefetch_block_bytes()
        # absolute readahead horizon: the fill thread never reads past
        # it (bounded-range callers know their end, and over-reading
        # depth*block bytes per range would be real wasted I/O on an
        # object store). Bytes BEYOND the horizon are still readable —
        # the reader continues on demand reads, silently (a horizon is
        # a hint, not EOF: ReadLines legitimately extends past its
        # range to finish the last item).
        self._limit = readahead_to
        self._buf = bytearray()     # dequeued, not yet returned
        self._demand: Optional[RetryingReader] = None
        self._hits = 0
        self._misses = 0
        self._wait_s = 0.0
        # the fill thread starts LAZILY on the first consuming read:
        # the delimiter-probe pattern (open, seek, read) would
        # otherwise waste a block read per seek before streaming.
        # Each (re)start gets its OWN _FillState generation: a thread
        # that outlives the teardown join timeout (hung storage) still
        # holds only ITS state object and can never interleave stale
        # blocks into a restarted reader's queue.
        self._st: Optional[_FillState] = None
        self._eof = False

    # -- background fill ------------------------------------------------
    def _start_thread(self, offset: int) -> None:
        st = _FillState()
        self._st = st
        self._eof = False
        st.thread = threading.Thread(target=self._fill,
                                     args=(st, offset), daemon=True,
                                     name="thrill-tpu-prefetch")
        st.thread.start()

    def _fill(self, st: "_FillState", offset: int) -> None:
        inner = None
        try:
            inner = RetryingReader(self._path, offset)
            fill_pos = offset
            while True:
                with st.cv:
                    while len(st.chunks) >= self._depth \
                            and not st.stop:
                        st.cv.wait(0.1)
                    if st.stop:
                        return
                take = self._block
                if self._limit is not None:
                    take = min(take, self._limit - fill_pos)
                    if take <= 0:
                        with st.cv:
                            if not st.stop:
                                # horizon reached, NOT EOF: the
                                # consumer continues on demand reads
                                st.chunks.append(None)
                                st.cv.notify_all()
                        return
                if faults.REGISTRY.active():
                    faults.check(_F_PREFETCH, path=self._path)
                t0 = time.perf_counter()
                data = inner.read(take)
                _IOSTATS.add(io_busy_s=time.perf_counter() - t0)
                fill_pos += len(data)
                with st.cv:
                    if st.stop:
                        return
                    st.chunks.append(data)      # b"" = EOF marker
                    st.cv.notify_all()
                if not data:
                    return
        except BaseException as e:
            with st.cv:
                st.err = e
                st.cv.notify_all()
        finally:
            if inner is not None:
                inner.close()

    def _teardown_thread(self) -> None:
        st = self._st
        if st is None:
            return
        with st.cv:
            st.stop = True
            st.cv.notify_all()
        # a thread wedged in a hung read past the join timeout is
        # abandoned WITH its state generation — it can only ever touch
        # that orphaned deque, never a successor's
        st.thread.join(timeout=30)
        self._st = None

    def _degrade(self, err: BaseException) -> None:
        """Background read failed: continue on demand reads from the
        first unread byte. Queued bytes stay valid (produced in order
        by the same reader before the failure)."""
        self._teardown_thread()
        faults.note("recovery", what="vfs.prefetch_degraded",
                    path=self._path, error=repr(err)[:200])
        self._demand = RetryingReader(self._path,
                                      self._pos + len(self._buf))

    def _next_chunk(self) -> bytes:
        """One more block for ``_buf`` (b"" at EOF), from the queue,
        the demand fallback, or — after a background failure — the
        degraded reader."""
        if self._demand is not None:
            return self._demand.read(self._block)
        if self._eof:
            return b""
        if self._st is None:
            self._start_thread(self._pos + len(self._buf))
        st = self._st
        waited = False
        with st.cv:
            if not st.chunks:
                err = st.err
                if err is None and st.thread.is_alive():
                    t0 = time.perf_counter()
                    while not st.chunks and st.err is None \
                            and st.thread.is_alive():
                        st.cv.wait(0.1)
                    dt = time.perf_counter() - t0
                    self._wait_s += dt
                    _IOSTATS.add(io_wait_s=dt, prefetch_misses=1)
                    self._misses += 1
                    waited = True
                err = st.err
                if not st.chunks:
                    if err is None:       # thread died silently
                        err = RuntimeError("prefetch thread exited "
                                           "without data or EOF")
                    st.err = None
            if st.chunks:
                data = st.chunks.popleft()
                st.cv.notify_all()
                if data is None:
                    # readahead horizon: continue on demand reads,
                    # silently (no recovery event — nothing failed)
                    horizon = True
                else:
                    if not data:
                        self._eof = True
                    elif not waited:
                        self._hits += 1
                        _IOSTATS.add(prefetch_hits=1)
                    return bytes(data)
            else:
                horizon = False
        if horizon:
            self._teardown_thread()
            self._demand = RetryingReader(self._path,
                                          self._pos + len(self._buf))
            return self._demand.read(self._block)
        self._degrade(err)
        return self._demand.read(self._block)

    # -- consuming API (mirrors RetryingReader) -------------------------
    def read(self, n: int = -1) -> bytes:
        if self._closed:
            raise ValueError("I/O operation on closed file")
        if n is None or n < 0:
            while True:
                data = self._next_chunk()
                if not data:
                    break
                self._buf += data
            out = bytes(self._buf)
            self._buf.clear()
            self._pos += len(out)
            return out
        while len(self._buf) < n:
            data = self._next_chunk()
            if not data:
                break
            self._buf += data
        out = bytes(self._buf[:n])
        del self._buf[:n]
        self._pos += len(out)
        return out

    def readinto(self, b) -> int:
        data = self.read(len(b))
        b[:len(data)] = data
        return len(data)

    def readline(self, n: int = -1) -> bytes:
        if self._closed:
            raise ValueError("I/O operation on closed file")
        limit = n if (n is not None and n >= 0) else None
        scanned = 0
        while True:
            idx = self._buf.find(b"\n", scanned)
            if idx >= 0:
                end = idx + 1
                break
            scanned = len(self._buf)
            if limit is not None and scanned >= limit:
                end = limit
                break
            data = self._next_chunk()
            if not data:
                end = len(self._buf)
                break
            self._buf += data
        if limit is not None:
            end = min(end, limit)
        out = bytes(self._buf[:end])
        del self._buf[:end]
        self._pos += len(out)
        return out

    def readlines(self, hint: int = -1) -> list:
        out = []
        total = 0
        while True:
            line = self.readline()
            if not line:
                return out
            out.append(line)
            total += len(line)
            if 0 < hint <= total:
                return out

    def read1(self, n: int = -1) -> bytes:
        return self.read(n if n is not None and n >= 0 else 1 << 16)

    def __iter__(self) -> "PrefetchingReader":
        return self

    def __next__(self) -> bytes:
        line = self.readline()
        if not line:
            raise StopIteration
        return line

    def seek(self, pos: int, whence: int = os.SEEK_SET) -> int:
        if self._closed:
            raise ValueError("I/O operation on closed file")
        if whence == os.SEEK_CUR:
            pos, whence = self._pos + pos, os.SEEK_SET
        if whence == os.SEEK_SET \
                and self._pos <= pos <= self._pos + len(self._buf):
            # within the buffered window: consume the prefix
            del self._buf[:pos - self._pos]
            self._pos = pos
            return pos
        # outside the window (or SEEK_END): restart at the target
        if self._demand is None:
            self._teardown_thread()
        self._buf.clear()
        if whence != os.SEEK_SET:
            # size-relative: resolve through a demand reader's seek
            if self._demand is None:
                self._demand = RetryingReader(self._path, 0)
            self._pos = self._demand.seek(pos, whence)
            return self._pos
        self._pos = pos
        self._eof = False
        if self._demand is not None:
            self._demand.seek(pos)
        # else: the readahead restarts lazily at _pos on the next read
        return pos

    def tell(self) -> int:
        return self._pos

    def close(self) -> None:
        if self._closed:
            return
        self._closed = True
        if self._demand is None:
            self._teardown_thread()
        else:
            self._demand.close()
        if self._hits or self._misses:
            faults.REGISTRY.log_line(
                "prefetch", path=self._path, hits=self._hits,
                misses=self._misses, wait_s=round(self._wait_s, 4),
                depth=self._depth)

    @property
    def closed(self) -> bool:
        return self._closed

    def __enter__(self) -> "PrefetchingReader":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


def OpenReadStream(path: str, offset: int = 0,
                   readahead_to: Optional[int] = None) -> IO[bytes]:
    """Open for reading, transparently decompressing by suffix, with
    transient-fault retry (reopen at offset) built in.

    With ``THRILL_TPU_PREFETCH`` > 0 (the default) the stream reads
    ahead of the consumer on a background thread
    (:class:`PrefetchingReader`); ``THRILL_TPU_PREFETCH=0`` restores
    the plain demand reader byte-identically.

    Compressed files do not support nonzero offsets (whole-file
    granularity, like the reference's ReadLines on compressed input).
    """
    if offset and path.endswith(COMPRESSED_SUFFIXES):
        if _scheme(path) in ("file",):
            raise ValueError("cannot seek into compressed file")
    depth = prefetch_depth()
    if depth <= 0:
        return RetryingReader(path, offset)
    return PrefetchingReader(path, offset, depth=depth,
                             readahead_to=readahead_to)


def write_file_atomic(path: str, data: bytes) -> None:
    """Write ``data`` so readers see either the old file or the whole
    new one, never a torn prefix: write to a same-directory temp name,
    fsync, then ``os.replace``. The checkpoint manifest commit
    (api/checkpoint.py) rides this — a manifest present on disk IS the
    epoch's commit record, so partial manifests must be impossible.
    Non-posix schemes (s3://, hdfs://) fall back to a plain write (the
    object stores' PUT is already all-or-nothing)."""
    if _scheme(path) != "file":
        with OpenWriteStream(path) as f:
            f.write(data)
        return
    d = os.path.dirname(path)
    if d:
        os.makedirs(d, exist_ok=True)
    tmp = f"{path}.tmp.{os.getpid()}"
    try:
        with open(tmp, "wb") as f:
            f.write(data)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise


def OpenWriteStream(path: str) -> IO[bytes]:
    if _scheme(path) == "s3":
        from . import s3_file
        return s3_file.s3_open_write(path)
    if _scheme(path) == "hdfs":
        from . import hdfs_file
        return hdfs_file.hdfs_open_write(path)
    if _scheme(path) in ("http", "https"):
        from . import object_store
        return object_store.http_open_write(path)
    d = os.path.dirname(path)
    if d:
        os.makedirs(d, exist_ok=True)
    return _open_filtered(path, "wb")


def _open_filtered(path: str, mode: str) -> IO[bytes]:
    if path.endswith(".gz"):
        return gzip.open(path, mode)
    if path.endswith(".bz2"):
        return bz2.open(path, mode)
    if path.endswith(".xz"):
        return lzma.open(path, mode)
    return open(path, mode)
