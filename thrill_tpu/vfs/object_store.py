"""Object-store transport on stdlib HTTP: ranged GETs, streamed PUTs.

Reference: thrill/vfs/s3_file.cpp — the reference rides vendored libs3,
but the wire protocol underneath is plain HTTP: ListObjectsV2 for Glob,
``Range: bytes=N-`` GETs for offset reads, PUT (single-shot or the
multipart protocol) for writes. This module speaks that protocol with
``http.client`` only, so the out-of-core tier runs against genuinely
slow remote storage with zero new dependencies:

* ``http://`` / ``https://`` paths dispatch here behind the vfs seam
  (file_io.Glob/_open_at/OpenWriteStream) — ReadLines/ReadBinary,
  checkpoint shards, flight dumps and the plan store are all
  scheme-agnostic above that seam, so they work unmodified;
* ``s3://`` paths fall back here when boto3 is absent AND
  ``THRILL_TPU_OBJECT_STORE_ENDPOINT`` names an S3-compatible endpoint
  (path-style REST: ``<endpoint>/<bucket>/<key>``).

Retry story: this layer classifies, the shared policy retries. A
response status rides on the raised exception as ``http_status`` and
``common/retry.py`` classifies 5xx/408/429 transient (404 and 403 map
to FileNotFoundError/PermissionError, which are already permanent);
connection resets and timeouts are OSErrors and retry as today. Reads
recover by REOPENING the range at the tracked offset — the
RetryingReader wrapping this stream already does exactly that — and a
server that ignores ``Range`` fails loudly (a silent restart from byte
0 would corrupt the resumed stream).

Accounting: every GET bumps ``remote_gets`` and records its
time-to-first-byte (``get_p50_ms()``); every PUT/part bumps
``remote_puts`` (common/iostats.py) — the perf sentinel pins these
exactly, so a silent fallback to whole-file reads fails a counter
diff.
"""

from __future__ import annotations

import collections
import http.client
import io
import os
import threading
import time
import urllib.parse
import xml.etree.ElementTree as ET
from typing import IO, List, Optional, Tuple

from ..common import faults
from ..common.iostats import IO as _IOSTATS
from ..common.retry import default_policy

# scheme-level injection sites. raise mode exercises the recovery
# ladder (retry the request / reopen the range at the tracked offset);
# ``delay=`` mode fires once per HTTP REQUEST, which is exactly the
# latency regime of a real object store (each GET costs ~RTT, however
# many stream reads it feeds)
_F_READ = faults.declare("vfs.http.read")
_F_WRITE = faults.declare("vfs.http.write")
_F_LIST = faults.declare("vfs.http.list")


def endpoint() -> Optional[str]:
    """S3-REST endpoint used for ``s3://`` paths when boto3 is absent:
    ``THRILL_TPU_OBJECT_STORE_ENDPOINT`` (or ``AWS_ENDPOINT_URL``),
    e.g. ``http://127.0.0.1:9000``."""
    ep = os.environ.get("THRILL_TPU_OBJECT_STORE_ENDPOINT") \
        or os.environ.get("AWS_ENDPOINT_URL")
    return ep.rstrip("/") if ep else None


def part_size() -> int:
    """THRILL_TPU_OBJECT_STORE_PART: streamed-PUT part threshold. At or
    above this many buffered bytes a write switches to the multipart
    protocol, so flush RAM is bounded by one part, not the object
    (multi-GB checkpoint shards must not double RAM at flush time).
    Default 8 MiB; floor 64 KiB so tests can exercise multipart
    cheaply (real S3 requires 5 MiB non-final parts — set accordingly
    against real endpoints)."""
    try:
        v = int(os.environ.get("THRILL_TPU_OBJECT_STORE_PART", "")
                or (8 << 20))
    except ValueError:
        v = 8 << 20
    return max(1 << 16, v)


def timeout_s() -> float:
    """THRILL_TPU_OBJECT_STORE_TIMEOUT: per-request socket timeout."""
    try:
        return float(os.environ.get("THRILL_TPU_OBJECT_STORE_TIMEOUT",
                                    "") or 60.0)
    except ValueError:
        return 60.0


class HTTPStatusError(OSError):
    """Non-2xx response. ``http_status`` drives retry classification
    (common/retry.py: 5xx/408/429 transient, other 4xx permanent)."""

    def __init__(self, status: int, url: str, detail: str = "") -> None:
        super().__init__(f"HTTP {status} for {url}"
                         + (f": {detail}" if detail else ""))
        self.http_status = status
        self.url = url


# -- GET latency ledger (time-to-first-byte per request) ----------------
_LAT_LOCK = threading.Lock()
_LAT_MS: collections.deque = collections.deque(maxlen=4096)


def _record_get(ms: float) -> None:
    with _LAT_LOCK:
        _LAT_MS.append(ms)


def get_p50_ms() -> float:
    """Median GET time-to-first-byte over the recent window (bench's
    ``em_remote_get_p50_ms``); 0.0 when no GETs ran."""
    with _LAT_LOCK:
        lat = sorted(_LAT_MS)
    return lat[len(lat) // 2] if lat else 0.0


def latency_reset() -> None:
    with _LAT_LOCK:
        _LAT_MS.clear()


# -- low-level request plumbing -----------------------------------------
def _parse(url: str) -> Tuple[bool, str, int, str]:
    """(https?, host, port, path-with-query) for one absolute URL."""
    u = urllib.parse.urlsplit(url)
    if u.scheme not in ("http", "https"):
        raise ValueError(f"not an http(s) url: {url!r}")
    if not u.hostname:
        raise ValueError(f"http url has no host: {url!r}")
    secure = u.scheme == "https"
    port = u.port or (443 if secure else 80)
    path = u.path or "/"
    if u.query:
        path += "?" + u.query
    return secure, u.hostname, port, path


def _connect(secure: bool, host: str, port: int) -> http.client.HTTPConnection:
    cls = http.client.HTTPSConnection if secure \
        else http.client.HTTPConnection
    return cls(host, port, timeout=timeout_s())


def _raise_for_status(status: int, url: str, body: bytes = b"") -> None:
    """Map a failure status onto the retry classes: 404/403 become the
    (permanent) errno exceptions the rest of the stack already knows;
    everything else carries ``http_status`` for classify()."""
    if status == 404:
        e: OSError = FileNotFoundError(f"object not found: {url}")
    elif status == 403:
        e = PermissionError(f"access denied: {url}")
    else:
        e = HTTPStatusError(status, url, body[:200].decode(
            "utf-8", "replace"))
    e.http_status = status  # type: ignore[attr-defined]
    raise e


def _request(method: str, url: str, body: bytes = b"",
             headers: Optional[dict] = None,
             ok: Tuple[int, ...] = (200,)) -> Tuple[int, dict, bytes]:
    """One buffered request/response round trip on a fresh connection
    (fresh per request: trivially thread-safe, and against a local
    mock/MinIO the connect cost is noise next to the injected
    latency). Returns (status, lowercased headers, body)."""
    secure, host, port, path = _parse(url)
    conn = _connect(secure, host, port)
    try:
        hdrs = {"Content-Length": str(len(body))}
        if headers:
            hdrs.update(headers)
        conn.request(method, path, body=body or None, headers=hdrs)
        resp = conn.getresponse()
        data = resp.read()
        status = resp.status
        rhdrs = {k.lower(): v for k, v in resp.getheaders()}
    except http.client.HTTPException as e:
        # not an OSError by inheritance, but it IS a broken transport
        # conversation — re-raise as one so the retry policy sees it
        raise ConnectionResetError(f"{method} {url}: {e!r}") from e
    finally:
        conn.close()
    if status not in ok:
        _raise_for_status(status, url, data)
    return status, rhdrs, data


# -- ranged reads -------------------------------------------------------
class _HttpReadStream(io.RawIOBase):
    """Streamed ranged GET over one object. One HTTP request per
    stream; the wrapping RetryingReader recovers from mid-stream
    failures by reopening at the tracked offset (a fresh ranged GET)."""

    def __init__(self, url: str, offset: int = 0) -> None:
        faults.check(_F_READ, url=url, offset=offset)
        self._url = url
        secure, host, port, path = _parse(url)
        self._conn = _connect(secure, host, port)
        t0 = time.perf_counter()
        try:
            headers = {}
            if offset:
                headers["Range"] = f"bytes={offset}-"
            self._conn.request("GET", path, headers=headers)
            resp = self._conn.getresponse()
        except http.client.HTTPException as e:
            self._conn.close()
            raise ConnectionResetError(f"GET {url}: {e!r}") from e
        except BaseException:
            self._conn.close()
            raise
        _IOSTATS.add(remote_gets=1)
        _record_get((time.perf_counter() - t0) * 1e3)
        if offset and resp.status == 416:
            # ranged open at/past EOF: a local file opens fine there
            # and reads b"" — mirror that (S3 416s unsatisfiable
            # ranges; callers like the delimited-range scanners probe
            # exactly-at-EOF offsets legitimately)
            resp.read()
            self._conn.close()
            self._resp = None
            return
        if resp.status not in (200, 206):
            body = resp.read()
            self._conn.close()
            _raise_for_status(resp.status, url, body)
        if offset and resp.status != 206:
            # the server ignored Range: reading from byte 0 here would
            # silently corrupt a resumed stream — fail LOUDLY instead
            # (status 200 classifies permanent, so no retry storm)
            self._conn.close()
            raise HTTPStatusError(
                200, url, f"server ignored Range: bytes={offset}-")
        self._resp = resp

    def readable(self) -> bool:
        return True

    def read(self, n: int = -1) -> bytes:
        if self._resp is None:          # opened at/past EOF
            return b""
        try:
            return self._resp.read(None if n is None or n < 0 else n)
        except http.client.HTTPException as e:
            raise ConnectionResetError(
                f"read {self._url}: {e!r}") from e

    def readinto(self, b) -> int:
        data = self.read(len(b))
        b[:len(data)] = data
        return len(data)

    def close(self) -> None:
        try:
            self._conn.close()
        finally:
            super().close()


def http_open_read(url: str, offset: int = 0) -> IO[bytes]:
    return io.BufferedReader(_HttpReadStream(url, offset))


# -- listing (ListObjectsV2) --------------------------------------------
def _split_bucket(url: str) -> Tuple[str, str, str]:
    """``http://host:port/bucket/key...`` → (base, bucket, key)."""
    u = urllib.parse.urlsplit(url)
    base = f"{u.scheme}://{u.netloc}"
    rest = u.path.lstrip("/")
    bucket, _, key = rest.partition("/")
    if not bucket:
        raise ValueError(f"object url has no bucket: {url!r}")
    return base, bucket, key


def _xml_text(elem, tag: str, default: str = "") -> str:
    # S3 XML arrives both with and without the aws namespace; match on
    # the local tag name
    for child in elem.iter():
        if child.tag == tag or child.tag.endswith("}" + tag):
            return child.text or default
    return default


def list_objects(base: str, bucket: str,
                 prefix: str) -> List[Tuple[str, int]]:
    """ListObjectsV2 with pagination: (key, size) for every object
    under ``prefix``, sorted by key."""
    out: List[Tuple[str, int]] = []
    token = None
    policy = default_policy()
    while True:
        q = {"list-type": "2", "prefix": prefix}
        if token:
            q["continuation-token"] = token
        url = f"{base}/{bucket}?{urllib.parse.urlencode(q)}"

        def op(url=url):
            faults.check(_F_LIST, url=url)
            return _request("GET", url)
        _, _, body = policy.run(op, what="vfs.http.list")
        root = ET.fromstring(body)
        for elem in root.iter():
            if elem.tag == "Contents" or elem.tag.endswith("}Contents"):
                k = _xml_text(elem, "Key")
                if k:
                    out.append((k, int(_xml_text(elem, "Size", "0"))))
        if _xml_text(root, "IsTruncated") != "true":
            break
        token = _xml_text(root, "NextContinuationToken")
        if not token:
            break
    out.sort()
    return out


def http_glob(path_or_glob: str) -> List[Tuple[str, int]]:
    """(url, size) matching the path or a single-trailing-'*' prefix
    glob — the s3_glob contract over the REST listing."""
    base, bucket, key = _split_bucket(path_or_glob)
    if "*" in key:
        star = key.index("*")
        if "*" in key[star + 1:]:
            raise ValueError(
                "object-store glob supports a single trailing '*'")
        prefix, suffix = key[:star], key[star + 1:]
    else:
        prefix, suffix = key, ""
    out = [(f"{base}/{bucket}/{k}", sz)
           for k, sz in list_objects(base, bucket, prefix)
           if not suffix or k.endswith(suffix)]
    out.sort()
    return out


# -- streamed writes ----------------------------------------------------
class _ObjectWriteStream(io.RawIOBase):
    """Streamed PUT with bounded RAM and an abort-on-error contract —
    the REST twin of s3_file._S3WriteStream. Below one part: a single
    PUT on close. At or past the part threshold: the S3 multipart
    protocol (initiate / per-part PUT / complete), each request retried
    under the shared policy (a part PUT is idempotent — same part
    number, same bytes). ``abort()`` drops a half-written upload so a
    failed producer never publishes a truncated object."""

    def __init__(self, url: str,
                 part: Optional[int] = None) -> None:
        self._url = url
        self._part_size = part_size() if part is None else max(1 << 16,
                                                               int(part))
        self._pending = bytearray()
        self._upload_id: Optional[str] = None
        self._parts: List[Tuple[int, str]] = []   # (number, etag)
        self._aborted = False
        self._policy = default_policy()

    def writable(self) -> bool:
        return True

    def write(self, b) -> int:
        if self._aborted:
            return len(b)           # see _S3WriteStream.write
        self._pending += b
        while len(self._pending) >= self._part_size:
            chunk = bytes(self._pending[:self._part_size])
            del self._pending[:self._part_size]
            self._upload_part(chunk)
        return len(b)

    def _put(self, url: str, body: bytes, what: str,
             headers: Optional[dict] = None) -> dict:
        def op():
            faults.check(_F_WRITE, url=url, nbytes=len(body))
            _, hdrs, _ = self._request_put(url, body, headers)
            return hdrs
        hdrs = self._policy.run(op, what=what)
        _IOSTATS.add(remote_puts=1)
        return hdrs

    @staticmethod
    def _request_put(url: str, body: bytes,
                     headers: Optional[dict]) -> Tuple[int, dict, bytes]:
        return _request("PUT", url, body=body, headers=headers,
                        ok=(200, 201, 204))

    def _upload_part(self, data: bytes) -> None:
        if self._upload_id is None:
            def op():
                faults.check(_F_WRITE, url=self._url, op="initiate")
                _, _, body = _request("POST", self._url + "?uploads")
                return _xml_text(ET.fromstring(body), "UploadId")
            self._upload_id = self._policy.run(
                op, what="vfs.http.write")
            if not self._upload_id:
                raise HTTPStatusError(
                    500, self._url, "initiate returned no UploadId")
        num = len(self._parts) + 1
        q = urllib.parse.urlencode(
            {"partNumber": str(num), "uploadId": self._upload_id})
        hdrs = self._put(f"{self._url}?{q}", data, "vfs.http.write")
        self._parts.append((num, hdrs.get("etag", f'"{num}"')))
        # the same part-size growth rule as the boto3 path: past 5000
        # parts, double every 500 so the 10,000-part cap covers the
        # 5 TiB object maximum while pending RAM grows with the object
        if num >= 5000 and num % 500 == 0 \
                and self._part_size < (5 << 30):
            self._part_size = min(self._part_size * 2, 5 << 30)

    def abort(self) -> None:
        self._aborted = True
        self._pending = bytearray()
        if self._upload_id is not None:
            uid, self._upload_id = self._upload_id, None
            try:
                q = urllib.parse.urlencode({"uploadId": uid})
                _request("DELETE", f"{self._url}?{q}", ok=(200, 204))
            except Exception:
                pass                 # best effort; never mask the cause

    def close(self) -> None:
        if self.closed:
            return
        try:
            if self._aborted:
                return
            if self._upload_id is None:
                self._put(self._url, bytes(self._pending),
                          "vfs.http.write")
                self._pending = bytearray()
            else:
                try:
                    if self._pending:
                        self._upload_part(bytes(self._pending))
                        self._pending = bytearray()
                    parts = "".join(
                        f"<Part><PartNumber>{n}</PartNumber>"
                        f"<ETag>{etag}</ETag></Part>"
                        for n, etag in self._parts)
                    body = (f"<CompleteMultipartUpload>{parts}"
                            f"</CompleteMultipartUpload>"
                            ).encode("utf-8")
                    q = urllib.parse.urlencode(
                        {"uploadId": self._upload_id})

                    def op():
                        faults.check(_F_WRITE, url=self._url,
                                     op="complete")
                        _request("POST", f"{self._url}?{q}", body=body)
                    self._policy.run(op, what="vfs.http.write")
                    self._upload_id = None
                except Exception:
                    self.abort()
                    raise
        finally:
            super().close()


class _AbortingWriter(io.BufferedWriter):
    """``with`` block aborts the upload when the body raises — an
    exception must never publish a truncated object as complete."""

    def __exit__(self, exc_type, exc, tb):
        if exc_type is not None:
            try:
                self.raw.abort()
            except Exception:
                pass
        return super().__exit__(exc_type, exc, tb)


def http_open_write(url: str) -> IO[bytes]:
    return _AbortingWriter(_ObjectWriteStream(url))


# -- s3:// fallback plumbing --------------------------------------------
def s3_rest_url(path: str) -> str:
    """s3://bucket/key → <endpoint>/bucket/key (path-style REST).
    Raises NotImplementedError when no endpoint is configured — the
    boto3 gate's message stays authoritative in that case."""
    ep = endpoint()
    if ep is None:
        raise NotImplementedError(
            "s3:// REST fallback needs THRILL_TPU_OBJECT_STORE_ENDPOINT")
    assert path.startswith("s3://"), path
    return f"{ep}/{path[len('s3://'):]}"
