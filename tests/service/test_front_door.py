"""Front door: socket admission, shed-load, streamed results.

Pinned acceptance for the network edge (service/front_door.py,
service/client.py — ISSUE 18):

* blob and items pipelines round-trip over a real socket, items
  consumable while the job is still running;
* every rejection is TYPED (kind + retry-after hint) — unknown
  pipeline, rate limit, tenant queue, draining — never a silent drop
  or a hang, and a shed client that honors the hint gets in;
* a client that vanishes mid-stream (SIGKILL-shaped), trickles bytes
  (slow-loris), idles half-open, or stops draining its result stream
  is DROPPED on a deadline — its jobs still complete and other
  tenants never stall;
* graceful drain (and SIGTERM) finishes in-flight jobs, delivers
  their results, typed-rejects new work, then says bye;
* the four new fault sites (service.front_door.accept / .stream,
  net.tcp.client_disconnect, service.front_door.slow_client) arm via
  the standard registry and degrade exactly as documented.
"""

import os
import signal
import socket
import threading
import time

import numpy as np
import pytest

from thrill_tpu.api import Context
from thrill_tpu.common import faults
from thrill_tpu.net.tcp import TcpConnection, _exchange_auth_flag
from thrill_tpu.parallel.mesh import MeshExec
from thrill_tpu.service.client import (FrontDoorClient, Rejected,
                                       RemoteJobError)
from thrill_tpu.service.front_door import FrontDoor

_SERVE_ENV = ("THRILL_TPU_SERVE_PORT", "THRILL_TPU_SERVE_RATE",
              "THRILL_TPU_SERVE_QUEUE", "THRILL_TPU_SERVE_TENANT_QUEUE",
              "THRILL_TPU_SERVE_READ_TIMEOUT_S",
              "THRILL_TPU_SERVE_WRITE_TIMEOUT_S",
              "THRILL_TPU_SERVE_DRAIN_TIMEOUT_S",
              "THRILL_TPU_SERVE_CHUNK", "THRILL_TPU_SERVE_EGRESS_BYTES",
              "THRILL_TPU_SECRET")


@pytest.fixture(autouse=True)
def _clean_registry(monkeypatch):
    for var in _SERVE_ENV:
        monkeypatch.delenv(var, raising=False)
    monkeypatch.delenv(faults.ENV_VAR, raising=False)
    faults.REGISTRY.reset()
    yield
    faults.REGISTRY.reset()


@pytest.fixture
def ctx():
    c = Context(MeshExec(num_workers=2))
    yield c
    c.close()


# module-level pipelines: stable identities share exchange-site caches
def _echo(ctx2, args):
    return args


def _slow(ctx2, args):
    time.sleep(float(args["s"]))
    return args["s"]


def _mesh_sum(ctx2, args):
    return int(ctx2.Distribute(
        np.arange(int(args["n"]), dtype=np.int64)).Sum())


def _gen(ctx2, args):
    for i in range(int(args["k"])):
        yield i * i


def _slow_gen(ctx2, args):
    for i in range(int(args["k"])):
        time.sleep(0.05)
        yield i


def _big(ctx2, args):
    return b"\x5a" * int(args["nbytes"])


def _front(ctx):
    fd = FrontDoor(ctx, port=0)
    for name, fn in (("echo", _echo), ("slow", _slow),
                     ("mesh_sum", _mesh_sum), ("gen", _gen),
                     ("slow_gen", _slow_gen), ("big", _big)):
        fd.register(name, fn)
    return fd


def _wait(pred, timeout_s=8.0, what="condition"):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if pred():
            return
        time.sleep(0.02)
    raise AssertionError(f"timed out waiting for {what}")


def _raw_client(fd, tenant="raw"):
    """A protocol-level client with NO reader thread: the adversarial
    tests (slow-loris, non-draining reader) need direct socket
    control the real client library refuses to give."""
    sock = socket.create_connection(("127.0.0.1", fd.port), timeout=10)
    conn = TcpConnection(sock)
    _exchange_auth_flag(conn, False)
    conn.send(("hello", {"tenant": tenant, "proto": 1}))
    frame = conn.recv_deadline(10.0)
    assert frame[0] == "welcome"
    return conn


# -- round trips ----------------------------------------------------------

def test_blob_and_items_round_trip_mixed_tenants(ctx):
    fd = _front(ctx)
    with FrontDoorClient("127.0.0.1", fd.port, tenant="alice") as a, \
            FrontDoorClient("127.0.0.1", fd.port, tenant="bob") as b:
        j1 = a.submit("mesh_sum", {"n": 64})
        j2 = b.submit("gen", {"k": 5})
        j3 = a.submit("echo", {"x": [1, 2, 3], "s": "hi"})
        assert j1.result(120) == int(np.arange(64).sum())
        assert list(j2.chunks(timeout=60)) == [0, 1, 4, 9, 16]
        assert j2.mode == "items"
        assert j3.result(60) == {"x": [1, 2, 3], "s": "hi"}
    assert fd.jobs_submitted == 3 and fd.jobs_rejected == 0
    assert fd.chunks_sent >= 7    # 5 items + >=1 chunk per blob
    fd.close()


def test_items_stream_consumable_mid_job(ctx):
    fd = _front(ctx)
    with FrontDoorClient("127.0.0.1", fd.port) as c:
        job = c.submit("slow_gen", {"k": 6})
        it = job.chunks(timeout=30)
        first = next(it)                 # arrives ~0.05s in: the job
        assert first == 0                # is still RUNNING server-side
        with job._cv:
            assert not job._done
        assert list(it) == [1, 2, 3, 4, 5]
    fd.close()


def test_authenticated_handshake_and_wrong_secret(ctx, monkeypatch):
    monkeypatch.setenv("THRILL_TPU_SECRET", "s3cr3t")
    fd = _front(ctx)
    with FrontDoorClient("127.0.0.1", fd.port) as c:   # env secret
        assert c.submit("echo", 7).result(30) == 7
    from thrill_tpu.net import wire
    with pytest.raises(wire.AuthError):
        FrontDoorClient("127.0.0.1", fd.port, secret=b"wrong")
    fd.close()


# -- typed shed-load ------------------------------------------------------

def test_unknown_pipeline_is_typed_reject(ctx):
    fd = _front(ctx)
    with FrontDoorClient("127.0.0.1", fd.port) as c:
        with pytest.raises(Rejected) as ei:
            c.submit("no_such_pipeline", None).result(30)
        assert ei.value.kind == "unknown_pipeline"
    assert fd.jobs_rejected == 1
    fd.close()


def test_rate_limit_reject_then_retry_after_success(ctx, monkeypatch):
    monkeypatch.setenv("THRILL_TPU_SERVE_RATE", "alice=4:1")
    fd = _front(ctx)
    with FrontDoorClient("127.0.0.1", fd.port, tenant="alice") as c:
        assert c.submit("echo", 1).result(60) == 1   # takes the token
        with pytest.raises(Rejected) as ei:
            c.submit("echo", 2).result(30)
        assert ei.value.kind == "rate_limited"
        assert ei.value.retry_after_s > 0
        # honoring the hint (max of hint and jitter) gets the job in
        job = c.submit_retry("echo", 3, attempts=8, seed=7)
        assert job.result(60) == 3
    assert ctx.overall_stats()["jobs_rate_limited"] >= 1
    fd.close()


def test_tenant_queue_cap_is_typed_and_per_tenant(ctx, monkeypatch):
    from thrill_tpu.service.scheduler import TenantQueueFull
    monkeypatch.setenv("THRILL_TPU_SERVE_TENANT_QUEUE", "1")
    started, release = threading.Event(), threading.Event()

    def _hold(c2):
        started.set()
        release.wait(30)

    hold = ctx.submit(_hold, tenant="alice", name="hold")
    assert started.wait(30)     # hold is RUNNING, not queued: the
    queued = ctx.submit(lambda c2: 1, tenant="alice", name="q1")
    shed = ctx.submit(lambda c2: 2, tenant="alice", name="q2")
    other = ctx.submit(lambda c2: 3, tenant="bob", name="b1")
    assert shed.done()
    err = shed.exception(0)
    assert isinstance(err, TenantQueueFull)
    assert err.kind == "tenant_queue_full" and err.tenant == "alice"
    assert err.retry_after_s >= 0
    release.set()
    assert queued.result(60) == 1 and other.result(60) == 3
    hold.result(60)


# -- misbehaving clients --------------------------------------------------

def test_client_vanish_mid_stream_other_tenant_unaffected(ctx):
    fd = _front(ctx)
    a = FrontDoorClient("127.0.0.1", fd.port, tenant="alice")
    job = a.submit("slow_gen", {"k": 12})
    assert next(job.chunks(timeout=30)) == 0
    a.conn.sock.close()          # SIGKILL-shaped: no bye, just gone
    with FrontDoorClient("127.0.0.1", fd.port, tenant="bob") as b:
        assert b.submit("echo", "ok").result(60) == "ok"
    _wait(lambda: fd.conns_dropped >= 1, what="vanished conn dropped")
    # the abandoned job drains to a no-op, never wedging the
    # dispatcher: a later job on a fresh conn still runs
    with FrontDoorClient("127.0.0.1", fd.port, tenant="carol") as c:
        assert c.submit("echo", 1).result(60) == 1
    fd.close()


def test_slow_loris_read_deadline_drops(ctx):
    fd = _front(ctx)
    conn = _raw_client(fd)
    conn.sock.sendall(b"\x20\x00")    # 2 of 4 header bytes, then stall
    _wait(lambda: fd.slow_clients >= 1, what="slow-loris detection")
    _wait(lambda: fd.conns_dropped >= 1, what="slow-loris drop")
    conn.close()
    fd.close()


def test_half_open_idle_client_dropped(ctx, monkeypatch):
    monkeypatch.setenv("THRILL_TPU_SERVE_READ_TIMEOUT_S", "0.3")
    fd = _front(ctx)
    c = FrontDoorClient("127.0.0.1", fd.port)
    _wait(lambda: fd.conns_dropped >= 1, what="half-open drop")
    assert fd.slow_clients == 0       # idle is idle, not slow-loris
    c.close()
    fd.close()


def test_slow_client_shed_on_egress_budget(ctx, monkeypatch):
    monkeypatch.setenv("THRILL_TPU_SERVE_WRITE_TIMEOUT_S", "0.4")
    monkeypatch.setenv("THRILL_TPU_SERVE_CHUNK", "8192")
    monkeypatch.setenv("THRILL_TPU_SERVE_EGRESS_BYTES", "65536")
    fd = _front(ctx)
    conn = _raw_client(fd)
    conn.sock.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, 4096)
    conn.send(("submit", {"id": 1, "pipeline": "big",
                          "args": {"nbytes": 8 << 20}}))
    # never read: the 8 MiB stream must hit the write deadline, shed
    # THIS connection, and leave the dispatcher free for bob
    _wait(lambda: fd.slow_clients >= 1, timeout_s=30,
          what="slow-client shed")
    with FrontDoorClient("127.0.0.1", fd.port, tenant="bob") as b:
        assert b.submit("echo", "ok").result(60) == "ok"
    conn.close()
    fd.close()


def test_deadline_expired_is_typed_error(ctx):
    fd = _front(ctx)
    with FrontDoorClient("127.0.0.1", fd.port) as c:
        first = c.submit("slow", {"s": 0.4})
        doomed = c.submit("echo", 1, deadline_s=0.05)
        with pytest.raises(RemoteJobError) as ei:
            doomed.result(60)
        assert ei.value.kind == "deadline"
        assert first.result(60) == 0.4
    assert fd.deadline_expired == 1
    fd.close()


# -- drain / SIGTERM ------------------------------------------------------

def test_graceful_drain_completes_inflight_rejects_new(ctx):
    fd = _front(ctx)
    c = FrontDoorClient("127.0.0.1", fd.port)
    inflight = c.submit("slow", {"s": 0.4})
    inflight.wait_accepted(30)   # drain's contract covers ACCEPTED
    got = {}                     # jobs; an unacked submit may race it

    def _drain():
        got["clean"] = fd.drain(20)

    t = threading.Thread(target=_drain)
    t.start()
    time.sleep(0.1)                     # drain is now waiting on the job
    with pytest.raises(Rejected) as ei:
        c.submit("echo", 1).result(30)
    assert ei.value.kind == "draining"
    assert ei.value.retry_after_s > 0
    assert inflight.result(60) == 0.4   # in-flight work DELIVERED
    t.join(30)
    assert got["clean"] is True
    c.close()
    fd.close()


def test_sigterm_triggers_drain(ctx):
    fd = _front(ctx)
    prev = signal.getsignal(signal.SIGTERM)
    try:
        fd.install_sigterm()
        os.kill(os.getpid(), signal.SIGTERM)
        assert fd.drained.wait(20)
    finally:
        signal.signal(signal.SIGTERM, prev)
    fd.close()


# -- fault sites ----------------------------------------------------------

def test_accept_fault_redialed_by_client(ctx):
    fd = _front(ctx)
    with faults.inject("service.front_door.accept", n=1):
        with FrontDoorClient("127.0.0.1", fd.port) as c:
            assert c.submit("echo", 5).result(60) == 5
    assert faults.REGISTRY.injected >= 1
    fd.close()


def test_stream_fault_typed_error_conn_survives(ctx):
    fd = _front(ctx)
    with FrontDoorClient("127.0.0.1", fd.port) as c:
        with faults.inject("service.front_door.stream", n=1):
            with pytest.raises(RemoteJobError) as ei:
                c.submit("gen", {"k": 3}).result(60)
            assert ei.value.kind == "stream"
        # the SAME connection keeps working: a torn stream is a
        # stream failure, not a connection or scheduler failure
        assert c.submit("echo", "after").result(60) == "after"
        # read while the client is still connected: once it closes, the
        # server's reader counts that close as a drop, sooner or later
        assert fd.conns_dropped == 0
    fd.close()


def test_injected_client_disconnect_drops_conn(ctx):
    fd = _front(ctx)
    with faults.inject("net.tcp.client_disconnect", n=1):
        c = FrontDoorClient("127.0.0.1", fd.port)
        _wait(lambda: fd.conns_dropped >= 1,
              what="injected disconnect drop")
        c.close()
    with FrontDoorClient("127.0.0.1", fd.port) as c2:
        assert c2.submit("echo", 1).result(60) == 1
    fd.close()


def test_injected_slow_client_site_drops(ctx):
    fd = _front(ctx)
    with faults.inject("service.front_door.slow_client", n=1):
        c = FrontDoorClient("127.0.0.1", fd.port)
        c.submit("echo", 1)          # forces a server->client frame
        _wait(lambda: fd.slow_clients >= 1, what="slow-client fire")
        c.close()
    fd.close()


# -- chaos ---------------------------------------------------------------

_FD_SITES = ["service.front_door.accept", "service.front_door.stream",
             "net.tcp.client_disconnect",
             "service.front_door.slow_client"]


def _edge_storm(ctx, seed: int):
    """Arm a seeded mix of the edge fault sites and drive real-socket
    traffic through them. Invariants: every submit RESOLVES (result,
    typed Rejected/RemoteJobError, or a connection error a redial
    recovers from), and the server Context survives to run a clean
    job after the storm."""
    import random
    rng = random.Random(seed)
    armed = rng.sample(_FD_SITES, k=rng.randint(1, 3))
    spec = ";".join(f"{s}:p=0.5:n=2:seed={seed}" for s in armed)
    fd = _front(ctx)
    outcomes = []
    with faults.inject(spec.split(";")[0]):
        os.environ[faults.ENV_VAR] = spec
        for j in range(6):
            try:
                with FrontDoorClient("127.0.0.1", fd.port,
                                     tenant=f"t{j % 2}") as c:
                    got = c.submit("echo", j).result(30)
                    outcomes.append(("ok", got == j))
            except (Rejected, RemoteJobError) as e:
                outcomes.append(("typed", type(e).__name__))
            except (ConnectionError, OSError, TimeoutError) as e:
                outcomes.append(("conn", type(e).__name__))
    os.environ.pop(faults.ENV_VAR, None)
    assert len(outcomes) == 6           # nothing hung, nothing silent
    with FrontDoorClient("127.0.0.1", fd.port) as c:
        assert c.submit("echo", "clean").result(60) == "clean"
    fd.close()


def test_front_door_chaos_seed0(ctx):
    _edge_storm(ctx, 0)


@pytest.mark.slow
@pytest.mark.chaos
@pytest.mark.parametrize("seed", range(1, 5))
def test_front_door_chaos_sweep(ctx, seed):
    _edge_storm(ctx, seed)
