"""When uploads and programs are done on the device (ISSUE 38): the
``transfer`` and ``device`` records of ``common/trace.py DeviceWatcher``,
children of the ``upload`` and ``dispatch`` spans. On a CPU mesh: the
structure, the effective-start rule and the donation hazard, never a time
worth reading."""

import importlib.util
import os
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from thrill_tpu.api import Context, RunLocalMock
from thrill_tpu.common import trace
from thrill_tpu.parallel.mesh import MeshExec

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
SIX = ("upload_s", "dispatch_call_s", "sync_wait_s", "fetch_s",
       "host_plan_s", "compile_s")


@pytest.fixture(autouse=True)
def device_programs(monkeypatch):
    """What the chip runs: the jitted device programs, not the CPU's
    native host paths (chipbench/run.py --rehearse sets the same)."""
    monkeypatch.setenv("THRILL_TPU_HOST_RADIX", "0")
    monkeypatch.setenv("THRILL_TPU_SORT_U32", "1")
    monkeypatch.setenv("THRILL_TPU_PACK_MOVE", "1")


def record_key(r):
    return r["key"]


def records(n=512, seed=3):
    rng = np.random.default_rng(seed)
    return {"key": rng.integers(0, 256, size=(n, 10), dtype=np.uint8),
            "value": rng.integers(0, 256, size=(n, 90), dtype=np.uint8)}


def sort_job(ctx, inp):
    got = ctx.Distribute(inp).Sort(key_fn=record_key).AllGatherArrays()
    return {k: np.asarray(v) for k, v in got.items()}


def spans(ctx):
    ctx.mesh_exec.flush_device_records()
    return [r for r in ctx.tracer.ring if r.get("kind") != "instant"]


def end_s(rec):
    return rec["t0_s"] + rec["dur_us"] / 1e6


def children(recs, cat):
    """Records of ``cat`` by the span they are parented to."""
    out = {}
    for r in recs:
        if r["cat"] == cat:
            out.setdefault(r.get("parent"), []).append(r)
    return out


@pytest.mark.parametrize("W", [1, 2])
def test_every_dispatch_and_upload_gets_one_record(W):
    ctx = Context(MeshExec(num_workers=W))
    try:
        for seed in (1, 2):
            sort_job(ctx, records(seed=seed))
        recs = spans(ctx)
    finally:
        ctx.close()
    device, transfer = children(recs, "device"), children(recs, "transfer")
    dispatches = [r for r in recs if r["cat"] == "dispatch"]
    uploads = [r for r in recs if r["cat"] == "upload"]
    assert dispatches and uploads
    assert sorted(device) == sorted(r["span"] for r in dispatches)
    assert sorted(transfer) == sorted(r["span"] for r in uploads)
    by_id = {r["span"]: r for r in recs}
    for parent, got in list(device.items()) + list(transfer.items()):
        assert len(got) == 1
        rec, above = got[0], by_id[parent]
        assert rec["name"] == above["name"]
        assert rec["t0_s"] >= above["t0_s"] and rec["dur_us"] >= 0
        assert "error" not in rec and "donated" not in rec
        # emit_span places ts by a second clock read: microseconds apart
        assert rec["ts"] >= above["ts"] - 1000
    for up in uploads:
        put = transfer[up["span"]][0]
        assert put["t0_s"] == up["t0_s"]
        assert {k: put[k] for k in ("bytes", "shape", "dtype")} \
            == {k: up[k] for k in ("bytes", "shape", "dtype")}


def test_a_cached_small_put_gets_no_second_record():
    ctx = Context(MeshExec(num_workers=1))
    try:
        mex = ctx.mesh_exec
        a = mex.put_small(np.arange(4))
        assert mex.put_small(np.arange(4)) is a      # the cache's hit
        recs = spans(ctx)
    finally:
        ctx.close()
    assert [r["cat"] for r in recs] == ["upload", "transfer"]


def test_the_effective_start():
    """A program starts no earlier than its dispatch, the previous
    program's ready and the ready of every transfer it reads; an
    argument of unknown origin counts every transfer before it."""
    ctx = Context(MeshExec(num_workers=1))
    try:
        mex = ctx.mesh_exec
        inc = mex.cached(("inc",), lambda: mex.smap(lambda x: x + 1, 1))
        add = mex.cached(("add",), lambda: mex.smap(lambda x, y: x + y, 2))
        x = mex.put(np.zeros((1, 1 << 16), np.float32))
        y = inc(x)
        z = inc(y)
        eager = jnp.ones((1, 1 << 16), np.float32)       # no record
        late = mex.put(np.ones((1, 8), np.float32))      # not read below
        w = add(z, eager)
        assert float(w.sum()) == 3 * (1 << 16)
        recs = spans(ctx)
        del late
    finally:
        ctx.close()
    transfers = [r for r in recs if r["cat"] == "transfer"]
    dispatches = {r["span"]: r for r in recs if r["cat"] == "dispatch"}
    device = [r for r in recs if r["cat"] == "device"]
    assert [r["name"] for r in device] == ["inc", "inc", "add"]
    first, second, third = device
    for rec in device:
        assert rec["t0_s"] >= dispatches[rec["parent"]]["t0_s"]
    # x's transfer, then the previous program, then every transfer
    assert first["t0_s"] >= end_s(transfers[0])
    assert second["t0_s"] >= end_s(first)
    assert third["t0_s"] >= max(end_s(second), end_s(transfers[1]))


def test_the_effective_start_reads_transfers_by_identity():
    """A program that reads only what a program made does not wait for
    a transfer still under way that it does not read."""
    import weakref
    tr = trace.Tracer(ring=64, enabled=True)
    w = trace.DeviceWatcher(tr)
    made = jnp.zeros(4)          # a program's output the watcher saw
    w._remember([made])
    moving = jnp.zeros(8)        # a transfer that ends an hour later
    with tr.span("dispatch", "p") as dp:
        pass
    late = dp.t0 + 3600.0
    w._transfers[id(moving)] = (weakref.ref(moving), late)
    w._last_transfer = late
    at = dp.t0 + 0.001          # the dispatch call's return

    def start(*args, **kwargs):
        return w._start(dp, ((args, kwargs), at), 0)

    assert start(made, 3) == at
    assert start(made, x=moving) == late
    # an argument the watcher never saw: every transfer before counts
    assert start(jnp.ones(2)) == late
    # the previous program's ready
    w._last_ready = at + 1.0
    assert start(made) == at + 1.0
    # a transfer on the device before the dispatch returned is let go
    w._transfers[id(made)] = (weakref.ref(made), at - 1e-4)
    start(made)
    assert list(w._transfers) == [id(moving)]


def test_a_loop_replayed_through_donating_twins_closes_its_records(
        monkeypatch):
    monkeypatch.setenv("THRILL_TPU_LOOP_FORI", "0")
    monkeypatch.setenv("THRILL_TPU_LOOP_DONATE", "1")   # off on a CPU
    spec = importlib.util.spec_from_file_location(
        "loop_index_plan", os.path.join(_ROOT, "tests", "api",
                                        "test_loop_index_plan.py"))
    loops = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(loops)
    seen = {}

    def job(ctx):
        for seed in (4, 5):
            got, d, want = loops.spread(ctx, seed)
            np.testing.assert_allclose(got, want, rtol=1e-12)
            assert d["loop_fori_iters"] == 0
        seen["stats"] = ctx.overall_stats()
        seen["tracer"] = ctx.tracer

    RunLocalMock(job, 1)
    recs = [r for r in seen["tracer"].ring if r.get("kind") != "instant"]
    assert not seen["tracer"].wrapped
    device = children(recs, "device")
    dispatches = [r for r in recs if r["cat"] == "dispatch"]
    assert len(dispatches) == seen["stats"]["device_dispatches"]
    assert sorted(device) == sorted(r["span"] for r in dispatches)
    assert all(len(v) == 1 and v[0]["dur_us"] >= 0 and "error" not in v[0]
               for v in device.values())
    assert seen["stats"]["loop_donated_bytes"] > 0


def test_a_program_whose_outputs_were_donated_closes_at_the_next_ready():
    tr = trace.Tracer(ring=64, enabled=True)
    w = trace.DeviceWatcher(tr)
    consume = jax.jit(lambda x: x + 1, donate_argnums=0)
    with tr.span("dispatch", "made") as made:
        gone = jnp.zeros(8) + 1
    with tr.span("dispatch", "consumer") as consumer:
        kept = consume(gone)
    assert gone.is_deleted()
    w.device(made, gone, ((), {}))
    w.device(consumer, kept, ((gone,), {}))
    assert w.stop(30)
    got = {r["name"]: r for r in tr.ring if r["cat"] == "device"}
    assert got["made"]["donated"] is True
    assert "donated" not in got["consumer"]
    assert got["made"]["parent"] == made.span_id
    assert end_s(got["made"]) == pytest.approx(end_s(got["consumer"]),
                                               abs=2e-6)
    assert got["consumer"]["t0_s"] >= end_s(got["made"])


def test_the_watcher_lets_go_of_a_buffer_once_it_is_recorded():
    """Held until the next hand-off, a job's input and output stayed in
    HBM over the idle lane: the peak rose by both."""
    import gc
    import time
    import weakref
    ctx = Context(MeshExec(num_workers=1))
    try:
        mex = ctx.mesh_exec
        inc = mex.cached(("inc",), lambda: mex.smap(lambda x: x + 1, 1))
        x = mex.put(np.zeros((1, 64), np.float32))
        y = inc(x)
        refs = [weakref.ref(x), weakref.ref(y)]
        deadline = time.monotonic() + 30
        while {r["cat"] for r in ctx.tracer.ring} < {"device", "transfer"}:
            assert time.monotonic() < deadline
            time.sleep(0.01)
        del x, y
        while any(r() is not None for r in refs):   # the lanes move on
            assert time.monotonic() < deadline
            time.sleep(0.01)
            gc.collect()
    finally:
        ctx.close()


def test_trace_off_starts_no_thread(monkeypatch):
    monkeypatch.setenv("THRILL_TPU_TRACE", "0")
    before = {t for t in threading.enumerate()
              if t.name.startswith("thrill-tpu-watch-")}
    ctx = Context(MeshExec(num_workers=2))
    try:
        n0 = trace.SPANS_CREATED
        sort_job(ctx, records(seed=5))
        assert ctx.mesh_exec._watcher is None
        assert trace.SPANS_CREATED == n0
        assert {t for t in threading.enumerate()
                if t.name.startswith("thrill-tpu-watch-")} <= before
    finally:
        ctx.close()


def test_close_ends_the_lanes():
    ctx = Context(MeshExec(num_workers=1))
    sort_job(ctx, records(seed=6))
    watcher = ctx.mesh_exec._watcher
    assert all(t.is_alive() for t in watcher._threads)
    ctx.close()
    assert ctx.mesh_exec._watcher is None
    assert all(not t.is_alive() for t in watcher._threads)


def test_a_mesh_that_goes_away_unclosed_ends_the_lanes():
    import gc
    mex = MeshExec(num_workers=1)
    mex.tracer = trace.Tracer(ring=64, enabled=True)
    mex.put(np.zeros((1, 8), np.float32))
    threads = mex._watcher._threads
    assert all(t.is_alive() for t in threads)
    del mex
    gc.collect()
    for t in threads:
        t.join(30)
        assert not t.is_alive()


@pytest.mark.parametrize("W", [1, 2])
def test_the_six_phases_still_sum_to_the_root_stages(W):
    spec = importlib.util.spec_from_file_location(
        "chipbench_span_window",
        os.path.join(_ROOT, "chipbench", "span_window.py"))
    span_window = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(span_window)
    ctx = Context(MeshExec(num_workers=W))
    try:
        sort_job(ctx, records(seed=7))
        recs = spans(ctx)
    finally:
        ctx.close()
    assert {"device", "transfer"} <= {r["cat"] for r in recs}
    p = span_window.sum_phases([recs])
    parts = sum(p[k] for k in SIX)
    assert p["root_stage_s"] > 0
    assert abs(parts - p["root_stage_s"]) <= 0.01 * p["root_stage_s"]
    assert p["dispatch_spans"] == sum(r["cat"] == "device" for r in recs)
