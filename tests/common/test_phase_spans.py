"""Host-phase spans inside the program (ISSUE 26): ``stage`` > ``upload``
/ ``dispatch`` / ``wait`` / ``fetch`` / ``compile`` on the one Tracer,
``pipe`` per pipeline, the seconds and bytes added where each span ends,
named device programs. On a CPU mesh: the structure and the counts, never
a time worth reading."""

import importlib.util
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from thrill_tpu.api import Context, FieldReduce, Zip
from thrill_tpu.common import trace
from thrill_tpu.common.doctor import critical_path
from thrill_tpu.common.metrics import render_prometheus
from thrill_tpu.parallel.mesh import MeshExec, _CountedJit

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
LEAVES = ("upload", "dispatch", "wait", "fetch")
PHASE_STATS = ("upload_s", "upload_bytes", "fetch_s", "fetch_bytes",
               "sync_wait_s", "compiles", "compile_s")


@pytest.fixture(autouse=True)
def device_programs(monkeypatch):
    """What the chip runs: the jitted device programs, not the CPU's
    native host paths (chipbench/run.py --rehearse sets the same)."""
    monkeypatch.setenv("THRILL_TPU_HOST_RADIX", "0")
    monkeypatch.setenv("THRILL_TPU_SORT_U32", "1")
    monkeypatch.setenv("THRILL_TPU_PACK_MOVE", "1")


@pytest.fixture(scope="module")
def span_window():
    spec = importlib.util.spec_from_file_location(
        "chipbench_span_window",
        os.path.join(_ROOT, "chipbench", "span_window.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def record_key(r):
    return r["key"]


def word_key(r):
    return r["w"]


COUNT = FieldReduce({"w": "first", "c": "sum"})


def records(n=512, seed=3):
    rng = np.random.default_rng(seed)
    return {"key": rng.integers(0, 256, size=(n, 10), dtype=np.uint8),
            "value": rng.integers(0, 256, size=(n, 90), dtype=np.uint8)}


def sort_job(ctx, inp):
    got = ctx.Distribute(inp).Sort(key_fn=record_key).AllGatherArrays()
    return {k: np.asarray(v) for k, v in got.items()}


def spans_of(ctx):
    return [r for r in ctx.tracer.ring if r.get("kind") != "instant"]


def ancestors(rec, by_id):
    while rec.get("parent") in by_id:
        rec = by_id[rec["parent"]]
        yield rec


@pytest.fixture
def traced_sort(request):
    """One Distribute -> Sort -> AllGatherArrays on ``W`` workers: the
    spans, the counter deltas and the input."""
    ctx = Context(MeshExec(num_workers=request.param))
    try:
        inp = records()
        before = ctx.overall_stats()
        got = sort_job(ctx, inp)
        after = ctx.overall_stats()
        yield {"spans": spans_of(ctx), "inp": inp, "got": got,
               "delta": {k: after[k] - before[k] for k in after
                         if isinstance(after[k], (int, float))}}
    finally:
        ctx.close()


W12 = pytest.mark.parametrize("traced_sort", [1, 2], indirect=True,
                              ids=["W1", "W2"])


@W12
def test_every_phase_span_has_a_stage_above_it_and_one_pipe(traced_sort):
    spans = traced_sort["spans"]
    by_id = {r["span"]: r for r in spans}
    cats = {r["cat"] for r in spans}
    assert {"stage", "upload", "dispatch"} <= cats
    for rec in spans:
        if rec["cat"] in LEAVES + ("compile",):
            above = list(ancestors(rec, by_id))
            assert above and above[-1]["cat"] == "stage", rec
    roots = [r for r in spans if r.get("parent") not in by_id]
    assert roots and all(r["cat"] == "stage" for r in roots)
    assert len({r["pipe"] for r in roots}) == 1
    # the action is the root; the nodes' stages nest by the pull recursion
    assert roots[-1]["name"] == "AllGatherArrays"
    stages = {r["name"]: r for r in spans if r["cat"] == "stage"}
    assert {"Distribute", "Sort", "AllGatherArrays"} <= set(stages)
    assert stages["Sort"] in ancestors(stages["Distribute"], by_id)
    assert all("dia_id" in r and "pipe" in r for r in stages.values())
    # a compile is a child of the dispatch that made it
    for rec in spans:
        if rec["cat"] == "compile":
            assert by_id[rec["parent"]]["cat"] == "dispatch"
            assert by_id[rec["parent"]]["name"] == rec["name"]


@W12
def test_upload_spans_carry_the_arrays_bytes(traced_sort):
    uploads = [r for r in traced_sort["spans"] if r["cat"] == "upload"]
    inp = traced_sort["inp"]
    sizes = [r["bytes"] for r in uploads]
    assert inp["key"].nbytes in sizes and inp["value"].nbytes in sizes
    assert sum(sizes) == traced_sort["delta"]["upload_bytes"]
    big = next(r for r in uploads if r["bytes"] == inp["value"].nbytes)
    assert big["dtype"] == "uint8" and big["shape"][-1] == 90
    assert big["name"] == "put"


@W12
def test_span_counts_equal_the_counters_deltas(traced_sort):
    spans, delta = traced_sort["spans"], traced_sort["delta"]
    count = lambda cat, name=None: sum(     # noqa: E731
        r["cat"] == cat and name in (None, r["name"]) for r in spans)
    assert count("dispatch") == delta["device_dispatches"] >= 1
    assert count("upload") == delta["device_uploads"]
    assert count("fetch", "fetch") == delta["device_fetches"]
    # a wait before every copy, counted nowhere; and one after the puts
    # of a Distribute that lent the caller's arrays (data/shards.py),
    # which on a CPU goes by the arrays' addresses
    assert count("wait", "device") == count("fetch")
    assert count("wait") - count("wait", "device") \
        == count("wait", "upload") <= 1
    assert count("compile") == delta["compiles"] >= 1


@W12
def test_the_parts_sum_to_the_root_stages(traced_sort, span_window):
    p = span_window.sum_phases([traced_sort["spans"]])
    parts = sum(p[k] for k in ("upload_s", "dispatch_call_s", "sync_wait_s",
                               "fetch_s", "host_plan_s", "compile_s"))
    assert p["root_stage_s"] > 0
    assert abs(parts - p["root_stage_s"]) <= 0.01 * p["root_stage_s"]
    assert p["host_plan_s"] > 0 and p["upload_s"] > 0


@W12
def test_the_result_is_sorted(traced_sort):
    got, inp = traced_sort["got"], traced_sort["inp"]
    order = np.lexsort(tuple(inp["key"][:, j] for j in range(9, -1, -1)))
    assert np.array_equal(got["key"], inp["key"][order])
    assert np.array_equal(got["value"], inp["value"][order])


def test_two_pipelines_in_one_context_get_two_pipes():
    ctx = Context(MeshExec(num_workers=2))
    try:
        sort_job(ctx, records(seed=1))
        sort_job(ctx, records(seed=2))
        by_id = {r["span"]: r for r in spans_of(ctx)}
        roots = [r for r in by_id.values() if r.get("parent") not in by_id]
        assert len({r["pipe"] for r in roots}) == 2
        # a node joins its parents' oldest pipeline
        a = ctx.Distribute(np.arange(8))
        b = ctx.Distribute(np.arange(8))
        assert a.node.pipe == a.node.id != b.node.pipe
        assert Zip(b, a, zip_fn=lambda x, y: x + y).node.pipe \
            == a.node.pipe
    finally:
        ctx.close()


def test_an_action_over_a_deferred_node_is_the_root_stage():
    ctx = Context(MeshExec(num_workers=1))
    try:
        rng = np.random.default_rng(5)
        inp = {"w": rng.integers(97, 100, size=(256, 4), dtype=np.uint8),
               "c": np.ones(256, np.int64)}
        got = ctx.Distribute(inp).ReduceByKey(word_key, COUNT) \
            .AllGatherArrays()
        assert int(np.asarray(got["c"]).sum()) == 256
        spans = spans_of(ctx)
        by_id = {r["span"]: r for r in spans}
        stages = {r["name"]: r for r in spans if r["cat"] == "stage"}
        root = stages["AllGatherArrays"]
        assert root.get("parent") is None
        assert root in ancestors(stages["ReduceByKey"], by_id)
        assert stages["ReduceByKey"] in ancestors(stages["Distribute"],
                                                  by_id)
        # the stitched dispatch and the counts fetch are the action's
        for cat in ("dispatch", "wait", "fetch"):
            rec = next(r for r in spans if r["cat"] == cat)
            assert root in ancestors(rec, by_id)
    finally:
        ctx.close()


def test_a_first_call_compiles_under_its_programs_name_a_second_not():
    ctx = Context(MeshExec(num_workers=1))
    try:
        c0 = ctx.overall_stats()["compiles"]
        sort_job(ctx, records(n=256, seed=7))
        first = [r for r in spans_of(ctx) if r["cat"] == "compile"]
        c1 = ctx.overall_stats()
        assert first and c1["compiles"] - c0 == len(first)
        assert c1["compile_s"] > 0
        names = {r["name"] for r in spans_of(ctx) if r["cat"] == "dispatch"}
        assert {r["name"] for r in first} <= names
        assert all(r["jax_event"] == "backend_compile"
                   and r["cache_load"] is False
                   and r["seconds"] * 1e6 >= r["dur_us"] - 1 for r in first)
        sort_job(ctx, records(n=256, seed=8))
        assert len([r for r in spans_of(ctx)
                    if r["cat"] == "compile"]) == len(first)
        assert ctx.overall_stats()["compiles"] == c1["compiles"]
    finally:
        ctx.close()


def test_trace_off_allocates_no_span_at_the_new_sites(monkeypatch):
    want = None
    for flag in ("1", "0"):
        monkeypatch.setenv("THRILL_TPU_TRACE", flag)
        ctx = Context(MeshExec(num_workers=2))
        try:
            n0 = trace.SPANS_CREATED
            s0 = ctx.overall_stats()
            got = sort_job(ctx, records(seed=11))
            s1 = ctx.overall_stats()
            if flag == "0":
                assert trace.SPANS_CREATED == n0
                assert not ctx.tracer.ring
            else:
                assert trace.SPANS_CREATED > n0
            # the plain adds run either way
            assert s1["upload_bytes"] - s0["upload_bytes"] >= 512 * 100
            assert s1["upload_s"] > s0["upload_s"]
            assert s1["device_fetches"] > s0["device_fetches"]
            assert s1["fetch_bytes"] > s0["fetch_bytes"]
            assert s1["sync_wait_s"] > s0["sync_wait_s"]
            assert s1["compiles"] > s0["compiles"]
        finally:
            ctx.close()
        if want is None:
            want = got
        assert all(np.array_equal(got[k], want[k]) for k in want)


def test_a_check_is_a_fetch_span_but_not_a_counted_fetch():
    ctx = Context(MeshExec(num_workers=1))
    try:
        mex = ctx.mesh_exec
        arr = mex.put(np.arange(16, dtype=np.int32).reshape(1, 16))
        mex.flush_device_records()      # the put's transfer record
        n = len(spans_of(ctx))
        f0 = mex.stats_fetches
        assert mex._fetch_raw(arr).sum() == 120
        assert mex.fetch(arr).sum() == 120
        assert mex.fetch(np.arange(3)).sum() == 3      # no device: no span
        new = [(r["cat"], r["name"]) for r in spans_of(ctx)[n:]]
        assert new == [("wait", "device"), ("fetch", "check"),
                       ("wait", "device"), ("fetch", "fetch")]
        assert mex.stats_fetches == f0 + 1
        assert spans_of(ctx)[-1]["bytes"] == 64
        assert mex.stats_fetch_bytes == 128
    finally:
        ctx.close()


def test_a_record_carries_its_start_on_the_perf_counter_clock():
    tr = trace.Tracer(ring=8, enabled=True)
    a = time.perf_counter()
    with tr.span("stage", "x"):
        pass
    b = time.perf_counter()
    tr.emit_span("compile", "y", a, b)
    stage, compile_ = list(tr.ring)
    assert a <= stage["t0_s"] <= b and "ts" in stage
    assert compile_["t0_s"] == a
    assert compile_["dur_us"] == int((b - a) * 1e6)


def test_latest_and_the_wrapped_ring():
    tr = trace.Tracer(ring=4, enabled=True)
    assert trace.latest() is tr and not tr.wrapped
    for i in range(4):
        tr.instant("plan", str(i))
    assert not tr.wrapped and tr.records_written == 4
    tr.instant("plan", "one too many")
    assert tr.wrapped and len(tr.ring) == 4
    assert trace.Tracer(ring=0, enabled=True).wrapped     # no ring at all
    ctx = Context(MeshExec(num_workers=1))
    try:
        assert trace.latest() is ctx.tracer
    finally:
        ctx.close()
    assert trace.latest() is ctx.tracer     # reachable after the close


@pytest.mark.parametrize("key", PHASE_STATS)
def test_overall_stats_and_the_metrics_endpoint_have(key):
    ctx = Context(MeshExec(num_workers=1))
    try:
        sort_job(ctx, records(n=128, seed=13))
        ctx.mesh_exec.fetch(ctx.mesh_exec.put(np.zeros((1, 4), np.int32)))
        stats = ctx.overall_stats()
        assert stats[key] > 0
        assert f"thrill_tpu_{key} " in render_prometheus(ctx)
    finally:
        ctx.close()


def test_a_jitted_program_carries_its_label():
    ctx = Context(MeshExec(num_workers=2))
    try:
        sort_job(ctx, records(seed=17))
        mex = ctx.mesh_exec
        programs = [(key, fn[0] if isinstance(fn, tuple) else fn)
                    for key, fn in mex._cache.items()]
        programs = [(k, f) for k, f in programs if isinstance(f, _CountedJit)]
        assert programs
        for key, fn in programs:
            assert fn._label().startswith(key[0]) and fn._label() != "f"
            assert fn._jitted.__name__ == fn._label()
        assert any(f._label() == "sort_fused" for _, f in programs)
        # the device plane's module line reads jit_<label>
        inc = mex.cached(("plus_one",), lambda: mex.smap(
            lambda x: x + 1, 1))
        x = mex.put(np.zeros((2, 4), np.int32))
        assert "module @jit_plus_one " in inc.lower(x).as_text()
        assert inc.donating((0,))._label() == "plus_one"
        named = mex.smap(lambda x: x, 1, name="fused_Sort")
        assert "module @jit_fused_Sort " in named.lower(x).as_text()
        assert named._label() == "fused_Sort"
    finally:
        ctx.close()


def test_a_stitched_program_is_named_for_its_ops():
    ctx = Context(MeshExec(num_workers=1))
    try:
        sort_job(ctx, records(seed=19))
        names = {r["name"] for r in spans_of(ctx) if r["cat"] == "dispatch"}
        assert "fused_Sort" in names
    finally:
        ctx.close()


def test_named_scopes_are_in_the_programs_metadata():
    from thrill_tpu.core import device_sort, rowmove
    from thrill_tpu.data import exchange

    def f(x):
        perm = device_sort.argsort_words([x.astype(jnp.uint64)])
        return rowmove.take_rows(x, perm)

    text = jax.jit(f).lower(jnp.arange(64, dtype=jnp.uint32)) \
        .as_text(debug_info=True)
    assert f"/{device_sort.SCOPE}/" in text and f"/{rowmove.SCOPE}/" in text

    def g(x):       # ReduceByKey's W=1 program: sort, gathers, the fold
        from thrill_tpu.core import segmented
        w, tree, valid, _ = segmented.sort_by_key_words(
            [x], {"v": x}, x > 3)
        return segmented.reduce_runs(w, tree, valid, None, ["sum"])

    text = jax.jit(g).lower(jnp.arange(64, dtype=jnp.uint32)) \
        .as_text(debug_info=True)
    assert f"/{rowmove.SCOPE}/" in text
    # the fold's rows come compact: positions, then scan and gathers
    assert "/segmented_reduce/run_bounds/" in text
    assert "/segmented_reduce/run_fold/" in text
    assert "/compact/" not in text
    ctx = Context(MeshExec(num_workers=2))
    try:
        mex = ctx.mesh_exec
        ship = mex.smap(lambda x: exchange.ship_blocks(
            x[0], jnp.array([0, 2]), jnp.array([2, 2]), 2, 2)[None], 1)
        text = ship.lower(mex.put(np.zeros((2, 4), np.int32))) \
            .as_text(debug_info=True)
        assert f'"{exchange.SCOPE}/all_to_all"' in text
        assert f"{exchange.SCOPE}/send_slice/" in text
    finally:
        ctx.close()


def test_phase_spans_are_annotations_in_a_host_profile(tmp_path):
    """With the host's tracer on, the program's phases lie on the
    profiler's clock (chipbench traces with it off: they vanish)."""
    from jax.profiler import ProfileData
    ctx = Context(MeshExec(num_workers=1))
    try:
        sort_job(ctx, records(n=128, seed=23))
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 1
        with jax.profiler.trace(str(tmp_path), profiler_options=options):
            sort_job(ctx, records(n=128, seed=24))
    finally:
        ctx.close()
    hits = list(tmp_path.glob("plugins/profile/*/*.xplane.pb"))
    assert hits
    names = {e.name for plane in ProfileData.from_file(str(hits[-1])).planes
             for line in plane.lines for e in line.events}
    assert {"stage:Sort", "stage:Distribute", "upload:put"} <= names
    assert not any(n.startswith("dispatch:") for n in names)
    assert set(trace.MIRRORED) == {"stage", "upload", "wait", "fetch",
                                   "exchange"}


def test_the_critical_path_takes_every_child_in_a_sequence():
    """Under a stage the uploads, dispatches and fetches run one after
    another: the long early child is on the path, not only the child
    that ends last."""
    def rec(span, parent, cat, ts, dur):
        r = {"event": "span", "cat": cat, "name": cat + str(span),
             "trace": "t", "span": span, "rank": 0, "ts": ts,
             "dur_us": dur}
        if parent is not None:
            r["parent"] = parent
        return r

    recs = [rec(1, None, "stage", 0, 1000),
            rec(2, 1, "exchange", 10, 900),
            rec(3, 2, "dispatch", 20, 800),
            rec(4, 1, "fetch", 950, 5),
            # overlaps the fetch and ends before it: not on the path
            rec(5, 1, "io", 940, 12)]
    edges = critical_path(recs, k=10)
    assert [e["cat"] for e in edges][:2] == ["dispatch", "exchange"]
    assert {e["cat"] for e in edges} == {"stage", "exchange", "dispatch",
                                         "fetch"}
    deepest = max(edges, key=lambda e: e["path"].count(">"))
    assert deepest["path"] == "stage:stage1 > exchange:exchange2 > " \
        "dispatch:dispatch3"
