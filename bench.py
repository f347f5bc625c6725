"""Benchmark: TeraSort record throughput on the local accelerator.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...}.
A run that finds no accelerator exits non-zero and prints no line,
unless ``JAX_PLATFORMS=cpu`` asked for the CPU; the line always names
the platform it ran on.

The north-star workload is TeraSort — 100-byte records
with 10-byte keys through the full DIA Sort pipeline. The reference
C++ framework cannot be built in this image (extlib submodules tlx/
foxxll are not checked out and there is no network), so ``vs_baseline``
compares against the strongest available host-side proxy measured in
the same run: numpy's lexsort-based TeraSort of the identical records
on the host CPU. vs_baseline = device_throughput / host_throughput.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import threading
import time

import numpy as np

RESULT = {
    "metric": "terasort_throughput",
    "value": 0.0,
    "unit": "Mrecords/s",
    "vs_baseline": 0.0,
    "platform": "none",
    # measurement-quality contract (round-5): "ok" means the machine
    # looked idle at start AND the timed iterations were stable;
    # "loaded" = loadavg said another process was competing before we
    # started; "noisy" = some timed section's best-of-N dispersion
    # exceeded _MAX_DISP (don't
    # trust round-over-round comparisons of this line). Every timed
    # section reports best-of-N with dispersion so background load
    # inflates the spread, not the headline.
    "quality": "ok",
}
_STATE_LOCK = threading.Lock()
_emitted = False


def _set(**kv):
    """Record result fields."""
    with _STATE_LOCK:
        RESULT.update(kv)


def _emit(**extra):
    """Print the one JSON line exactly once."""
    global _emitted
    with _STATE_LOCK:
        if _emitted:
            return
        _emitted = True
        RESULT.update(extra)
        payload = json.dumps(RESULT)
    print(payload, flush=True)


#: dispersion past this flags the line as "noisy". Calibrated on this
#: 1-core box: idle-machine best-of-3 spreads reach ~0.4 from GC and
#: jax worker-thread scheduling alone; genuine contention (a parallel
#: jax process) pushes past 2x. The loadavg guard is the primary load
#: detector; dispersion is the backstop for mid-run arrivals.
_MAX_DISP = 0.6


def _best_of(fn, iters: int = 3):
    """Best-of-N timing: returns (min_seconds, dispersion). The min is
    the load-robust estimator (background processes only ever ADD
    time); dispersion = (max-min)/min feeds the quality flag."""
    times = []
    for _ in range(max(iters, 1)):
        t0 = time.perf_counter()
        fn()
        times.append(time.perf_counter() - t0)
    best = min(times)
    disp = (max(times) - best) / best if best > 0 else 0.0
    return best, round(disp, 3)


def _note_dispersion(disp: float) -> None:
    """Escalate quality to "noisy" when any timed section's spread
    says the numbers are load-contaminated."""
    if disp > _MAX_DISP and RESULT.get("quality") == "ok":
        _set(quality="noisy")


def _host_terasort(keys: np.ndarray, values: np.ndarray):
    """numpy proxy baseline: pack key words, lexsort, gather."""
    w0 = np.zeros(len(keys), dtype=np.uint64)
    w1 = np.zeros(len(keys), dtype=np.uint64)
    for i in range(8):
        w0 = (w0 << np.uint64(8)) | keys[:, i].astype(np.uint64)
    for i in range(8, 10):
        w1 = (w1 << np.uint64(8)) | keys[:, i].astype(np.uint64)
    w1 <<= np.uint64(48)
    perm = np.lexsort((w1, w0))
    return keys[perm], values[perm]


def _key_fn(r):
    """Module-level key extractor: stable identity -> the Sort executable
    compiles once and is reused across timed iterations."""
    return r["key"]


def _run_bench() -> None:
    import jax

    from thrill_tpu.common.platform import require_accelerator
    require_accelerator()

    import thrill_tpu  # noqa: F401  (enables x64)
    from thrill_tpu.api import Context
    from thrill_tpu.parallel.mesh import MeshExec

    platform = jax.default_backend()
    _set(platform=platform)
    # load guard: on a contended machine the line must SAY so (the
    # round-4 driver capture read as a phantom 2.5x regression purely
    # from background load)
    try:
        load1 = os.getloadavg()[0]
        _set(loadavg=round(load1, 2))
        if load1 > 1.5:
            _set(quality="loaded")
            print(f"bench: loadavg {load1:.2f} > 1.5 — machine is "
                  f"contended, numbers are suspect", file=sys.stderr)
    except OSError:
        pass
    default_n = 1 << 20 if platform != "cpu" else 1 << 18
    try:
        n = int(os.environ.get("THRILL_TPU_BENCH_N", "") or default_n)
    except ValueError:
        n = default_n
    if n < 1024:
        print(f"bench: clamping n={n} to 1024 (minimum)", file=sys.stderr)
        n = 1024
    _set(n=n)

    rng = np.random.default_rng(0)
    recs = {
        "key": rng.integers(0, 256, size=(n, 10)).astype(np.uint8),
        "value": rng.integers(0, 256, size=(n, 90)).astype(np.uint8),
    }

    mex = MeshExec()  # all local devices
    ctx = Context(mex)

    # ingest once (reference TeraSort reads its input once, too); the
    # timed iterations measure the Sort pipeline itself, not the
    # host->device upload of the same 100 MB. The
    # upload cost is still reported (upload_s field).
    inp = ctx.Distribute(recs)
    t0 = time.perf_counter()
    jax.block_until_ready(jax.tree.leaves(
        inp.node.materialize(consume=False).tree))
    _set(upload_s=round(time.perf_counter() - t0, 3))

    def run_once():
        inp.Keep()
        out = inp.Sort(key_fn=_key_fn)
        shards = out.node.materialize()
        leaves = jax.tree.leaves(shards.tree)
        jax.block_until_ready(leaves)
        # few-byte readback: the timed region ends on bytes that
        # reached the host, not only on block_until_ready
        np.asarray(leaves[0][0, :1])
        return shards

    run_once()                      # warmup + compile
    run_once()                      # second warmup: steady-state HBM/GC
    xs = _xchg_snapshot(mex)
    dt, disp = _best_of(run_once, iters=3)
    _set(terasort_disp=disp, **_xchg_fields(mex, xs, "terasort"))
    _note_dispersion(disp)

    # tracing overhead contract (common/trace.py): paired on/off
    # timing of the SAME Sort pipeline pins what the spine costs when
    # enabled, and the per-lane span counts say where spans come from
    # — future PRs cannot silently regress the disabled-path cost
    tr = ctx.tracer
    prev_tr = tr.enabled
    try:
        lanes0 = dict(tr.lane_counts)       # delta, not lifetime
        tr.enabled = True
        dt_on, _ = _best_of(run_once, iters=2)
        tr.enabled = False
        dt_off, _ = _best_of(run_once, iters=2)
        _set(trace_overhead_frac=round(
                 max(dt_on / dt_off - 1.0, 0.0), 4),
             trace_spans={k: int(v - lanes0.get(k, 0)) for k, v in
                          sorted(dict(tr.lane_counts).items())
                          if v - lanes0.get(k, 0)})
    except Exception as e:  # observability metric never kills the line
        _set(trace_error=repr(e)[:200])
    finally:
        # a raising leg must not leave the tracer forced on/off for
        # every later workload (the fusion_report env-leak bug class)
        tr.enabled = prev_tr

    # host proxy baseline on identical data (best-of-2: one spike in
    # the BASELINE leg would otherwise inflate vs_baseline)
    host_dt, host_disp = _best_of(
        lambda: _host_terasort(recs["key"], recs["value"]), iters=2)
    _note_dispersion(host_disp)

    mrec_s = n / dt / 1e6
    host_mrec_s = n / host_dt / 1e6

    # secondary north-star metric (BASELINE.md): WordCount ReduceByKey
    # items/sec on the device path, vs a collections.Counter host proxy
    wc = _wordcount_metric(ctx, n)
    # iterative north stars (BASELINE.md): PageRank and k-means —
    # Collapse loops over InnerJoin/ReduceToIndex, vs numpy proxies
    prm = _pagerank_metric(ctx)
    kmm = _kmeans_metric(ctx)
    # suffix sorting (BASELINE.md north-star #5): prefix-doubling
    # rounds of the full Sort pipeline vs a numpy lexsort proxy
    sfm = _suffix_metric(ctx)
    # host-storage EM sort (spill + native k-way merge) A/B vs the
    # generic python-heap engine — platform-independent, so it
    # reports the host engine even in a TPU window
    em = _em_sort_metric(ctx)
    # remote out-of-core + array-payload lanes (ISSUE 17): the em
    # workload against 20ms-per-request object storage (overlap vs
    # synchronous ladder, resume leg) and the columnar ndarray-leaf
    # spill A/B
    emr = _em_remote_metric()
    ema = _em_array_metric(ctx)
    # durability cost (api/checkpoint.py), opt-in: epoch-write overhead
    # and resume/restore time on the Sort pipeline
    ck = (_ckpt_metric(n)
          if os.environ.get("THRILL_TPU_BENCH_CKPT") == "1" else {})

    # memory-pressure observability (mem/pressure.py): the HBM peak the
    # governor accounted, the cost model's high watermark, and how
    # often the OOM ladder engaged — a nonzero oom_retries on a clean
    # bench run means the working set is brushing the HBM budget
    press = ctx.overall_stats()
    _set(hbm_peak=int(press.get("hbm_peak", 0)),
         hbm_high_watermark=int(press.get("hbm_high_watermark", 0)),
         oom_retries=int(press.get("oom_retries", 0)),
         segment_splits=int(press.get("segment_splits", 0)))
    # scoped failure domains (api/context.py pipeline()/heal): the
    # seed metrics for the sustained-traffic harness — a clean bench
    # run reports 0 aborts / 0 reconnects / 0.0 heal seconds, and any
    # nonzero value means the run survived real faults
    _set(pipeline_aborts=int(press.get("pipeline_aborts", 0)),
         conn_reconnects=int(press.get("conn_reconnects", 0)),
         heal_time_s=float(press.get("heal_time_s", 0.0)))
    # plan observatory (common/decisions.py): cost-model estimate
    # quality as mean |log2(predicted/actual)| per decision kind, WITH
    # the per-lane join count and stddev — vs_* ratios are known to
    # swing run-to-run on this rig, so a regression in estimate
    # quality must be judged against its own dispersion, not a bare
    # point value
    try:
        acc = ctx.decisions.accuracy()
        _set(cost_model_mae={k: v["mae_log2"] for k, v in acc.items()
                             if v.get("mae_log2") is not None},
             cost_model_mae_n={k: v["joined"] for k, v in acc.items()
                               if v.get("mae_log2") is not None},
             cost_model_mae_std={k: v["stdev_log2"]
                                 for k, v in acc.items()
                                 if v.get("stdev_log2") is not None},
             decisions_recorded=int(
                 press.get("decisions_recorded", 0)),
             decisions_joined=int(press.get("decisions_joined", 0)))
    except Exception as e:  # observability lane never kills the line
        _set(cost_model_error=repr(e)[:200])
    # adaptive planner (api/planner.py): how often a learned plan was
    # invalidated and re-chosen after an audit/deferred-check lie, and
    # how many re-choices actually changed the plan — 0/0 on a run
    # whose learned stats held, so any nonzero value on a clean bench
    # says the cost model's own inputs drifted mid-run
    _set(planner_replans=int(press.get("planner_replans", 0)),
         planner_switch_count=int(press.get("planner_switches", 0)))
    # overlapped-exchange data plane (data/exchange.py): run-wide
    # overlap fraction, capacity-plan cache hit rate, and the
    # bytes-on-wire baseline for the shrink-the-wire ROADMAP item
    n_ex = int(press.get("exchanges", 0))
    hits = int(press.get("cap_cache_hits", 0))
    misses = int(press.get("cap_cache_misses", 0))
    _set(exchange_overlap_frac=round(
             press.get("exchanges_overlapped", 0) / n_ex, 3)
         if n_ex else 0.0,
         cap_cache_hit=round(hits / (hits + misses), 3)
         if hits + misses else 0.0,
         bytes_on_wire=int(press.get("bytes_on_wire", 0)),
         bytes_on_wire_raw=int(press.get("bytes_on_wire_raw", 0)),
         wire_compress_ratio=float(
             press.get("wire_compress_ratio", 1.0)))

    # sustained-traffic serve lane (service/scheduler.py): closed-loop
    # client threads submitting a mixed WordCount/PageRank workload
    # through ctx.submit — qps + latency percentiles make throughput
    # regressions as loud as the dispatch budgets
    sv = _serve_metric(ctx)

    # external-traffic lane (ISSUE 18): real socket clients through
    # the front door at ~2x overload — accept-to-result latency for
    # served jobs plus the served-vs-rejected shed split
    fdm = _front_door_metric(ctx)

    # elastic-mesh micro-lane (ISSUE 16): fenced W=2->3->2 resize cost
    # under a live job stream, in its own forced-multi-device process
    el = _elastic_metric()

    # supervised process-elasticity lane (ISSUE 20): the same walk as
    # a drain -> seal -> relaunch-with-resume move on real processes
    # under supervise.sh, autoscaler-driven, front-door traffic live
    elp = _elastic_proc_metric()

    # Pallas/narrowing A/B lanes (ISSUE 19): same Sort pipeline under
    # flipped single knobs, one process per leg
    ab = _pallas_ab_metric()

    _emit(value=round(mrec_s, 3),
          vs_baseline=round(mrec_s / host_mrec_s, 3),
          **wc, **prm, **kmm, **sfm, **em, **emr, **ema, **ck,
          **sv, **fdm, **el, **elp, **ab)
    ctx.close()


def _wc_key(t):
    return t["w"]


def _wordcount_metric(ctx, n: int) -> dict:
    """WordCount throughput: n packed words, zipf-ish key skew, full
    device ReduceByKey; proxy = collections.Counter over the strings.
    The reduce functor is the declarative FieldReduce — the idiomatic
    WordCount spelling here, matching the reference's std::plus functor
    (examples/word_count/word_count.hpp) which its templates likewise
    inline into the aggregation loop."""
    import collections
    from thrill_tpu.api import FieldReduce
    try:
        doc_snap = _doctor_snapshot(getattr(ctx, "doctor", None))
        rng = np.random.default_rng(1)
        vocab_n = max(1024, n // 64)
        ids = np.minimum(rng.zipf(1.3, size=n) - 1, vocab_n - 1)
        words = np.zeros((n, 16), dtype=np.uint8)
        digits = np.char.zfill(ids.astype("U8"), 8)   # 8-char ids
        words[:, :8] = np.frombuffer(
            "".join(digits.tolist()).encode("ascii"),
            dtype=np.uint8).reshape(n, 8)
        import jax
        d = ctx.Distribute({"w": words,
                            "c": np.ones(n, dtype=np.int64)})
        d.Keep()

        red = FieldReduce({"w": "first", "c": "sum"})

        def once():
            d.Keep()
            out = d.ReduceByKey(_wc_key, red)
            sh = out.node.materialize()
            jax.block_until_ready(jax.tree.leaves(sh.tree))
            np.asarray(jax.tree.leaves(sh.tree)[0])[:1]

        once()                                   # warmup + compile
        dt, disp = _best_of(once, iters=3)
        _note_dispersion(disp)
        strs = ["".join(map(chr, row)) for row in words]
        host_dt, host_disp = _best_of(
            lambda: collections.Counter(strs), iters=2)
        _note_dispersion(host_disp)
        # doctor lane (common/doctor.py): this lane's zipf keys are
        # the bench's natural skew probe — per-lane deltas, so earlier
        # lanes' waits/skew on the shared ctx cannot leak in
        return {"wordcount_mitems_s": round(n / dt / 1e6, 3),
                "wordcount_vs_counter": round(host_dt / dt, 3),
                "wordcount_disp": disp,
                **_doctor_fields(getattr(ctx, "doctor", None),
                                 doc_snap, "wordcount")}
    except Exception as e:  # secondary metric never kills the line
        return {"wordcount_error": repr(e)[:200]}


def _examples_path():
    p = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "examples")
    if p not in sys.path:
        sys.path.insert(0, p)


def _loop_phase_fields(ctx, name: str, prefix: str) -> dict:
    """Per-iteration phase breakdown of the newest api/loop.py report
    for loop ``name``: what fraction of loop wall went to the capture
    iteration (graph build + pull recursion + fusion planning + its
    dispatches) vs replayed iterations (pure dispatch), plus the
    replay hit rate — so a PageRank/k-means speedup is ATTRIBUTABLE to
    the iteration layer, not just asserted. The same numbers stream as
    ``event=iteration`` / ``event=loop_replay`` profile lines when
    THRILL_TPU_LOG is set (rendered by tools/json2profile.py)."""
    reps = [r for r in getattr(ctx.mesh_exec, "loop_reports", [])
            if r.get("name") == name]
    if not reps:
        return {}
    r = reps[-1]
    total = r["capture_s"] + r["replay_s"]
    hit = (r["replays"] + r["fori_iters"]) / max(r["iters"], 1)
    return {f"{prefix}_plan_frac": round(r["capture_s"] / total, 3)
            if total > 0 else None,
            f"{prefix}_replay_hit": round(hit, 3),
            f"{prefix}_plan_builds": r["captures"],
            f"{prefix}_replay_s": round(r["replay_s"], 4),
            f"{prefix}_capture_s": round(r["capture_s"], 4)}


def _doctor_snapshot(doc) -> tuple | None:
    """Per-lane doctor baseline: (exchange-wait seconds, per-site
    exchange counts) — the shared bench ctx accumulates doctor state
    across lanes, so each lane must report DELTAS, the _xchg_snapshot
    pattern."""
    if doc is None:
        return None
    return (doc.wait_exchange_s,
            {s: st["exchanges"] for s, st in doc.skew_by_site.items()})


def _doctor_fields(doc, snap, prefix: str) -> dict:
    """This lane's exchange-barrier wait and the worst skew ratio
    among sites whose exchange count GREW during the lane (a site's
    ratio is its own pipeline's — bench lanes don't share exchange
    call sites)."""
    if doc is None or snap is None:
        return {f"{prefix}_skew_ratio": 0.0,
                f"{prefix}_xchg_wait_s": 0.0}
    wait0, sites0 = snap
    ratios = [st["ratio"] for s, st in doc.skew_by_site.items()
              if st["exchanges"] > sites0.get(s, 0)]
    return {f"{prefix}_skew_ratio": round(max(ratios, default=0.0), 3),
            f"{prefix}_xchg_wait_s": round(
                max(doc.wait_exchange_s - wait0, 0.0), 4)}


def _xchg_snapshot(mex) -> tuple:
    """(exchanges, overlapped, cap hits, cap misses, wire, wire raw)
    counter snapshot for per-workload exchange attribution."""
    return (mex.stats_exchanges, mex.stats_exchanges_overlapped,
            mex.stats_cap_cache_hits, mex.stats_cap_cache_misses,
            mex.stats_bytes_wire_device + mex.stats_bytes_wire_host,
            mex.stats_bytes_wire_device_raw + mex.stats_bytes_wire_host
            + mex.stats_bytes_wire_host_saved)


def _xchg_fields(mex, snap, prefix: str) -> dict:
    """Per-workload overlap + wire fields since ``snap``: what fraction
    of the workload's exchanges dispatched with NO mid-shuffle host sync
    (``*_exchange_overlap_frac`` — the ROADMAP success metric: near 1.0
    in steady state at W>1, exactly 0 where the workload has no
    exchanges, e.g. dense-gather PageRank), the capacity-plan cache
    hit rate over its lookups, and the workload's bytes-on-wire with
    its compression ratio (ISSUE 7: wire regressions loud per workload,
    the way dispatch budgets are)."""
    ex, ov, h, m, wire, raw = (b - a
                               for a, b in zip(snap,
                                               _xchg_snapshot(mex)))
    out = {f"{prefix}_exchange_overlap_frac":
           round(ov / ex, 3) if ex else 0.0,
           f"{prefix}_bytes_on_wire": int(wire),
           f"{prefix}_wire_compress_ratio":
           round(raw / wire, 3) if wire else 1.0}
    if h + m:
        out[f"{prefix}_cap_cache_hit"] = round(h / (h + m), 3)
    return out


def _pagerank_metric(ctx) -> dict:
    """PageRank end-to-end: per-iteration edge throughput of the full
    DIA pipeline (dense-gather InnerJoin + scatter ReduceToIndex,
    LoopPlan-replayed via api/loop.py Iterate, examples/page_rank.py;
    reference: examples/page_rank/page_rank.hpp:71-131) against the
    numpy scatter-add proxy on identical data, with parity checked."""
    try:
        _examples_path()
        import page_rank as pr
        pages, m, iters = 4096, 1 << 16, 5
        try:
            m = int(os.environ.get("THRILL_TPU_BENCH_PR_EDGES", "") or m)
        except ValueError:
            pass
        edges = pr.zipf_graph(pages, m, seed=2)
        holder = {}

        def once():
            holder["ranks"] = pr.page_rank(ctx, edges, pages,
                                           iterations=iters)

        once()                                   # warmup + compile
        xs = _xchg_snapshot(ctx.mesh_exec)
        dt, disp = _best_of(once, iters=2)
        xf = _xchg_fields(ctx.mesh_exec, xs, "pagerank")
        _note_dispersion(disp)
        hh = {}

        def host_once():
            hh["want"] = pr.page_rank_dense(ctx, edges, pages, iters)

        host_dt, host_disp = _best_of(host_once, iters=2)
        _note_dispersion(host_disp)
        want = hh["want"]
        if not np.allclose(holder["ranks"], want, rtol=1e-6, atol=1e-9):
            return {"pagerank_error": "parity mismatch vs numpy"}
        return {"pagerank_medges_s": round(m * iters / dt / 1e6, 3),
                "pagerank_vs_numpy": round(host_dt / dt, 3),
                "pagerank_disp": disp, **xf,
                **_loop_phase_fields(ctx, "page_rank", "pagerank")}
    except Exception as e:  # secondary metric never kills the line
        return {"pagerank_error": repr(e)[:200]}


def _kmeans_metric(ctx) -> dict:
    """k-means end-to-end: per-iteration point throughput of the DIA
    classify + ReduceToIndex loop (examples/k_means.py; reference:
    examples/k-means/k-means.hpp:176-259) against the numpy Lloyd
    proxy, with centroid parity checked."""
    try:
        _examples_path()
        import k_means as km
        n, dim, k, iters = 1 << 17, 8, 16, 5
        try:
            n = int(os.environ.get("THRILL_TPU_BENCH_KM_N", "") or n)
        except ValueError:
            pass
        rng = np.random.default_rng(4)
        points = rng.normal(size=(n, dim))
        holder = {}

        def once():
            holder["centers"] = km.k_means(ctx, points, k,
                                           iterations=iters, seed=0)

        once()                                   # warmup + compile
        dt, disp = _best_of(once, iters=2)
        _note_dispersion(disp)
        # identical seed-0 start centers for the proxy
        rng0 = np.random.default_rng(0)
        centers0 = points[rng0.choice(n, size=k, replace=False)].copy()
        hh = {}

        def host_once():
            hh["want"] = km.k_means_dense(points, centers0, iters)

        host_dt, host_disp = _best_of(host_once, iters=2)
        _note_dispersion(host_disp)
        want = hh["want"]
        if not np.allclose(holder["centers"], want, rtol=1e-6,
                           atol=1e-8):
            return {"kmeans_error": "parity mismatch vs numpy"}
        return {"kmeans_mitems_s": round(n * iters / dt / 1e6, 3),
                "kmeans_vs_numpy": round(host_dt / dt, 3),
                "kmeans_disp": disp,
                **_loop_phase_fields(ctx, "k_means", "kmeans")}
    except Exception as e:  # secondary metric never kills the line
        return {"kmeans_error": repr(e)[:200]}


def _suffix_numpy_doubling(text: np.ndarray) -> np.ndarray:
    """Host proxy: the same prefix-doubling algorithm in pure numpy
    (lexsort per round). A slice-key ``sorted`` proxy is O(n^2 log n)
    and unusable past ~20k chars; this is the strongest fair host
    baseline for the sort-heavy recursion (reference:
    examples/suffix_sorting/prefix_doubling.cpp)."""
    n = len(text)
    rank = text.astype(np.int64)
    k = 1
    while True:
        r2 = np.zeros(n, np.int64)
        if k < n:
            r2[:-k] = rank[k:]
        order = np.lexsort((r2, rank))
        b = np.ones(n, np.int64)
        b[1:] = ((rank[order][1:] != rank[order][:-1])
                 | (r2[order][1:] != r2[order][:-1]))
        nr = np.cumsum(b)
        new_rank = np.empty(n, np.int64)
        new_rank[order] = nr
        rank = new_rank
        if nr[-1] == n:
            return order
        k *= 2


def _suffix_metric(ctx) -> dict:
    """Suffix-array build throughput (prefix doubling over the DIA
    Sort pipeline, examples/suffix_sorting.py) vs the numpy doubling
    proxy, exact-parity checked. Chars/s counts one full build."""
    try:
        _examples_path()
        import suffix_sorting as ss
        n = 1 << 16
        try:
            n = int(os.environ.get("THRILL_TPU_BENCH_SUF_N", "") or n)
        except ValueError:
            pass
        rng = np.random.default_rng(7)
        text = rng.integers(97, 101, size=n).astype(np.uint8)  # a-d
        holder = {}

        def once():
            holder["sa"] = ss.suffix_array(ctx, text)

        once()                                   # warmup + compile
        dt, disp = _best_of(once, iters=2)
        _note_dispersion(disp)
        hh = {}

        def host_once():
            hh["sa"] = _suffix_numpy_doubling(text)

        host_dt, host_disp = _best_of(host_once, iters=2)
        _note_dispersion(host_disp)
        if not np.array_equal(holder["sa"], hh["sa"]):
            return {"suffix_error": "suffix array mismatch vs numpy"}
        return {"suffix_mchars_s": round(n / dt / 1e6, 3),
                "suffix_vs_numpy": round(host_dt / dt, 3),
                "suffix_disp": disp}
    except Exception as e:  # secondary metric never kills the line
        return {"suffix_error": repr(e)[:200]}


def _em_sort_metric(ctx) -> dict:
    """Host EM sort (forced spills, ~40 runs of string items): native
    byte-key engine (core/order_key.py + native/mwmerge.cpp) A/B'd
    in-run against the generic Python-heap engine on identical
    machinery. Two forms of evidence: the TOTAL ratio
    (em_sort_vs_py_engine) and the MERGE-PHASE ratio
    (em_merge_vs_py, from the sort's phase decomposition) — the spill
    phase is engine-independent, so the phase ratio pins the native
    engine's win even at scales where spill time dominates the total
    (ref hot loop: api/sort.hpp:216-271)."""
    try:
        n = 1 << 22
        try:
            n = int(os.environ.get("THRILL_TPU_BENCH_EM_N", "") or n)
        except ValueError:
            pass
        rng = np.random.default_rng(3)
        items = [f"key-{v:014d}" for v in
                 rng.integers(0, 1 << 48, size=n).tolist()]
        prev = {k: os.environ.get(k) for k in
                ("THRILL_TPU_HOST_SORT_RUN", "THRILL_TPU_EM_MERGE",
                 "THRILL_TPU_SPILL_RESIDENT", "THRILL_TPU_PREFETCH",
                 "THRILL_TPU_WRITEBACK", "THRILL_TPU_NATIVE_RECORDS")}
        os.environ["THRILL_TPU_HOST_SORT_RUN"] = str(n // 40)
        # pin a genuinely disk-resident merge regime (~quarter of the
        # spilled volume stays RAM-resident) so the overlap structure
        # fields measure real storage traffic, not an all-RAM store
        os.environ["THRILL_TPU_SPILL_RESIDENT"] = "32M"

        def run_once(data):
            d = ctx.Distribute(list(data), storage="host")
            t0 = time.perf_counter()
            node = d.Sort().node
            hs = node.materialize()
            dt = time.perf_counter() - t0
            return (dt, sum(len(l) for l in hs.lists),
                    getattr(node, "_em_stats", {}))

        def best_leg(data):
            """Best-of-2 per engine leg: the A/B ratio was observed to
            swing 2x run-over-run on single shots (page cache, GC)."""
            a = run_once(data)
            b = run_once(data)
            return a if a[0] <= b[0] else b

        def med_leg(data):
            """Median-of-3 for the acceptance-pinned A/B legs (the
            rig-variance rule: judge paired multi-run medians)."""
            runs = sorted([run_once(data) for _ in range(3)],
                          key=lambda r: r[0])
            return runs[1]

        try:
            # warmup: a small EM sort pays the one-time native build /
            # ctypes load OUTSIDE the timed window (_wordcount_metric
            # warms up the same way). Must exceed run_size (n/40) or
            # the warmup takes the in-memory path and loads nothing.
            run_once(items[: max(1 << 17, n // 40 + 1)])
            dt, got_n, stats = med_leg(items)
            # paired tier A/B on the same rig and data: the full
            # out-of-core tier ON (prefetch + write-behind + native
            # records, the leg above) vs the SYNCHRONOUS PICKLE LADDER
            # it replaced (demand reads, caller-thread spills, per-item
            # pickle encode — the pre-tier baseline). Medians of 3 per
            # the rig-variance rule; em_overlap_frac is the structural
            # view. (Before ISSUE 15 this lane toggled only
            # prefetch/writeback, which measured ~1.0x because the
            # GIL-held pickle encode dominated both legs — the record
            # format is what made the spill job hideable at all.)
            os.environ["THRILL_TPU_PREFETCH"] = "0"
            os.environ["THRILL_TPU_WRITEBACK"] = "0"
            os.environ["THRILL_TPU_NATIVE_RECORDS"] = "0"
            sync_dt, _, _ = med_leg(items)
            os.environ.pop("THRILL_TPU_PREFETCH", None)
            os.environ.pop("THRILL_TPU_WRITEBACK", None)
            # native columnar records on-vs-off with the overlap tier
            # on (ISSUE 15): isolates the record format's contribution
            # — the off leg spills per-item pickle blocks exactly as
            # PR 13 did
            norec_dt, _, norec_stats = med_leg(items)
            os.environ.pop("THRILL_TPU_NATIVE_RECORDS", None)
            os.environ["THRILL_TPU_EM_MERGE"] = "py"
            # median like the native leg it is ratioed against — mixed
            # estimators (median vs best) would skew the engine ratio
            py_dt, _, py_stats = med_leg(items)
        finally:
            for k, v in prev.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v
        if got_n != n:
            return {"em_sort_error": f"lost items: {got_n}/{n}"}
        out = {"em_sort_mitems_s": round(n / dt / 1e6, 3),
               "em_sort_vs_py_engine": round(py_dt / dt, 3),
               # out-of-core overlap structure (ISSUE 13/15): fraction
               # of background-I/O busy time hidden behind compute,
               # foreground fraction lost to I/O waits, merge
               # readahead hit rate, write-behind volume, and the
               # paired full-tier-vs-synchronous-ladder median ratio
               "em_overlap_frac": stats.get("overlap_frac", 0.0),
               "em_io_wait_frac": round(
                   stats.get("io_wait_s", 0.0) / dt, 4),
               "em_prefetch_hit_rate": stats.get("prefetch_hit_rate",
                                                 0.0),
               "em_spill_writeback_bytes": stats.get("writeback_bytes",
                                                     0),
               "em_overlap_ab": round(sync_dt / dt, 3),
               # native-records paired A/B + the structural witness
               # that the on leg really rode the columnar format
               "em_records_ab": round(norec_dt / dt, 3),
               "em_records_blocks": stats.get("records_blocks", 0),
               "em_spill_s": stats.get("spill_s", 0.0),
               "em_spill_s_norec": norec_stats.get("spill_s", 0.0)}
        if stats.get("merge_s") and py_stats.get("merge_s") \
                and stats.get("engine") == "native":
            out["em_merge_s"] = stats["merge_s"]
            out["em_merge_vs_py"] = round(
                py_stats["merge_s"] / stats["merge_s"], 3)
        return out
    except Exception as e:  # tertiary metric never kills the line
        return {"em_sort_error": repr(e)[:200]}


def _em_remote_metric() -> dict:
    """Remote out-of-core lane (ISSUE 17): the em workload end-to-end
    against the in-repo object server with 20ms injected per-REQUEST
    latency — ReadLines from remote objects, host EM sort whose run
    commits (bin + CRC'd manifest, core/em_runs.py) PUT to the remote
    checkpoint dir from the write-behind job. Paired A/B vs the
    synchronous ladder (PREFETCH=0 + WRITEBACK=0: demand GETs and
    inline commit PUTs on the caller thread) — the overlap machinery
    must beat the ladder where latency is REAL, not just on /tmp
    (acceptance: >=1.5x, medians of 3). A third leg relaunches the
    same program with resume=True against the committed runs:
    ``em_resume_saved_frac`` is the fraction of the full run's wall
    clock the merge-only restart saves. ``em_remote_gets`` /
    ``em_remote_puts`` / ``em_remote_get_p50_ms`` come from the
    process-global transport counters (common/iostats.py +
    vfs/object_store.py), deltas around the overlap leg."""
    try:
        import dataclasses

        from thrill_tpu.api import Run
        from thrill_tpu.common.config import Config
        from thrill_tpu.common.iostats import IO
        from thrill_tpu.tools.object_server import ObjectServer
        from thrill_tpu.vfs import object_store

        n = 1 << 18
        try:
            n = int(os.environ.get(
                "THRILL_TPU_BENCH_EM_REMOTE_N", "") or n)
        except ValueError:
            pass
        lat_s = 0.02
        try:
            lat_s = float(os.environ.get(
                "THRILL_TPU_BENCH_REMOTE_LAT_MS", "") or 20.0) / 1e3
        except ValueError:
            pass
        rng = np.random.default_rng(5)
        vals = rng.integers(0, 1 << 48, size=n).tolist()
        prev = {k: os.environ.get(k) for k in
                ("THRILL_TPU_HOST_SORT_RUN",
                 "THRILL_TPU_SPILL_RESIDENT",
                 "THRILL_TPU_PREFETCH", "THRILL_TPU_WRITEBACK")}
        os.environ["THRILL_TPU_HOST_SORT_RUN"] = str(n // 40)
        os.environ["THRILL_TPU_SPILL_RESIDENT"] = "32M"
        # no epoch auto-resume: the resume leg must exercise the RUN
        # store (merge-only restart), not an epoch restore
        base = dataclasses.replace(Config.from_env(), ckpt_dir="",
                                   ckpt_auto=False, resume=False)
        stats_box: dict = {}

        def job_for(url):
            def job(ctx):
                node = ctx.ReadLines(f"{url}/b/in-*").Sort().node
                hs = node.materialize()
                stats_box.clear()
                stats_box.update(getattr(node, "_em_stats", {}) or {})
                return sum(len(lst) for lst in hs.lists)
            return job

        def leg(url, ck, resume=False):
            cfg = dataclasses.replace(base, ckpt_dir=ck, resume=resume)
            t0 = time.perf_counter()
            got = Run(job_for(url), cfg, resume=resume)
            dt = time.perf_counter() - t0
            if got != n:
                raise RuntimeError(f"em-remote lost items: {got}/{n}")
            return dt

        def med(fn):
            return sorted(fn() for _ in range(3))[1]

        try:
            with ObjectServer(latency_s=lat_s) as srv:
                shard = max(1, n // 8)
                for s in range(8):
                    body = "\n".join(
                        f"key-{v:014d}"
                        for v in vals[s * shard:(s + 1) * shard])
                    srv.put(f"b/in-{s:02d}.txt",
                            body.encode() + b"\n")
                ck_a = f"{srv.url}/b/ck-a"
                ck_b = f"{srv.url}/b/ck-b"
                leg(srv.url, ck_a)            # warmup (ctypes, compile)
                object_store.latency_reset()
                s0 = IO.snapshot()
                dt = med(lambda: leg(srv.url, ck_a))
                ov_stats = dict(stats_box)    # overlap leg's _em_stats
                s1 = IO.snapshot()
                p50 = object_store.get_p50_ms()
                os.environ["THRILL_TPU_PREFETCH"] = "0"
                os.environ["THRILL_TPU_WRITEBACK"] = "0"
                sync_dt = med(lambda: leg(srv.url, ck_b))
                os.environ.pop("THRILL_TPU_PREFETCH", None)
                os.environ.pop("THRILL_TPU_WRITEBACK", None)
                r0 = IO.snapshot()
                res_dt = med(
                    lambda: leg(srv.url, ck_a, resume=True))
                r1 = IO.snapshot()
        finally:
            for k, v in prev.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v
        legs = 3                              # counters span the median triple
        return {
            "em_remote_mitems_s": round(n / dt / 1e6, 3),
            "em_remote_overlap_ab": round(sync_dt / dt, 3),
            "em_remote_overlap_frac": ov_stats.get("overlap_frac",
                                                   0.0),
            "em_remote_gets": (s1["remote_gets"]
                               - s0["remote_gets"]) // legs,
            "em_remote_puts": (s1["remote_puts"]
                               - s0["remote_puts"]) // legs,
            "em_remote_get_p50_ms": round(p50, 2),
            "em_resume_saved_frac": round(
                max(0.0, 1.0 - res_dt / dt), 4),
            "em_resume_runs_reused": (r1["runs_reused"]
                                      - r0["runs_reused"]) // legs,
        }
    except Exception as e:  # tertiary metric never kills the line
        return {"em_remote_error": repr(e)[:200]}


def _em_akey(t):
    return t[0]


def _em_array_metric(ctx) -> dict:
    """Array-payload spill A/B (ISSUE 17 edge f): host EM sort of
    (key, float64[W]) tuples (W=32 default) — the PageRank-shaped payload
    that dominates remote writes — with the native columnar record
    format ON (each ndarray leaf rides one (N, 16) column,
    data/records.py) vs OFF (per-item pickle, the pre-tier cost).
    Medians of 3; acceptance pins records-on >= 1.2x."""
    try:
        n = 1 << 16
        try:
            n = int(os.environ.get(
                "THRILL_TPU_BENCH_EM_ARRAY_N", "") or n)
        except ValueError:
            pass
        w = 32
        try:
            w = int(os.environ.get(
                "THRILL_TPU_BENCH_EM_ARRAY_W", "") or w)
        except ValueError:
            pass
        rng = np.random.default_rng(9)
        keys = rng.integers(0, 1 << 44, size=n).tolist()
        payload = rng.standard_normal((n, w))
        items = [(f"k-{k:014d}", payload[i])
                 for i, k in enumerate(keys)]
        prev = {k: os.environ.get(k) for k in
                ("THRILL_TPU_HOST_SORT_RUN",
                 "THRILL_TPU_SPILL_RESIDENT",
                 "THRILL_TPU_NATIVE_RECORDS")}
        os.environ["THRILL_TPU_HOST_SORT_RUN"] = str(n // 40)
        os.environ["THRILL_TPU_SPILL_RESIDENT"] = "32M"

        def run_once():
            d = ctx.Distribute(list(items), storage="host")
            t0 = time.perf_counter()
            node = d.Sort(key_fn=_em_akey).node
            hs = node.materialize()
            dt = time.perf_counter() - t0
            got = sum(len(lst) for lst in hs.lists)
            if got != n:
                raise RuntimeError(f"em-array lost items: {got}/{n}")
            return dt, getattr(node, "_em_stats", {}) or {}

        def med():
            return sorted((run_once() for _ in range(3)),
                          key=lambda r: r[0])[1]

        try:
            run_once()                        # warmup
            dt, stats = med()
            os.environ["THRILL_TPU_NATIVE_RECORDS"] = "0"
            pk_dt, _ = med()
        finally:
            for k, v in prev.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v
        return {
            "em_array_mitems_s": round(n / dt / 1e6, 3),
            "em_array_records_ab": round(pk_dt / dt, 3),
            "em_array_records_blocks": stats.get("records_blocks", 0),
        }
    except Exception as e:  # tertiary metric never kills the line
        return {"em_array_error": repr(e)[:200]}


def _serve_kv(x):
    return (x % 257, x)


def _serve_add(a, b):
    return a + b


def _serve_metric(ctx) -> dict:
    """Sustained-traffic serve lane (service/scheduler.py): closed-loop
    client threads — each submits its next job only after the previous
    one resolved — driving a mixed WordCount-shaped ReduceByKey /
    PageRank workload through ``ctx.submit`` under two tenants.
    Reports queries/s, p50/p99 submit-to-result latency, mean queue
    wait, and the plan-store hit counter (nonzero when the operator
    exported THRILL_TPU_PLAN_STORE and this process warm-started), so
    a serving-throughput regression is as loud as a dispatch-budget
    one. Sizes stay small: the lane measures the service plane's
    overhead and fairness machinery, not raw operator throughput (the
    dedicated lanes above own that)."""
    try:
        import threading

        _examples_path()
        import page_rank as pr
        doc_snap = _doctor_snapshot(getattr(ctx, "doctor", None))
        n_wc = 1 << 13
        edges = pr.zipf_graph(512, 1 << 12, seed=5)
        try:
            clients = int(os.environ.get("THRILL_TPU_BENCH_SERVE_CLIENTS",
                                         "") or 3)
            per_client = int(os.environ.get("THRILL_TPU_BENCH_SERVE_JOBS",
                                            "") or 4)
        except ValueError:
            clients, per_client = 3, 4
        data = np.arange(n_wc, dtype=np.int64)

        def wordcount_job(c):
            c.Distribute(data).Map(_serve_kv).ReducePair(
                _serve_add).Size()
            return None

        def pagerank_job(c):
            return pr.page_rank(c, edges, 512, iterations=2)

        # warmup through the scheduler so compiles stay out of the
        # timed window (every other lane warms up the same way);
        # bounded like the client loop — a wedged dispatcher must
        # degrade to serve_error, never hang the whole bench line
        ctx.submit(wordcount_job, tenant="t0").result(600)
        ctx.submit(pagerank_job, tenant="t1").result(600)

        lat: list = []
        waits: list = []
        choices: list = []
        errors: list = []
        lock = threading.Lock()

        def client(i: int):
            for j in range(per_client):
                fn = wordcount_job if (i + j) % 2 == 0 else pagerank_job
                t0 = time.perf_counter()
                try:
                    fut = ctx.submit(fn, tenant=f"t{i % 2}",
                                     name=f"c{i}-j{j}")
                    fut.result(600)
                except Exception as e:  # noqa: BLE001
                    with lock:
                        errors.append(repr(e)[:200])
                    return
                with lock:
                    lat.append(time.perf_counter() - t0)
                    waits.append(fut.queue_wait_s)
                    choices.append(fut.plan_decisions)

        threads = [threading.Thread(target=client, args=(i,), daemon=True)
                   for i in range(clients)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        if errors or not lat:
            return {"serve_error": (errors or ["no jobs completed"])[0]}
        lat.sort()
        stats = ctx.overall_stats()
        return {
            "serve_qps": round(len(lat) / wall, 3),
            "serve_p50_ms": round(lat[len(lat) // 2] * 1e3, 2),
            "serve_p99_ms": round(
                lat[min(int(len(lat) * 0.99), len(lat) - 1)] * 1e3, 2),
            "serve_jobs": len(lat),
            "queue_wait_s": round(sum(waits) / len(waits), 4),
            "queue_depth_peak": int(stats.get("queue_depth_peak", 0)),
            # bounded admission (ISSUE 16): 0 on this uncapped lane —
            # a nonzero value means something set THRILL_TPU_SERVE_QUEUE
            # and the closed-loop clients still managed to trip it
            "serve_jobs_rejected": int(stats.get("jobs_rejected", 0)),
            "plan_store_hits": int(stats.get("plan_store_hits", 0)),
            "plan_builds": int(stats.get("plan_builds", 0)),
            # plan choices the decision ledger recorded per served job
            # (mean/max across the lane's jobs) and re-optimizations
            # the adaptive planner fired while serving — steady-state
            # serving should trend toward 0 choices per job (every
            # plan cached or seeded) and 0 replans
            "serve_plan_choices_per_job": round(
                sum(choices) / len(choices), 2) if choices else 0.0,
            "serve_plan_choices_max": int(max(choices)) if choices
            else 0,
            "serve_planner_replans": int(
                stats.get("planner_replans", 0)),
            # deterministic-bucket twins of the wall-clock quantiles:
            # the scheduler's per-tenant log2 histograms (ISSUE 14;
            # worst tenant shown — the per-tenant split lives in
            # overall_stats serve_p50_ms/serve_p99_ms)
            "serve_hist_p50_ms": max(
                (stats.get("serve_p50_ms") or {}).values(),
                default=0.0),
            "serve_hist_p99_ms": max(
                (stats.get("serve_p99_ms") or {}).values(),
                default=0.0),
            # doctor lane: the serve lane's OWN exchange-barrier
            # seconds and worst skew (per-lane deltas — the shared
            # ctx's lifetime totals include every earlier lane)
            **_doctor_fields(getattr(ctx, "doctor", None), doc_snap,
                             "serve"),
        }
    except Exception as e:  # secondary metric never kills the line
        return {"serve_error": repr(e)[:200]}


def _front_door_metric(ctx) -> dict:
    """External-traffic lane (ISSUE 18, service/front_door.py): N REAL
    socket clients — the full admission protocol, auth flag, framing,
    chunked result streaming — driving the same mixed WordCount/
    PageRank tenants through a FrontDoor at ~2x overload. The
    per-tenant token-bucket rate is set to HALF the capacity the
    warmup measured, so the closed-loop clients (offering at about
    capacity) run the shed path for real: the lane reports
    accept-to-result p50/p99 for SERVED jobs and the served-vs-
    rejected split — all of it also exported through the existing
    Prometheus surface (fd_* counters and the serve latency
    histograms ride overall_stats, common/metrics.py)."""
    try:
        import threading

        from thrill_tpu.service.client import FrontDoorClient, Rejected
        from thrill_tpu.service.front_door import FrontDoor
        from thrill_tpu.service.scheduler import _parse_rates

        _examples_path()
        import page_rank as pr
        doc_snap = _doctor_snapshot(getattr(ctx, "doctor", None))
        edges = pr.zipf_graph(512, 1 << 12, seed=5)
        data = np.arange(1 << 13, dtype=np.int64)
        try:
            clients = int(os.environ.get("THRILL_TPU_BENCH_FD_CLIENTS",
                                         "") or 4)
            per_client = int(os.environ.get("THRILL_TPU_BENCH_FD_JOBS",
                                            "") or 6)
        except ValueError:
            clients, per_client = 4, 6

        def wordcount_pipe(c, args):
            c.Distribute(data).Map(_serve_kv).ReducePair(
                _serve_add).Size()
            return None

        def pagerank_pipe(c, args):
            return pr.page_rank(c, edges, 512, iterations=2)

        fd = FrontDoor(ctx, port=0)
        fd.register("wc", wordcount_pipe)
        fd.register("pr", pagerank_pipe)
        try:
            # warmup over the socket (compiles out of the timed
            # window) doubles as the capacity probe for the 2x
            # overload point
            t0 = time.perf_counter()
            with FrontDoorClient("127.0.0.1", fd.port,
                                 tenant="t0") as wcli:
                wcli.submit("wc", None).result(600)
                wcli.submit("pr", None).result(600)
            cap_qps = 2.0 / max(time.perf_counter() - t0, 1e-3)
            # per-tenant rate = capacity/(2*tenants): total admitted
            # ~= capacity/2 while the clients offer ~capacity -> 2x.
            # Closed-loop algebra: a reject is instant, a served job
            # holds its client for ~1/capacity, so per tenant
            # served ~= rate*wall + burst ~= served/2 + burst, i.e.
            # served ~= 2*burst. burst = offered/(tenants*4) puts the
            # split near half served / half shed.
            burst = max(per_client * clients // 8, 1)
            svc = ctx.service
            prev_rates, prev_buckets = svc._rates, svc._buckets
            svc._rates = _parse_rates(
                f"default={max(cap_qps / 4.0, 0.1):.4f}:{burst}")
            svc._buckets = {}

            lat: list = []
            rejected = [0]
            errors: list = []
            lock = threading.Lock()

            def client(i: int):
                try:
                    with FrontDoorClient("127.0.0.1", fd.port,
                                         tenant=f"t{i % 2}") as c:
                        for j in range(per_client):
                            name = "wc" if (i + j) % 2 == 0 else "pr"
                            t1 = time.perf_counter()
                            try:
                                c.submit(name, None).result(600)
                            except Rejected:
                                with lock:
                                    rejected[0] += 1
                                continue
                            with lock:
                                lat.append(time.perf_counter() - t1)
                except Exception as e:  # noqa: BLE001
                    with lock:
                        errors.append(repr(e)[:200])

            threads = [threading.Thread(target=client, args=(i,),
                                        daemon=True)
                       for i in range(clients)]
            t0 = time.perf_counter()
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            wall = time.perf_counter() - t0
            svc._rates, svc._buckets = prev_rates, prev_buckets
            if errors or not lat:
                return {"fd_error": (errors
                                     or ["no jobs served"])[0]}
            lat.sort()
            stats = ctx.overall_stats()
            return {
                "fd_qps": round(len(lat) / wall, 3),
                "fd_p50_ms": round(lat[len(lat) // 2] * 1e3, 2),
                "fd_p99_ms": round(
                    lat[min(int(len(lat) * 0.99),
                            len(lat) - 1)] * 1e3, 2),
                # served-vs-rejected under ~2x overload: BOTH must be
                # nonzero for the lane to have exercised shed-load
                "fd_served": len(lat),
                "fd_rejected": rejected[0],
                "fd_conns": int(stats.get("fd_conns_accepted", 0)),
                "fd_chunks": int(stats.get("fd_chunks_sent", 0)),
                # 0 on a healthy lane: loopback clients drain fine
                "fd_slow_clients": int(
                    stats.get("fd_slow_clients", 0)),
                **_doctor_fields(getattr(ctx, "doctor", None),
                                 doc_snap, "fd"),
            }
        finally:
            fd.close(drain=False)
    except Exception as e:  # secondary metric never kills the line
        return {"fd_error": repr(e)[:200]}


_AB_CODE = r'''
import json
import os
import sys
import time

import numpy as np

from thrill_tpu.api import Context
from thrill_tpu.parallel.mesh import MeshExec

ctx = Context(MeshExec(num_workers=4))
mex = ctx.mesh_exec
rng = np.random.default_rng(41)
n = 1 << 15
vals = rng.integers(0, 1 << 20, size=n).astype(np.int64)
pay = rng.integers(0, 1 << 10, size=n).astype(np.int32)


def once():
    sh = ctx.Distribute({"k": vals, "p": pay}).Sort(
        key_fn=lambda t: t["k"]).node.materialize()
    import jax
    jax.block_until_ready(jax.tree.leaves(sh.tree))


once()                                       # compile leg
t0 = time.perf_counter()
once()                                       # steady-state leg
dt = time.perf_counter() - t0
st = ctx.overall_stats()
print("ABLANE " + json.dumps({
    "s": round(dt, 4),
    "wire": int(st["bytes_wire_device"]),
    "wire_raw": int(st["bytes_wire_device_raw"])}))
ctx.close()
'''


def _pallas_ab_metric() -> dict:
    """Paired A/B lanes (ISSUE 19): the SAME W=4 Sort pipeline under
    flipped single knobs, each leg its own process so executable caches
    and learned specs never bleed across legs — (a) phase-B narrowing
    on vs off (wire bytes are the primary observable; wall clock on a
    CPU rig mostly prices the cast), and (b) the radix engine vs the
    default engine choice. The presorted exchange path is forced
    (SORT_FUSED=0) so both knobs actually engage."""

    def leg(extra):
        env = dict(os.environ)
        env.update({"JAX_PLATFORMS": "cpu",
                    "XLA_FLAGS":
                        "--xla_force_host_platform_device_count=4",
                    "THRILL_TPU_SORT_FUSED": "0"})
        env.update(extra)
        try:
            out = subprocess.run([sys.executable, "-c", _AB_CODE],
                                 env=env, capture_output=True,
                                 text=True, timeout=900)
            for line in reversed(out.stdout.splitlines()):
                if line.startswith("ABLANE "):
                    return json.loads(line[len("ABLANE "):])
            return {"error": (out.stderr or "no ABLANE line")[-200:]}
        except Exception as e:   # secondary metric never kills the line
            return {"error": repr(e)[:200]}

    non = leg({"THRILL_TPU_XCHG_NARROW": "1"})
    noff = leg({"THRILL_TPU_XCHG_NARROW": "0"})
    rad = leg({"THRILL_TPU_SORT_IMPL": "radix"})
    auto = leg({"THRILL_TPU_SORT_IMPL": "auto"})
    out = {}
    if "error" not in non and "error" not in noff:
        out.update(ab_narrow_on_s=non["s"], ab_narrow_off_s=noff["s"],
                   ab_narrow_wire=non["wire"],
                   ab_narrow_off_wire=noff["wire"],
                   ab_narrow_wire_ratio=round(
                       non["wire"] / noff["wire"], 3)
                   if noff["wire"] else 1.0)
    else:
        out["ab_narrow_error"] = str(
            non.get("error") or noff.get("error"))[:200]
    if "error" not in rad and "error" not in auto:
        out.update(ab_radix_s=rad["s"], ab_engine_auto_s=auto["s"])
    else:
        out["ab_engine_error"] = str(
            rad.get("error") or auto.get("error"))[:200]
    return out


_ELASTIC_CODE = r'''
import json

import numpy as np

from thrill_tpu.api import Context
from thrill_tpu.parallel.mesh import MeshExec

ctx = Context(MeshExec(num_workers=2))


def job(c):
    return int(c.Distribute(np.arange(1 << 12, dtype=np.int64)).Map(
        lambda x: x % 97).Sum())


ctx.submit(job, tenant="a").result(300)     # start + warm the service
f1 = [ctx.submit(job, tenant="a") for _ in range(2)]
up = ctx.resize(3)                          # fenced: lands mid-stream
f2 = [ctx.submit(job, tenant="b") for _ in range(2)]
down = ctx.resize(2)
want = job(Context(MeshExec(num_workers=2)))
assert all(f.result(300) == want for f in f1 + f2)
st = ctx.overall_stats()
print("ELASTIC " + json.dumps({
    "resize_up_s": round(up, 4), "resize_down_s": round(down, 4),
    "resize_time_s": round(float(st["resize_time_s"]), 4),
    "resizes": int(st["resizes"]),
    "jobs_rejected": int(st["jobs_rejected"])}))
ctx.close()
'''


def _elastic_metric() -> dict:
    """Elastic-mesh micro-lane (ISSUE 16): a serving Context resizes
    W=2->3->2 through the scheduler fence under a live job stream —
    reports the resize wall time (the re-partition + generation-bump
    cost the elastic protocol adds at a W change) and the shed-load
    counter (0 on this uncapped lane: elastic machinery costs nothing
    when unused). Runs out-of-process with a forced 4-device CPU mesh
    because the elastic protocol needs more addressable devices than
    the main bench mesh has on a 1-device CPU rig."""
    env = dict(os.environ)
    env.update({"JAX_PLATFORMS": "cpu",
                "XLA_FLAGS": "--xla_force_host_platform_device_count=4"})
    env.pop("THRILL_TPU_SERVE_QUEUE", None)
    try:
        out = subprocess.run([sys.executable, "-c", _ELASTIC_CODE],
                             env=env, capture_output=True, text=True,
                             timeout=900)
        for line in reversed(out.stdout.splitlines()):
            if line.startswith("ELASTIC "):
                return json.loads(line[len("ELASTIC "):])
        return {"resize_error":
                (out.stderr or "no ELASTIC line")[-200:]}
    except Exception as e:  # secondary metric never kills the line
        return {"resize_error": repr(e)[:200]}


_ELASTIC_PROC_CODE = r'''
import json
import os
import time

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

from thrill_tpu.common.platform import force_cpu_platform

force_cpu_platform()

import numpy as np

from thrill_tpu.api import Context
from thrill_tpu.api.context import ResizeRelaunch
from thrill_tpu.common.config import Config
from thrill_tpu.parallel.mesh import MeshExec
from thrill_tpu.service.autoscale import AutoscalePolicy, Autoscaler
from thrill_tpu.service.client import FrontDoorClient
from thrill_tpu.service.front_door import FrontDoor

HOT = {"queue_depth": 99, "jobs_rejected": 0, "jobs_in_flight": 2,
       "serve_p99_ms": 0.0}
IDLE = {"queue_depth": 0, "jobs_rejected": 0, "jobs_in_flight": 0,
        "serve_p99_ms": 0.0}


def _wc(c, args):
    hist = c.Distribute(np.arange(256, dtype=np.int64)).Map(
        lambda x: (x % 7, 1)).ReducePair(lambda a, b: a + b)
    return sorted([int(k), int(v)] for k, v in hist.AllGather())


ck = os.environ["THRILL_TPU_CKPT_DIR"]
phase = int(os.environ.get("THRILL_TPU_SUPERVISE_ROUND", "0"))
w = int(os.environ.get("THRILL_TPU_RESIZE_W", "2"))
resumed = os.environ.get("THRILL_TPU_RESUME") == "1"

ctx = Context(MeshExec(num_workers=w), config=Config(ckpt_dir=ck),
              resume=resumed)
out = {"phase": phase, "w": w}
d = ctx.Distribute(np.arange(1 << 10, dtype=np.int64)).Map(
    lambda x: x * 3 + 1).Checkpoint("stage")
d.Keep(4)
d.Execute()

# the move clock spans two processes: the exiting phase stamps
# wall time right before ResizeRelaunch, the resumed phase reads
# it back once its state is restored and serving again
stamp = os.path.join(ck, "bench_move_t0.json")
if resumed and os.path.isfile(stamp):
    with open(stamp) as f:
        rec = json.load(f)
    os.remove(stamp)
    out["move_s"] = round(time.time() - rec["t"], 4)
    out["move_to"] = rec["to"]
    out["resume_skipped_ops"] = int(
        ctx.overall_stats().get("resume_skipped_ops", 0))

# live front-door traffic: a real loopback socket client with jobs
# still in flight when the move begins (the drain resolves them)
fd = FrontDoor(ctx, port=0)
fd.register("wc", _wc)
cli = FrontDoorClient("127.0.0.1", fd.port, tenant="bench")
want = cli.submit("wc", None).result(300)
live = [cli.submit("wc", None) for _ in range(2)]
for j in live:
    # admitted but unread: the move's drain must finish these (a
    # submit still in the socket gets a draining reject instead —
    # not the in-flight shape this lane times)
    j.wait_accepted(60)

if phase >= 2:
    assert all(j.result(300) == want for j in live)
    cli.close()
    print("ELASTIC_PROC " + json.dumps(out), flush=True)
    ctx.close()
else:
    a = Autoscaler(ctx, policy=AutoscalePolicy(
        min_w=2, max_w=3, up_queue=8, confirm_ticks=2,
        idle_ticks=2, cooldown_ticks=0))
    target = None
    for m in [HOT] * 4 if phase == 0 else [IDLE] * 4:
        target = a.observe(m, ctx.num_workers)
        if target is not None:
            break
    assert target == (3 if phase == 0 else 2), target
    out["decisions"] = a.decisions_made
    try:
        ctx.resize_processes(target, state=d)
    except ResizeRelaunch:
        # the drain already resolved the in-flight socket jobs
        assert all(j.result(30) == want for j in live)
        out["seal_s"] = round(ctx.stats_resize_time_s, 4)
        with open(stamp, "w") as f:
            json.dump({"t": time.time(), "to": target}, f)
        print("ELASTIC_PROC " + json.dumps(out), flush=True)
        raise
    raise AssertionError("resize_processes returned")
'''


def _elastic_proc_metric() -> dict:
    """Supervised process-elasticity lane (ISSUE 20): a 2-process-
    shaped run under run-scripts/supervise.sh walks W=2->3->2 through
    the REAL autoscaling policy (injected hot/idle metric sequences)
    with live front-door socket traffic in flight at each move —
    reports the full move walls (exit-to-serving-again, up and down:
    the relaunch + RESIZE-epoch resume cost process elasticity adds
    over the in-process fenced resize above) and the policy decision
    count. Out-of-process like the elastic micro-lane, plus the
    supervisor in between."""
    import shutil
    import tempfile
    sup = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "run-scripts", "supervise.sh")
    td = tempfile.mkdtemp(prefix="ttpu-bench-elproc-")
    env = dict(os.environ)
    for k in ("XLA_FLAGS", "THRILL_TPU_RESUME", "THRILL_TPU_RESIZE_W",
              "THRILL_TPU_SERVE_QUEUE", "THRILL_TPU_AUTOSCALE_S"):
        env.pop(k, None)
    env.update({"JAX_PLATFORMS": "cpu",
                "THRILL_TPU_CKPT_DIR": os.path.join(td, "ck"),
                # the in-flight jobs compile fresh XLA programs at the
                # new W; don't let a loaded rig turn a slow compile
                # into a spurious drain abort
                "THRILL_TPU_RESIZE_TIMEOUT_S": "120"})
    try:
        out = subprocess.run(
            ["bash", sup, "-n", "2", "--", sys.executable, "-c",
             _ELASTIC_PROC_CODE],
            env=env, capture_output=True, text=True, timeout=1200)
        lines = [json.loads(l[len("ELASTIC_PROC "):])
                 for l in out.stdout.splitlines()
                 if l.startswith("ELASTIC_PROC ")]
        if out.returncode != 0 or len(lines) != 3:
            return {"resize_proc_error":
                    (out.stderr or "bad phase count")[-200:]}
        up = next(l for l in lines if l.get("move_to") == 3)
        down = next(l for l in lines if l.get("move_to") == 2)
        return {
            "resize_proc_up_s": up["move_s"],
            "resize_proc_down_s": down["move_s"],
            # in-process share of the moves (drain+seal+gate+marker)
            "resize_proc_seal_s": round(sum(
                l.get("seal_s", 0.0) for l in lines), 4),
            "autoscale_decisions": sum(
                l.get("decisions", 0) for l in lines),
        }
    except Exception as e:  # secondary metric never kills the line
        return {"resize_proc_error": repr(e)[:200]}
    finally:
        shutil.rmtree(td, ignore_errors=True)


def _ckpt_metric(n: int) -> dict:
    """Opt-in (THRILL_TPU_BENCH_CKPT=1) durability-cost metric: the
    same Sort pipeline run bare vs with a per-stage Checkpoint()
    (api/checkpoint.py), plus a resumed run. Records
    ``ckpt_overhead_frac`` (fractional slowdown the epoch writes add)
    and ``recovery_time_s`` (restore cost on resume) so the BENCH_*
    trajectory tracks what durability costs as the engine gets
    faster."""
    try:
        import shutil
        import tempfile

        from thrill_tpu.api import Run
        from thrill_tpu.common.config import Config
        n = min(n, 1 << 16)           # durability cost, not throughput
        rng = np.random.default_rng(7)
        recs = {
            "key": rng.integers(0, 256, size=(n, 10)).astype(np.uint8),
            "value": rng.integers(0, 256, size=(n, 22)).astype(np.uint8),
        }

        bytes_holder = {}

        def job(ctx, ckpt):
            d = ctx.Distribute(recs).Sort(key_fn=_key_fn)
            if ckpt:
                d = d.Checkpoint("bench-sort")
            shards = d.node.materialize()
            import jax
            jax.block_until_ready(jax.tree.leaves(shards.tree))
            if ckpt and ctx.checkpoint is not None \
                    and ctx.checkpoint.bytes_written:
                bytes_holder["b"] = ctx.checkpoint.bytes_written
            return None

        td = tempfile.mkdtemp(prefix="ttpu-bench-ckpt-")
        try:
            import dataclasses
            # both legs inherit the SAME env-tuned engine config
            # (worker count, sort engine, exchange...) but the
            # checkpoint knobs are pinned per leg: the plain leg must
            # not auto-checkpoint because the operator happens to have
            # THRILL_TPU_CKPT_DIR/_AUTO/_RESUME exported, and the
            # bench must never write epochs into a real checkpoint dir
            base = dataclasses.replace(Config.from_env(), ckpt_dir="",
                                       ckpt_auto=False, resume=False)
            cfg = dataclasses.replace(base, ckpt_dir=td)
            Run(lambda ctx: job(ctx, False), base)    # warmup/compile
            dt_plain, _ = _best_of(
                lambda: Run(lambda ctx: job(ctx, False), base), iters=2)
            dt_ckpt, _ = _best_of(
                lambda: Run(lambda ctx: job(ctx, True), cfg), iters=2)

            # recovery: a fresh resumed run restores the newest epoch
            rec_holder = {}

            def resumed(ctx):
                job(ctx, True)
                rec_holder.update(ctx.overall_stats())
                return None

            Run(resumed, cfg, resume=True)
            return {
                "ckpt_overhead_frac": round(
                    max(dt_ckpt / dt_plain - 1.0, 0.0), 4),
                "ckpt_bytes": int(bytes_holder.get("b", 0)),
                "recovery_time_s": rec_holder.get("recovery_time_s",
                                                  0.0),
                "resume_skipped_ops": int(rec_holder.get(
                    "resume_skipped_ops", 0)),
            }
        finally:
            shutil.rmtree(td, ignore_errors=True)
    except Exception as e:  # opt-in metric never kills the line
        return {"ckpt_error": repr(e)[:200]}


def main():
    _run_bench()


if __name__ == "__main__":
    main()
