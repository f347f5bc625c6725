"""Dispatch-budget regression tests.

Every dispatch and every host sync pays a fixed launch cost, so DISPATCH
AND SYNC COUNT, not FLOPs or bytes, governs small-to-medium pipeline
cost. These tests pin the budgets so a future change can't silently add a
mid-pipeline host sync or an uncached plan upload. (The reference has
no analog: its workers run host-side, a "dispatch" is a function call.
This is the TPU-native counterpart of its no-per-item-virtual-call
discipline, SURVEY.md §7.)

THRILL_TPU_HOST_RADIX=0 forces the jitted device engines on the CPU
test mesh (otherwise W=1 sorts/reduces run in the native host engine
with zero device dispatches, which is correct but not what these tests
measure).
"""

from __future__ import annotations

import os
import sys

import numpy as np
import pytest

import jax

from thrill_tpu.api import Bind, Context, FieldReduce, InnerJoin
from thrill_tpu.parallel.mesh import MeshExec

_EXAMPLES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "..", "..", "examples")


@pytest.fixture(autouse=True)
def _force_device_engines(monkeypatch):
    monkeypatch.setenv("THRILL_TPU_HOST_RADIX", "0")


def _snap(mex):
    return np.array([mex.stats_dispatches, mex.stats_uploads,
                     mex.stats_fetches])


def _key(t):
    return t["key"]


def _wc_key(t):
    return t["w"]


def _terasort_data(n):
    rng = np.random.default_rng(0)
    return {"key": rng.integers(0, 256, size=(n, 10)).astype(np.uint8),
            "value": rng.integers(0, 256, size=(n, 90)).astype(np.uint8)}


def test_terasort_w1_single_dispatch():
    """The whole W=1 sort (encode + argsort + payload gather) is ONE
    fused program, zero plan uploads, zero syncs in steady state."""
    mex = MeshExec(num_workers=1)
    ctx = Context(mex)
    inp = ctx.Distribute(_terasort_data(2048))
    jax.block_until_ready(jax.tree.leaves(
        inp.node.materialize(consume=False).tree))

    def run():
        inp.Keep()
        sh = inp.Sort(key_fn=_key).node.materialize()
        jax.block_until_ready(jax.tree.leaves(sh.tree))

    run()                                     # warm (compile + caches)
    s0 = _snap(mex)
    run()
    assert tuple(_snap(mex) - s0) == (1, 0, 0)


def _off_alignment(a):
    """``a`` at an address that is 16 modulo 64: jax's CPU client keeps
    a 64-byte aligned host array as the device buffer itself, so there
    the staging copies after all (tests/data/test_shards_staging.py);
    on the chip no address does that."""
    raw = np.empty(a.nbytes + 128, np.uint8)
    start = (16 - raw.ctypes.data) % 64
    out = raw[start:start + a.nbytes].view(a.dtype).reshape(a.shape)
    out[...] = a
    return out


@pytest.mark.parametrize("W", [1, 4])
def test_terasort_power_of_two_stages_without_a_host_copy(W):
    """Distribute -> Sort at a power-of-two size pads nothing, so the
    input goes up as a view of itself: ``stage_copy_bytes`` 0 while
    every byte still reaches the device. A copy put back into the
    staging fails here before it costs a chip run (ISSUE 27: it was
    1.8 of 3.1 s of a 2^23-record job on the chip)."""
    mex = MeshExec(num_workers=W)
    ctx = Context(mex)
    data = {k: _off_alignment(v) for k, v in _terasort_data(4096).items()}
    want = data["key"][np.lexsort(data["key"].T[::-1])]
    s0 = ctx.overall_stats()
    got = ctx.Distribute(data).Sort(key_fn=_key).AllGatherArrays()
    s1 = ctx.overall_stats()
    assert s1["stage_copy_bytes"] - s0["stage_copy_bytes"] == 0
    assert s1["upload_bytes"] - s0["upload_bytes"] >= 4096 * 100
    assert np.array_equal(got["key"], want)


def test_wordcount_w1_single_dispatch():
    mex = MeshExec(num_workers=1)
    ctx = Context(mex)
    n = 2048
    rng = np.random.default_rng(1)
    words = rng.integers(0, 64, size=(n, 8)).astype(np.uint8)
    d = ctx.Distribute({"w": words, "c": np.ones(n, np.int64)})
    d.Keep()
    red = FieldReduce({"w": "first", "c": "sum"})

    def run():
        d.Keep()
        sh = d.ReduceByKey(_wc_key, red).node.materialize()
        jax.block_until_ready(jax.tree.leaves(sh.tree))

    run()
    s0 = _snap(mex)
    run()
    assert tuple(_snap(mex) - s0) == (1, 0, 0)


def test_pagerank_full_run_budget():
    """A full 4-iteration PageRank run: plan uploads stay cached
    (put_small), join size syncs are skipped (out_size_hint), map
    stacks hand host counts through — at most one blocking fetch for
    the entire run (the final AllGather egress)."""
    sys.path.insert(0, _EXAMPLES)
    import page_rank as pr
    mex = MeshExec(num_workers=1)
    ctx = Context(mex)
    edges = pr.zipf_graph(512, 4096)
    want = pr.page_rank_dense(ctx, edges, 512, iterations=4)
    got = pr.page_rank(ctx, edges, 512, iterations=4)   # warm + parity
    assert np.allclose(got, want, rtol=1e-6)
    s0 = _snap(mex)
    pr.page_rank(ctx, edges, 512, iterations=4)
    disp, up, fetch = (_snap(mex) - s0).tolist()
    assert disp <= 40, disp
    assert up <= 4, up
    assert fetch <= 2, fetch


def test_kmeans_full_run_zero_syncs():
    """The Lloyd loop never blocks: device-resident centroids via
    AllGatherArrays + Bind; ZERO fetches for the whole run."""
    sys.path.insert(0, _EXAMPLES)
    import k_means as km
    mex = MeshExec(num_workers=1)
    ctx = Context(mex)
    rng = np.random.default_rng(0)
    pts = rng.random((2048, 8)).astype(np.float64)
    centers0 = pts[np.random.default_rng(3).choice(
        2048, size=4, replace=False)].copy()
    want = km.k_means_dense(pts, centers0, 3)
    got = km.k_means(ctx, pts, 4, iterations=3, seed=3)   # warm + parity
    assert np.allclose(got, want, rtol=1e-8)
    s0 = _snap(mex)
    km.k_means(ctx, pts, 4, iterations=3, seed=3)
    disp, up, fetch = (_snap(mex) - s0).tolist()
    assert fetch == 0, fetch
    assert disp <= 10, disp
    assert up <= 2, up


def test_sgd_and_logreg_zero_syncs():
    """Gradient-descent loops (Bind model vector + Sum(device=True)):
    zero blocking fetches for whole runs."""
    sys.path.insert(0, _EXAMPLES)
    import logistic_regression as lr
    import sgd
    mex = MeshExec(num_workers=2)
    ctx = Context(mex)
    rng = np.random.default_rng(0)
    X = rng.normal(size=(1024, 4))
    y = (X @ np.ones(4) > 0).astype(np.float64)
    w = lr.logistic_regression(ctx, X, y, iterations=5)      # warm
    assert np.mean((X @ w > 0) == (y > 0.5)) > 0.9
    s0 = _snap(mex)
    lr.logistic_regression(ctx, X, y, iterations=5)
    disp, up, fetch = (_snap(mex) - s0).tolist()
    assert fetch == 0, fetch
    assert up <= 2, up
    sgd.sgd_linear(ctx, X, y * 2 - 1, iterations=5)          # warm
    s0 = _snap(mex)
    sgd.sgd_linear(ctx, X, y * 2 - 1, iterations=5)
    disp, up, fetch = (_snap(mex) - s0).tolist()
    assert fetch == 0, fetch


def test_suffix_doubling_one_upload_one_read_per_round():
    """The suffix-array doubling loop is ONE pipeline of operators: the
    text goes up once, every round (and the names step) reads ONE
    scalar back to decide whether another follows, and a round is four
    dispatches (the index column, the sorts with the pairing window
    between them, the naming, the reduction) at W=1."""
    sys.path.insert(0, _EXAMPLES)
    import suffix_sorting as ss
    mex = MeshExec(num_workers=1)
    ctx = Context(mex)
    rng = np.random.default_rng(7)
    text = rng.integers(97, 101, size=4096).astype(np.uint8)
    sa = ss.suffix_array(ctx, text)               # warm + parity
    sb = bytes(text)
    assert sorted(sa.tolist()) == list(range(len(text)))
    assert all(sb[sa[i]:] < sb[sa[i + 1]:]
               for i in range(0, len(sa) - 1, 29))
    s0 = _snap(mex)
    stats = {}
    ss.suffix_array(ctx, text, stats=stats)
    disp, up, fetch = (_snap(mex) - s0).tolist()
    rounds = stats["rounds"]
    assert up == 1, up
    assert fetch == rounds + 1, (fetch, rounds)
    # names: sort + naming + reduction; a round: the four above; the
    # result: its index column
    assert disp == 3 + 4 * rounds + 1, (disp, rounds)


def _wc_text_file(tmp_path):
    rng = np.random.default_rng(5)
    vocab = ["w%03d" % i for i in range(97)]
    path = tmp_path / "words.txt"
    path.write_text(" ".join(rng.choice(vocab, size=2048)) + "\n")
    return str(path)


def _wc_run(ctx, mex, path):
    """One WordCount example pipeline run; returns (result, dispatches)."""
    sys.path.insert(0, _EXAMPLES)
    import word_count as wc
    d0 = mex.stats_dispatches
    cols = jax.tree.map(np.asarray,
                        wc.word_count_text_device(ctx, path)
                        .AllGatherArrays())
    order = np.lexsort(tuple(cols["w"].T))
    return ({k: v[order] for k, v in cols.items()},
            mex.stats_dispatches - d0)


def test_wordcount_pipeline_fusion_budget(monkeypatch):
    """Pinned dispatch budget for the WordCount example pipeline
    (ReadWordsPacked -> Map -> ReduceByKey): program stitching fuses
    the Map stack into the reduce's local phase — ONE dispatch where
    the per-op model pays two. THRILL_TPU_FUSE=0 must restore the old
    count exactly."""
    mex = MeshExec(num_workers=1)
    ctx = Context(mex)
    import tempfile
    import pathlib
    with tempfile.TemporaryDirectory() as td:
        path = _wc_text_file(pathlib.Path(td))
        _wc_run(ctx, mex, path)                      # warm (fused)
        fused_res, fused = _wc_run(ctx, mex, path)
        monkeypatch.setenv("THRILL_TPU_FUSE", "0")
        _wc_run(ctx, mex, path)                      # warm (unfused)
        unfused_res, unfused = _wc_run(ctx, mex, path)
    for k in fused_res:
        assert np.array_equal(fused_res[k], unfused_res[k]), k
    assert fused == 1, fused
    assert unfused == 2, unfused
    assert unfused >= 2 * fused


def test_pagerank_pipeline_fusion_budget(monkeypatch):
    """Pinned dispatch budgets for the PageRank example pipeline
    across BOTH execution layers: fusion (program stitching) and loop
    replay (api/loop.py LoopPlan capture + whole-loop fori lowering).

    4-iter run, per-op model (FUSE=0, REPLAY=0): 20 dispatches.
    Stitching alone (REPLAY=0): 11 — upfront degree/edge/rank build 3
    + 2 fused programs (Zip+scale, join+reduce+dampen) x 4 iterations.
    Loop replay on top: 6 in the call that captures — upfront 3 +
    capture iteration 2 + ONE whole-loop fori_loop dispatch for
    iterations 2..4 — and 4 in every later call: the body takes its
    tables as ``invariants``, so the kept tape is rebound and all four
    iterations are the one fori dispatch."""
    sys.path.insert(0, _EXAMPLES)
    import page_rank as pr
    edges = pr.zipf_graph(512, 4096)
    want = pr.page_rank_dense(None, edges, 512, iterations=4)

    def run_mode(fuse, replay):
        monkeypatch.setenv("THRILL_TPU_FUSE", fuse)
        monkeypatch.setenv("THRILL_TPU_LOOP_REPLAY", replay)
        mex = MeshExec(num_workers=1)
        ctx = Context(mex)

        def run():
            d0 = mex.stats_dispatches
            got = pr.page_rank(ctx, edges, 512, iterations=4)
            return got, mex.stats_dispatches - d0

        _, first = run()                             # warm
        got, disp = run()
        assert np.allclose(got, want, rtol=1e-6)
        stats = ctx.overall_stats()
        ctx.close()
        return got, disp, dict(stats, first_call_dispatches=first)

    got_f, fused, stats = run_mode("1", "1")
    got_nr, fused_noreplay, _ = run_mode("1", "0")
    got_u, unfused, _ = run_mode("0", "0")
    assert stats["first_call_dispatches"] == 6, stats
    assert fused == 4, fused
    assert fused_noreplay == 11, fused_noreplay
    assert unfused == 20, unfused        # the per-op dispatch count
    assert unfused >= 3 * fused, (unfused, fused)
    # every layer computes bit-identical ranks
    assert np.array_equal(got_f, got_nr)
    assert np.array_equal(got_f, got_u)
    # the stitched run reports its stage compositions and the loop
    # layer reports plan-once semantics (2 runs = 1 capture + 1 rebind)
    assert stats["fused_dispatches"] > 0
    assert stats["fused_ops"] > stats["fused_dispatches"]
    assert any(" + " in k for k in stats["fused_stages"])
    assert stats["loop_plan_builds"] == 1
    assert stats["loop_plan_rebinds"] == 1
    assert stats["loop_fori_iters"] == 3 + 4     # 2..4, then 1..4


def _xk(t):
    return t["k"]


def test_exchange_overlap_budget():
    """Exchange-overlap lane: a steady-state repeated query at W=2
    (hash ReduceByKey — a real shuffle per run) pays the mid-shuffle
    send-matrix sync exactly ONCE. Runs 2..N dispatch phase B on the
    cached capacity plan: the capacity-cache hit rate is >= (N-1)/N
    and the per-run tracked-fetch budget drops to the egress fetches
    alone (zero mid-shuffle host syncs — the ISSUE 6 acceptance
    metric; an Iterate replay tape composes on top by skipping the
    planning step entirely, pinned in tests/api/test_loop.py)."""
    from thrill_tpu.api import FieldReduce
    mex = MeshExec(num_workers=2)
    ctx = Context(mex)
    rng = np.random.default_rng(11)
    vals = rng.integers(0, 64, 4096).astype(np.int64)
    red = FieldReduce({"k": "first", "c": "sum"})

    def run():
        out = ctx.Distribute(
            {"k": vals, "c": np.ones_like(vals)}).ReduceByKey(_xk, red)
        sh = out.node.materialize()
        jax.block_until_ready(jax.tree.leaves(sh.tree))

    run()                       # warm: compile + the one synced plan
    assert mex.stats_cap_cache_misses == 0   # first run syncs, no miss
    h0, f0, ov0 = (mex.stats_cap_cache_hits, mex.stats_fetches,
                   mex.stats_exchanges_overlapped)
    N = 4
    for _ in range(N):
        run()
    assert mex.stats_exchanges_overlapped - ov0 == N
    assert mex.stats_cap_cache_hits - h0 >= N
    assert mex.stats_cap_cache_misses == 0
    # zero tracked fetches for N whole runs: no mid-shuffle sync, and
    # the post-phase counts stay device-resident to the barrier
    assert mex.stats_fetches - f0 == 0, mex.stats_fetches - f0
    ctx.close()


def test_bytes_on_wire_pinned():
    """bytes_on_wire budgets, pinned like dispatch counts: the W=1
    PageRank pipeline ships NOTHING (the dense-gather join needs no
    exchange — that zero IS the claim), a W=2 WordCount-shaped reduce
    ships its padded phase-B blocks, and the stat matches the dense
    plan's fabric formula exactly."""
    sys.path.insert(0, _EXAMPLES)
    import page_rank as pr
    mex = MeshExec(num_workers=1)
    ctx = Context(mex)
    edges = pr.zipf_graph(256, 2048)
    pr.page_rank(ctx, edges, 256, iterations=3)
    assert ctx.overall_stats()["bytes_on_wire"] == 0
    ctx.close()

    from thrill_tpu.api import FieldReduce
    mex2 = MeshExec(num_workers=2)
    ctx2 = Context(mex2)
    vals = np.arange(2048, dtype=np.int64)
    red = FieldReduce({"k": "first", "c": "sum"})
    out = ctx2.Distribute(
        {"k": vals, "c": np.ones_like(vals)}).ReduceByKey(_xk, red)
    out.node.materialize()
    stats = ctx2.overall_stats()
    assert stats["bytes_on_wire"] > 0
    assert stats["bytes_on_wire"] == stats["bytes_wire_device"]
    # dense plan fabric volume: W*(W-1)*M_pad rows x item bytes per
    # exchange — the stat is the padded-wire truth, not payload bytes
    assert stats["bytes_wire_device"] % (2 * (2 - 1)) == 0
    ctx2.close()


def test_onefactor_narrowed_bytes_on_wire_lower(monkeypatch):
    """A 1-factor-planned exchange with learned narrow specs ships
    STRICTLY fewer bytes_on_wire than the same plan full-width, and the
    raw counter keeps the full-width equivalent (the compression
    denominator). Same pipeline, same plan, only the narrowing knob
    differs."""
    import jax.numpy as jnp
    from thrill_tpu.data import exchange as ex

    def run(narrow):
        monkeypatch.setenv("THRILL_TPU_XCHG_NARROW", narrow)
        # captured at mesh construction: set before MeshExec
        monkeypatch.setenv("THRILL_TPU_EXCHANGE", "onefactor")
        mex = MeshExec(num_workers=4)
        ctx = Context(mex)
        vals = (np.arange(6000, dtype=np.int64) * 11) % 1000
        outs = []
        for _ in range(2):
            shards = ctx.Distribute({"k": vals}).node.materialize()

            def dest(tree, mask, widx):
                return (tree["k"] % 4).astype(jnp.int32)

            out = ex.exchange(shards, dest, ("of_narrow_budget",))
            outs.append([np.sort(np.asarray(t["k"]))
                         for t in out.to_worker_arrays()])
        stats = ctx.overall_stats()
        ctx.close()
        return outs, stats

    outs_on, on = run("1")
    outs_off, off = run("0")
    for a, b in zip(outs_on, outs_off):
        for ta, tb in zip(a, b):
            assert np.array_equal(ta, tb)
    assert on["bytes_on_wire"] < off["bytes_on_wire"]
    assert on["bytes_wire_device_raw"] == off["bytes_on_wire"]


def test_put_small_content_cache():
    mex = MeshExec(num_workers=2)
    u0 = mex.stats_uploads
    b1 = mex.put_small(np.array([[3], [4]], np.int32))
    b2 = mex.put_small(np.array([[3], [4]], np.int32))
    assert b1 is b2
    assert mex.stats_uploads == u0 + 1
    b3 = mex.put_small(np.array([[3], [5]], np.int32))
    assert b3 is not b1


def test_allgather_arrays_device_and_host():
    mex = MeshExec(num_workers=4)
    ctx = Context(mex)
    d = ctx.Distribute(np.arange(37, dtype=np.int64)).Keep()
    cols = d.AllGatherArrays()
    assert isinstance(cols, jax.Array)
    assert np.array_equal(np.sort(np.asarray(cols)), np.arange(37))
    # host-storage path returns numpy-stacked leaves
    h = ctx.Distribute(list(range(10)), storage="host")
    cols_h = h.AllGatherArrays()
    assert sorted(np.asarray(cols_h).tolist()) == list(range(10))


def test_distribute_device_arrays_uneven_split():
    """Device-array Distribute splits on device for ANY n/W (no fetch,
    no upload), preserving order and counts."""
    mex = MeshExec(num_workers=3)
    ctx = Context(mex)
    src = jax.numpy.arange(37, dtype=jax.numpy.int64) * 3
    s0 = _snap(mex)
    d = ctx.Distribute(src)
    sh = d._link().pull(True)
    assert sh.counts.tolist() == [12, 12, 13]
    disp, up, fetch = (_snap(mex) - s0).tolist()
    assert (up, fetch) == (0, 0), (up, fetch)
    got = np.concatenate([np.asarray(jax.tree.leaves(sh.tree)[0][w, :c])
                          for w, c in enumerate(sh.counts)])
    assert np.array_equal(got, np.arange(37) * 3)


def test_allgather_arrays_empty():
    mex = MeshExec(num_workers=2)
    ctx = Context(mex)
    d = ctx.Distribute(np.arange(8, dtype=np.int64)).Filter(
        lambda x: x < 0)
    cols = d.AllGatherArrays()
    assert np.asarray(cols).shape[0] == 0


def _idkey(x):
    return x


def _takeleft(a, b):
    return a


def test_join_out_size_hint_correct_and_overflow(monkeypatch):
    mex = MeshExec(num_workers=1)
    ctx = Context(mex)
    l = ctx.Distribute(np.arange(16, dtype=np.int64))
    r = ctx.Distribute(np.arange(8, 16, dtype=np.int64))
    j = InnerJoin(l, r, _idkey, _idkey, _takeleft, out_size_hint=8)
    assert sorted(j.AllGather()) == list(range(8, 16))
    assert mex.stats_join_overflow_retries == 0

    # an overflowing hint RECOVERS by default: the join re-runs its
    # expansion un-hinted (lineage retry) and the results are exact
    l2 = ctx.Distribute([1, 1, 1, 1])
    r2 = ctx.Distribute([1, 1, 1, 1])
    j2 = InnerJoin(l2, r2, _idkey, _idkey, _takeleft, out_size_hint=4)
    assert j2.AllGather() == [1] * 16
    assert mex.stats_join_overflow_retries == 1

    # with recovery disabled the overflow raises (never truncates)
    monkeypatch.setenv("THRILL_TPU_JOIN_RECOVER", "0")
    l3 = ctx.Distribute([1, 1, 1, 1])
    r3 = ctx.Distribute([1, 1, 1, 1])
    j3 = InnerJoin(l3, r3, _idkey, _idkey, _takeleft, out_size_hint=4)
    with pytest.raises(ValueError, match="out_size_hint"):
        j3.AllGather()


def test_join_overflow_is_sticky_and_drain_preserves_tail(monkeypatch):
    """With recovery disabled, a swallowed overflow error must not
    unlock truncated reads (sticky re-raise), and one raising check
    must not discard other joins' queued checks."""
    monkeypatch.setenv("THRILL_TPU_JOIN_RECOVER", "0")
    mex = MeshExec(num_workers=1)
    ctx = Context(mex)
    l = ctx.Distribute([1, 1, 1, 1]).Keep(3)
    r = ctx.Distribute([1, 1, 1, 1]).Keep(3)
    j = InnerJoin(l, r, _idkey, _idkey, _takeleft, out_size_hint=4)
    jn = j.node.materialize(consume=False)     # builds the hint path
    # second overflowing join queues its own check behind the first
    j2 = InnerJoin(l, r, _idkey, _idkey, _takeleft, out_size_hint=4)
    j2n = j2.node.materialize(consume=False)
    with pytest.raises(ValueError, match="out_size_hint"):
        mex.fetch(np.zeros(1))                 # drain: first check fires
    # swallowed once — but the tail survived: the next fetch raises
    # for the SECOND join
    with pytest.raises(ValueError, match="out_size_hint"):
        mex.fetch(np.zeros(1))
    # and the first join's counts stay poisoned (sticky), not silent
    with pytest.raises(ValueError, match="out_size_hint"):
        _ = jn.counts
    with pytest.raises(ValueError, match="out_size_hint"):
        _ = jn.counts                          # still raising, not cached


def test_join_overflow_recovery_survives_hbm_spill():
    """HBM pressure must not leak truncated columns to disk: spilling
    a hint-carrying result validates (and recovers) BEFORE
    serializing, so the restored shards are the healed ones."""
    from thrill_tpu.common.config import Config
    mex = MeshExec(num_workers=1)
    ctx = Context(mex, Config(hbm_limit=1))        # always exceeded
    l = ctx.Distribute([1, 1, 1, 1])
    r = ctx.Distribute([1, 1, 1, 1])
    j = InnerJoin(l, r, _idkey, _idkey, _takeleft, out_size_hint=4)
    j.node.materialize(consume=False)    # cached, check still pending
    # caching another node pressures the join result out to the store
    other = ctx.Distribute(np.arange(32, dtype=np.int64))
    other.node.materialize(consume=False)
    assert ctx.hbm.spill_count >= 1
    assert mex.stats_join_overflow_retries == 1    # healed pre-spill
    assert j.AllGather() == [1] * 16               # restored + exact
    ctx.close()


def test_two_overflowed_joins_under_pressure_recover_exactly_once():
    """Re-entrancy: two unresolved hinted joins under HBM pressure
    spill each other during recovery (validate -> maybe_spill ->
    spill(other) -> validate ...). Each join must recover EXACTLY once
    (mutual recursion used to re-run recovery hundreds of times) and
    both must still read back exact."""
    from thrill_tpu.common.config import Config
    mex = MeshExec(num_workers=1)
    ctx = Context(mex, Config(hbm_limit=1))        # always exceeded
    l = ctx.Distribute([1, 1, 1, 1]).Keep(1)
    r = ctx.Distribute([1, 1, 1, 1]).Keep(1)
    j1 = InnerJoin(l, r, _idkey, _idkey, _takeleft, out_size_hint=4)
    j1.node.materialize(consume=False)
    j2 = InnerJoin(l, r, _idkey, _idkey, _takeleft, out_size_hint=4)
    j2.node.materialize(consume=False)
    # a third cached node turns the pressure into spills of the joins
    other = ctx.Distribute(np.arange(32, dtype=np.int64))
    other.node.materialize(consume=False)
    assert mex.stats_join_overflow_retries == 2    # once per join
    assert j1.AllGather() == [1] * 16
    assert j2.AllGather() == [1] * 16
    ctx.close()


def test_join_overflow_recovery_heals_downstream_pipeline():
    """The dispatch-budget contract of the recovery: a page_rank-style
    chain (hinted join -> device map -> reduce -> egress) with a WRONG
    hint produces exact results with exactly one lineage retry, no
    counted mid-pipeline fetch, and one extra dispatch (the re-run
    expansion); a RIGHT hint stays zero-retry."""
    mex = MeshExec(num_workers=1)
    ctx = Context(mex)
    keys = [1, 2, 1, 2, 1]
    l = ctx.Distribute(np.asarray(keys, dtype=np.int64))
    r = ctx.Distribute(np.asarray([1, 2], dtype=np.int64))
    j = InnerJoin(l, r, _idkey, _idkey, lambda a, b: a + b,
                  out_size_hint=2)             # true per-worker max: 5
    s0 = _snap(mex)
    got = sorted(int(x) for x in
                 j.Map(lambda x: x * 10).AllGather())
    assert got == sorted((k + k) * 10 for k in keys)
    assert mex.stats_join_overflow_retries == 1
    disp, up, fetch = (_snap(mex) - s0).tolist()
    assert fetch <= 1, fetch                   # egress only; no sync
    ctx.close()


# ----------------------------------------------------------------------
# shrink-the-wire budgets (ISSUE 7): >=2x bytes_on_wire vs the PR 6
# baseline, pinned like dispatch counts
# ----------------------------------------------------------------------

def _jk(t):
    return t["k"]


def _join_sum(a, b):
    return {"k": a["k"], "s": a["v"] + b["v"]}


def test_wire_shrink_innerjoin_budget(monkeypatch):
    """W=2 InnerJoin pipeline: row narrowing (i64 keys/payloads in
    narrow ranges) shrinks bytes_on_wire >= 2x vs the PR 6 baseline
    (THRILL_TPU_WIRE_COMPRESS=0), results bit-identical with
    compression and pruning individually disabled; the location filter
    composes (pruned rows shrink the wire further, never change the
    result)."""
    n = 4096

    def run(compress, prune):
        monkeypatch.setenv("THRILL_TPU_WIRE_COMPRESS", compress)
        monkeypatch.setenv("THRILL_TPU_LOCATION_DETECT", prune)
        mex = MeshExec(num_workers=2)
        ctx = Context(mex)
        lk = np.arange(n, dtype=np.int64)
        l = ctx.Distribute({"k": lk, "v": (lk * 3) % 1000})
        rk = np.arange(0, n, 4, dtype=np.int64)     # quarter keyspace
        r = ctx.Distribute({"k": rk, "v": rk % 97})
        j = InnerJoin(l, r, _jk, _jk, _join_sum)
        cols = jax.tree.map(np.asarray, j.AllGatherArrays())
        order = np.lexsort((cols["s"], cols["k"]))
        out = {kk: np.asarray(vv)[order] for kk, vv in cols.items()}
        wire = ctx.overall_stats()["bytes_on_wire"]
        ctx.close()
        return out, wire

    base, wire_base = run("0", "0")    # the PR 6 baseline plane
    comp, wire_comp = run("1", "0")    # compression alone
    full, wire_full = run("1", "1")    # compression + pruning
    for k in base:
        assert np.array_equal(base[k], comp[k]), k
        assert np.array_equal(base[k], full[k]), k
    assert wire_base > 0
    assert wire_base >= 2 * wire_comp, (wire_base, wire_comp)
    assert wire_full <= wire_comp, (wire_full, wire_comp)


def _pr_idx(t):
    return t["i"]


def test_wire_shrink_pagerank_budget(monkeypatch):
    """W=2 multi-iteration PageRank-shaped traffic (per iteration an
    index-partitioned scatter of (page index, f32 contribution) — the
    ReduceToIndex exchange PageRank pays at W>1): narrowing the index
    column shrinks bytes_on_wire >= 2x vs the PR 6 baseline, ranks
    bit-identical."""
    from thrill_tpu.api import FieldReduce
    npages, nedges, iters = 200, 4096, 3
    rng = np.random.default_rng(3)
    src = rng.integers(0, npages, nedges).astype(np.int64)
    dst = rng.integers(0, npages, nedges).astype(np.int64)
    deg = np.maximum(np.bincount(src, minlength=npages), 1)

    def run(compress):
        monkeypatch.setenv("THRILL_TPU_WIRE_COMPRESS", compress)
        mex = MeshExec(num_workers=2)
        ctx = Context(mex)
        red = FieldReduce({"i": "first", "r": "sum"})
        ranks = np.full(npages, 1.0 / npages, np.float32)
        for _ in range(iters):
            contrib = (ranks[src] / deg[src]).astype(np.float32)
            d = ctx.Distribute({"i": dst, "r": contrib})
            out = d.ReduceToIndex(_pr_idx, red, size=npages,
                                  neutral={"i": 0, "r": np.float32(0)})
            cols = jax.tree.map(np.asarray, out.AllGatherArrays())
            ranks = (0.15 / npages
                     + 0.85 * np.asarray(cols["r"])).astype(np.float32)
        wire = ctx.overall_stats()["bytes_on_wire"]
        ctx.close()
        return ranks, wire

    ranks_base, wire_base = run("0")
    ranks_comp, wire_comp = run("1")
    assert np.array_equal(ranks_base, ranks_comp)
    assert wire_base > 0
    assert wire_base >= 2 * wire_comp, (wire_base, wire_comp)
