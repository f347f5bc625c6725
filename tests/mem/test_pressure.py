"""Memory-pressure escalation ladder (mem/pressure.py).

The invariant under test at every rung: memory pressure makes the job
SLOWER, never WRONG and never dead. Rung 1 (admission) spills cold
cached shards before a dispatch that would cross the watermark; rung 2
(OOM-retry) catches device RESOURCE_EXHAUSTED, spills, and re-runs
with donation disarmed; rung 3 re-plans a row-local fused chain as
row-range sub-dispatches; rung 4 runs the chain's host-engine form.
Every rung is exercised with the ``mem.oom`` injection (CPU-testable)
and asserted bit-identical against the unpressured run.
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from thrill_tpu.api import Context
from thrill_tpu.common import faults
from thrill_tpu.common.config import Config
from thrill_tpu.mem import pressure
from thrill_tpu.parallel.mesh import MeshExec


@pytest.fixture(autouse=True)
def _clean_registry(monkeypatch):
    monkeypatch.delenv(faults.ENV_VAR, raising=False)
    monkeypatch.delenv("THRILL_TPU_HBM_LIMIT", raising=False)
    faults.REGISTRY.reset()
    yield
    faults.REGISTRY.reset()


def _map_filter_pipeline(ctx, n=96):
    d = ctx.Distribute(np.arange(n, dtype=np.int64))
    return sorted(int(x) for x in
                  d.Map(lambda x: x * 3 + 1).Filter(
                      lambda x: x % 2 == 0).AllGather())


def _want_map_filter(n=96):
    return sorted(x * 3 + 1 for x in range(n) if (x * 3 + 1) % 2 == 0)


# ----------------------------------------------------------------------
# rung 1: admission control
# ----------------------------------------------------------------------

def test_admission_spills_cold_shards_before_dispatch(monkeypatch):
    """With a budget below (cached bytes + next dispatch's estimate),
    the cold cached node spills BEFORE the dispatch (event=mem_spill),
    restores transparently on its next pull, and everything is exact."""
    monkeypatch.setenv("THRILL_TPU_HBM_LIMIT", "64Ki")
    mex = MeshExec(num_workers=2)
    ctx = Context(mex)
    assert ctx.pressure.enabled and ctx.pressure.budget == 64 * 1024
    a = ctx.Distribute(np.arange(4096, dtype=np.int64))   # 32 KiB
    a.Keep(2)
    assert a.Size() == 4096
    got = sorted(int(x) for x in ctx.Distribute(
        np.arange(8192, dtype=np.int64)).Map(lambda x: x + 1)
        .AllGather())
    stats = ctx.overall_stats()
    assert got == [x + 1 for x in range(8192)]
    assert stats["hbm_spills"] >= 1
    assert stats["pressure_spilled_bytes"] > 0
    assert stats["hbm_high_watermark"] > 64 * 1024
    assert any(e.get("event") == "mem_spill"
               for e in faults.REGISTRY.events)
    # the spilled node restores transparently and exactly
    assert [int(x) for x in a.AllGather()] == list(range(4096))
    assert stats["oom_retries"] == 0      # admission alone was enough
    ctx.close()


def test_restore_overlap_under_pressure(monkeypatch, tmp_path):
    """ISSUE 13 acceptance: the pressure-restore path runs the
    double-buffered readahead — a pressured W=2 run whose spill store
    is genuinely disk-resident (THRILL_TPU_SPILL_RESIDENT) emits
    event=restore_overlap on the restore, counts it in overall_stats,
    and the restored data is exact. THRILL_TPU_PREFETCH=0 takes the
    sequential path bit-identically."""
    import json
    monkeypatch.setenv("THRILL_TPU_HBM_LIMIT", "512Ki")
    monkeypatch.setenv("THRILL_TPU_SPILL_RESIDENT", "64K")
    log = tmp_path / "run.jsonl"
    mex = MeshExec(num_workers=2)
    ctx = Context(mex, Config(log_path=str(log)))
    assert ctx.pressure.enabled
    a = ctx.Distribute(np.arange(1 << 16, dtype=np.int64))  # 512 KiB
    a.Keep(2)
    assert a.Size() == 1 << 16
    got = sorted(int(x) for x in ctx.Distribute(
        np.arange(1 << 16, dtype=np.int64)).Map(lambda x: x + 1)
        .AllGather())
    assert got == [x + 1 for x in range(1 << 16)]
    # the spilled node restores with the next block's read in flight
    assert [int(x) for x in a.AllGather()] == list(range(1 << 16))
    stats = ctx.overall_stats()
    assert stats["hbm_spills"] >= 1 and stats["hbm_restores"] >= 1
    assert stats["restore_overlaps"] >= 1
    ctx.close()
    # log naming is per-host (common/logger.default_log_path)
    evs = [json.loads(l)
           for l in open(tmp_path / "run-host0.jsonl") if l.strip()]
    assert any(e.get("event") == "restore_overlap"
               and e.get("kind") == "hbm" for e in evs), \
        [e.get("event") for e in evs][-20:]

    # parity: the sequential path restores the same values
    monkeypatch.setenv("THRILL_TPU_PREFETCH", "0")
    try:
        ctx2 = Context(MeshExec(num_workers=2))
        b = ctx2.Distribute(np.arange(1 << 16, dtype=np.int64))
        b.Keep(2)
        b.Size()
        ctx2.Distribute(np.arange(1 << 16, dtype=np.int64)) \
            .Map(lambda x: x + 1).AllGather()
        assert [int(x) for x in b.AllGather()] == list(range(1 << 16))
        assert ctx2.overall_stats()["restore_overlaps"] == 0
        ctx2.close()
    finally:
        monkeypatch.delenv("THRILL_TPU_PREFETCH")


def test_no_budget_means_zero_admission_overhead():
    """No THRILL_TPU_HBM_LIMIT and no device memory stats (CPU):
    pressure stays disabled, no watermark tracking, no spills."""
    mex = MeshExec(num_workers=2)
    ctx = Context(mex)
    assert not ctx.pressure.enabled
    assert _map_filter_pipeline(ctx) == _want_map_filter()
    stats = ctx.overall_stats()
    assert stats["hbm_high_watermark"] == 0
    assert stats["pressure_spilled_bytes"] == 0
    assert stats["oom_retries"] == 0 and stats["segment_splits"] == 0
    ctx.close()


# ----------------------------------------------------------------------
# rungs 2-4: the OOM ladder
# ----------------------------------------------------------------------

def test_oom_retry_recovers_bit_identical():
    """Rung 2: one injected RESOURCE_EXHAUSTED at the dispatch choke
    point -> spill + re-dispatch; results exact, event visible."""
    with faults.inject("mem.oom", n=1, seed=7):
        mex = MeshExec(num_workers=2)
        ctx = Context(mex)
        got = _map_filter_pipeline(ctx)
        stats = ctx.overall_stats()
        ctx.close()
    assert got == _want_map_filter()
    assert stats["oom_retries"] >= 1
    assert faults.REGISTRY.injected >= 1
    assert any(e.get("event") == "oom_retry"
               for e in faults.REGISTRY.events)


def test_oom_split_rung_replans_row_ranges(monkeypatch):
    """Rung 3: with the retry budget exhausted (attempts=1), a
    row-local fused chain re-plans as K row-range sub-dispatches
    (event=segment_split) and the result matches the unpressured run
    bit-identically."""
    mex0 = MeshExec(num_workers=2)
    ctx0 = Context(mex0)
    want = _map_filter_pipeline(ctx0)
    ctx0.close()

    monkeypatch.setenv("THRILL_TPU_RETRY_ATTEMPTS", "1")
    faults.REGISTRY.reset()
    with faults.inject("mem.oom", n=1, seed=7):
        mex = MeshExec(num_workers=2)
        ctx = Context(mex)
        got = _map_filter_pipeline(ctx)
        stats = ctx.overall_stats()
        ctx.close()
    assert got == want == _want_map_filter()
    assert stats["segment_splits"] >= 1
    assert any(e.get("event") == "segment_split"
               for e in faults.REGISTRY.events)


def test_oom_host_fallback_last_rung(monkeypatch):
    """Rung 4: an unbounded OOM (every device dispatch dies) still
    completes through the host engine — slower, unbounded by HBM,
    bit-identical."""
    monkeypatch.setenv("THRILL_TPU_RETRY_ATTEMPTS", "1")
    with faults.inject("mem.oom", n=0, seed=7):
        mex = MeshExec(num_workers=2)
        ctx = Context(mex)
        got = _map_filter_pipeline(ctx)
        ctx.close()
    assert got == _want_map_filter()
    assert any(e.get("what") == "mem.host_fallback"
               for e in faults.REGISTRY.events)


def test_oom_ladder_disabled_surfaces_cleanly(monkeypatch):
    """THRILL_TPU_OOM_RETRY=0: the ladder falls away and the OOM
    surfaces as a clean error on the first dispatch — never a hang."""
    monkeypatch.setenv("THRILL_TPU_OOM_RETRY", "0")
    with faults.inject("mem.oom", n=0, seed=7):
        mex = MeshExec(num_workers=2)
        ctx = Context(mex)
        with pytest.raises(pressure.SimulatedOom):
            _map_filter_pipeline(ctx)
        ctx.close()


# ----------------------------------------------------------------------
# parity: pressured runs match unpressured runs bit-identically
# ----------------------------------------------------------------------

def _wordcount(ctx, n=200):
    from thrill_tpu.api import FieldReduce
    rng = np.random.default_rng(3)
    data = rng.integers(0, 17, size=n)
    got = ctx.Distribute(np.asarray(data, dtype=np.int64)) \
        .Map(lambda x: {"k": x, "v": 1}) \
        .ReduceByKey(lambda t: t["k"],
                     FieldReduce({"k": "first", "v": "sum"})).AllGather()
    return sorted((int(t["k"]), int(t["v"])) for t in got)


def _sort_records(ctx, n=512):
    rng = np.random.default_rng(5)
    recs = {"key": rng.integers(0, 100, size=n).astype(np.int64),
            "val": rng.integers(0, 1 << 30, size=n).astype(np.int64)}
    out = ctx.Distribute(recs).Sort(key_fn=lambda r: r["key"]).AllGather()
    return [(int(r["key"]), int(r["val"])) for r in out]


@pytest.mark.parametrize("workload", ["wordcount", "sort"])
def test_pressured_parity_vs_unpressured(workload, monkeypatch):
    """THRILL_TPU_HBM_LIMIT far below the working set + injected OOMs:
    WordCount and Sort complete bit-identical to the unpressured run
    (the acceptance invariant of the escalation ladder)."""
    fn = {"wordcount": _wordcount, "sort": _sort_records}[workload]
    mex0 = MeshExec(num_workers=2)
    ctx0 = Context(mex0)
    want = fn(ctx0)
    ctx0.close()

    monkeypatch.setenv("THRILL_TPU_HBM_LIMIT", "4Ki")
    faults.REGISTRY.reset()
    with faults.inject("mem.oom", n=2, seed=11):
        mex = MeshExec(num_workers=2)
        ctx = Context(mex)
        got = fn(ctx)
        stats = ctx.overall_stats()
        ctx.close()
    assert got == want
    assert stats["oom_retries"] >= 1      # the ladder really engaged


def test_pagerank_parity_under_pressure(monkeypatch):
    """PageRank (Iterate + replay) under a tiny budget and an injected
    OOM stays bit-identical to the unpressured run."""
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(__file__),
                                    "..", "..", "examples"))
    import page_rank as pr
    rng = np.random.default_rng(0)
    edges = np.unique(rng.integers(0, 48, size=(300, 2)), axis=0)

    mex0 = MeshExec(num_workers=2)
    ctx0 = Context(mex0)
    want = pr.page_rank(ctx0, edges, 48, iterations=3)
    ctx0.close()

    monkeypatch.setenv("THRILL_TPU_HBM_LIMIT", "8Ki")
    faults.REGISTRY.reset()
    with faults.inject("mem.oom", n=1, seed=3):
        mex = MeshExec(num_workers=2)
        ctx = Context(mex)
        got = pr.page_rank(ctx, edges, 48, iterations=3)
        ctx.close()
    assert np.array_equal(got, want)


# ----------------------------------------------------------------------
# donation disarm
# ----------------------------------------------------------------------

def test_donating_twin_retries_through_base():
    """A donating twin whose dispatch OOMs re-dispatches through its
    NON-donating base (the retry must not re-donate buffers the failed
    dispatch may have consumed) — results exact."""
    mex = MeshExec(num_workers=1)
    ctx = Context(mex)
    fn = mex.jit_cached(("press_donate_retry",), lambda x: x * 2.0)
    twin = fn.donating((0,))
    assert twin._donate_base is fn
    x = jnp.arange(8, dtype=jnp.float64)
    with faults.inject("mem.oom", n=1, seed=5):
        out = twin(jnp.copy(x))
    assert np.allclose(np.asarray(out), np.arange(8) * 2.0)
    assert mex.pressure.oom_retries >= 1
    ctx.close()


def test_consumed_donated_buffer_surfaces_clean_error():
    """When the failed donating dispatch already consumed an input
    buffer, the ladder surfaces a clear donated-buffer error instead
    of retrying into a deleted-array crash."""
    mex = MeshExec(num_workers=1)
    ctx = Context(mex)
    fn = mex.jit_cached(("press_donate_dead",), lambda x: x + 1.0)
    twin = fn.donating((0,))
    x = jnp.copy(jnp.arange(4, dtype=jnp.float64))
    x.delete()
    with pytest.raises(RuntimeError, match="donated"):
        pressure.recover_dispatch(
            twin, (x,), {}, pressure.SimulatedOom("mem.oom"))
    ctx.close()


# ----------------------------------------------------------------------
# Iterate compose: OOM mid-replay degrades to re-planning
# ----------------------------------------------------------------------

def test_iterate_oom_mid_replay_replans_not_corrupts(monkeypatch):
    """An OOM surviving the (disabled) retry budget on a REPLAYED
    dispatch must degrade to full re-planning — a second capture, a
    slower loop, bit-identical results. Never a lying tape."""
    from thrill_tpu.api.loop import Iterate
    monkeypatch.setenv("THRILL_TPU_RETRY", "0")      # ladder: 1 attempt
    # per-iteration replay: the whole-loop fori program is one plain
    # jax.jit dispatch outside the choke point (an OOM there reaches
    # the same Iterate fallback through the plain exception path)
    monkeypatch.setenv("THRILL_TPU_LOOP_FORI", "0")
    monkeypatch.setenv(faults.ENV_VAR, "mem.oom:n=1:after=1")
    mex = MeshExec(num_workers=1)
    ctx = Context(mex)
    step = mex.jit_cached(("press_loop_step",), lambda x: x * 2.0 + 1.0)
    out = Iterate(ctx, lambda x: step(x),
                  jnp.arange(8, dtype=jnp.float64), 4,
                  name="press_loop")
    got = np.asarray(out)
    stats = ctx.overall_stats()
    ctx.close()
    want = np.arange(8, dtype=np.float64)
    for _ in range(4):
        want = want * 2.0 + 1.0
    assert np.allclose(got, want)
    assert stats["loop_replay_fallbacks"] >= 1
    assert stats["loop_plan_builds"] >= 2            # re-captured


# ----------------------------------------------------------------------
# cost model
# ----------------------------------------------------------------------

def test_estimate_learns_program_output_bytes(monkeypatch):
    """First dispatch of a program estimates via the factor guess;
    afterwards the learned output size replaces it."""
    monkeypatch.setenv("THRILL_TPU_HBM_LIMIT", "1Gi")
    mex = MeshExec(num_workers=1)
    ctx = Context(mex)
    fn = mex.jit_cached(("press_learn",), lambda x: x[:4])
    x = jnp.arange(64, dtype=jnp.float64)
    assert fn._out_bytes is None
    cold = ctx.pressure.estimate_call_bytes(fn, (x,))
    assert cold == int(x.nbytes * ctx.pressure.est_factor)
    fn(x)
    assert fn._out_bytes == 4 * 8
    warm = ctx.pressure.estimate_call_bytes(fn, (x,))
    assert warm == x.nbytes + 4 * 8
    # an explicit plan hint wins over both, and is consumed once
    ctx.pressure.hint_output_bytes(128)
    assert ctx.pressure.estimate_call_bytes(fn, (x,)) == x.nbytes + 128
    assert ctx.pressure.estimate_call_bytes(fn, (x,)) == warm
    ctx.close()


def test_is_oom_error_classification():
    assert pressure.is_oom_error(pressure.SimulatedOom("mem.oom"))
    assert pressure.is_oom_error(
        RuntimeError("RESOURCE_EXHAUSTED: Out of memory allocating "
                     "12345 bytes"))
    assert pressure.is_oom_error(MemoryError())
    assert not pressure.is_oom_error(RuntimeError("shape mismatch"))
    assert not pressure.is_oom_error(faults.InjectedIOError("x"))
    assert not pressure.is_oom_error(KeyError("RESOURCE_EXHAUSTED"))


def test_compile_time_vmem_refusal_is_not_an_oom():
    """The v5e compiler's refusal of a kernel that does not fit VMEM
    says RESOURCE_EXHAUSTED too (text as printed for presence_fill at
    8192 registers, PR 22). Spilling HBM cannot make it compile, so the
    ladder must let it surface as itself."""
    refusal = RuntimeError(
        "RESOURCE_EXHAUSTED: Ran out of memory in memory space vmem "
        "while allocating on stack for %_lambda_.1 = "
        "s32[8192,1]{1,0:T(8,128)S(1)} custom-call(%reshape.2, "
        "%reshape.59), custom_call_target=\"tpu_custom_call\". Scoped "
        "allocation with size 28.12M and limit 16.00M exceeded scoped "
        "vmem limit by 12.12M.")
    assert not pressure.is_oom_error(refusal)
    assert pressure.is_oom_error(RuntimeError(
        "RESOURCE_EXHAUSTED: Ran out of memory in memory space hbm. "
        "Used 17.2G of 15.75G hbm."))


def test_admission_never_spills_the_dispatchs_own_sources(monkeypatch):
    """Spilling a node whose buffers feed the IN-FLIGHT dispatch frees
    no HBM (args keep the arrays alive) and buys a restore round trip
    — spill_cold must skip nodes named in exclude_buffers."""
    monkeypatch.setenv("THRILL_TPU_HBM_LIMIT", "1Ki")   # always over
    mex = MeshExec(num_workers=2)
    ctx = Context(mex)
    a = ctx.Distribute(np.arange(2048, dtype=np.int64))
    a.Keep(2)
    assert a.Size() == 2048                  # a is cached + in the LRU
    node = a.node.node if hasattr(a.node, "node") else a.node
    leaves = __import__("jax").tree.leaves(node._shards.tree)
    live = {id(l) for l in leaves}
    assert ctx.pressure.spill_cold(exclude_buffers=live) == 0
    from thrill_tpu.data.shards import DeviceShards
    assert isinstance(node._shards, DeviceShards)        # not spilled
    assert ctx.pressure.spill_cold() > 0                 # without it: spills
    ctx.close()
