"""Performance doctor (common/doctor.py) + perf-contract sentinel.

Acceptance pins (ISSUE 14):
* a delay-injected rank (``net.group.delay.r1:delay=...`` — the
  latency fault mode) is named the straggler by the wait attribution,
  with a nonzero ``collective_wait_s``;
* a deliberately hot-keyed ReduceByKey reports ``skew_ratio >= 3`` on
  the correct exchange site, with the hot-slot verdict in the ledger
  and the ``kind=skew`` instant on the trace's plan lane;
* the critical-path pass over the span ring names the exchange span;
* ``THRILL_TPU_DOCTOR=0`` is a pinned zero-allocation no-op at the
  collective choke points (module RECORDS counter stays flat);
* perf-sentinel round-trip: a snapshot diffs clean against an
  identical fresh run, and a ``THRILL_TPU_FUSE=0`` run fails on the
  dispatch-count contract.
"""

import json
import os
import threading

import numpy as np
import pytest

from thrill_tpu.api import RunLocalMock
from thrill_tpu.common import doctor as doctor_mod
from thrill_tpu.common import faults
from thrill_tpu.common.doctor import Doctor, critical_path
from thrill_tpu.net.mock import MockNetwork


@pytest.fixture(autouse=True)
def _clean_faults(monkeypatch):
    monkeypatch.delenv(faults.ENV_VAR, raising=False)
    faults.REGISTRY.reset()
    yield
    faults.REGISTRY.reset()


def _run_ranks(groups, fn, timeout=30.0):
    errs = []

    def run(g):
        try:
            fn(g)
        except BaseException as e:  # surfaced below
            errs.append(e)

    ts = [threading.Thread(target=run, args=(g,), daemon=True)
          for g in groups]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout)
        assert not t.is_alive(), "rank thread wedged"
    assert not errs, errs


# ----------------------------------------------------------------------
# collective wait attribution
# ----------------------------------------------------------------------

def test_straggler_attribution_pins_delayed_rank(monkeypatch):
    """W=2 host group, rank 1 armed with the latency fault at every
    collective entry: rank 0's per-peer waits must blame rank 1."""
    groups = MockNetwork.construct(2)
    docs = [Doctor(rank=r) for r in range(2)]
    for g, d in zip(groups, docs):
        g.doctor = d
    monkeypatch.setenv(faults.ENV_VAR,
                       "net.group.delay.r1:delay=40ms:n=0")

    def fn(g):
        for _ in range(4):
            g.barrier()

    _run_ranks(groups, fn)
    assert faults.REGISTRY.stats()["faults_delayed"] >= 4
    d0 = docs[0]
    # nonzero attribution, pinned on the right rank
    assert d0.collective_wait_s > 0.05
    assert d0.straggler_rank() == 1
    assert d0.straggler_scores()[1] > 0.05
    # the delayed rank itself barely waited on the prompt one
    assert docs[1].wait_by_peer.get(0, 0.0) < d0.wait_by_peer[1]
    st = d0.stats()
    assert st["wait_net_s"] > 0.05
    assert st["collective_wait_s"] >= st["wait_io_s"]
    assert st["straggler_waits"]["1"] > 0.05
    rep = d0.report()
    assert rep["straggler_rank"] == 1
    assert "barrier" in " ".join(rep["wait_by_site"]) \
        or "all_reduce" in " ".join(rep["wait_by_site"])


def test_delay_fault_applies_to_exactly_one_rank(monkeypatch):
    """The per-rank site naming: arming r1 must not slow r0."""
    groups = MockNetwork.construct(2)
    monkeypatch.setenv(faults.ENV_VAR,
                       "net.group.delay.r1:delay=20ms:n=2")
    _run_ranks(groups, lambda g: g.barrier())
    sites = faults.REGISTRY.sites
    assert sites["net.group.delay.r1"].hits >= 1
    # r0's dynamic site either never materialized or never slept
    assert faults.REGISTRY.stats()["faults_delayed"] >= 1


# ----------------------------------------------------------------------
# partition-skew attribution
# ----------------------------------------------------------------------

def _hot_kv(x):
    # ONE hot key: the device reduce pre-aggregates locally, so
    # duplicate-count skew collapses to one row per worker — but a
    # single key routes EVERY pre-reduced row to one worker, a
    # deterministic 4x hot slot on the W=4 mesh (recv rows [4,0,0,0])
    return (x * 0 + 7, x)


def _add(a, b):
    return a + b


def test_hot_key_reducebykey_pins_skew_ratio():
    box = {}

    def job(ctx):
        out = ctx.Distribute(np.arange(200, dtype=np.int64)) \
            .Map(_hot_kv).ReducePair(_add).AllGather()
        assert [(int(k), int(v)) for k, v in out] \
            == [(7, sum(range(200)))]
        box["stats"] = ctx.overall_stats()
        box["hot"] = ctx.doctor.hot_sites()
        box["skew_decisions"] = ctx.decisions.kind_counts.get("skew", 0)
        box["ring"] = list(ctx.tracer.ring or ())
        box["explain"] = ctx.explain()

    RunLocalMock(job, 4)
    st = box["stats"]
    assert st["skew_ratio"] >= 3.0, st["skew_ratio"]
    hot = box["hot"]
    assert hot and hot[0]["hot"] and hot[0]["ratio"] >= 3.0
    assert hot[0]["site"].startswith("xchg:")
    # every exchange of this one-shuffle pipeline is the reduce's: the
    # hot verdict is on the correct (only) exchange site
    assert len({h["site"] for h in hot}) == 1
    # the verdict reached the decision ledger (ctx.explain's source)
    assert box["skew_decisions"] >= 1
    assert "hot slot" in box["explain"]
    # ... and the trace's plan lane as a kind=skew instant
    skews = [r for r in box["ring"]
             if r.get("name") == "skew" and r.get("kind") == "skew"]
    assert skews and skews[0]["cat"] == "plan"
    assert skews[0]["worker"] == hot[0]["worker"]


def test_balanced_exchange_stays_cool():
    box = {}

    def job(ctx):
        ctx.Distribute(np.arange(256, dtype=np.int64)) \
            .Map(_mod_kv).ReducePair(_add).AllGather()
        box["stats"] = ctx.overall_stats()
        box["hot"] = ctx.doctor.hot_sites()

    RunLocalMock(job, 4)
    assert box["stats"]["skew_ratio"] < 3.0
    assert box["hot"] == []


def _mod_kv(x):
    return (x % 32, x)


# ----------------------------------------------------------------------
# critical path
# ----------------------------------------------------------------------

def test_critical_path_names_exchange_span(monkeypatch):
    """A deterministically slow exchange (the latency fault mode at
    the chunk dispatch site — 2s dwarfs any compile) must be what the
    critical path names; rig-speed variance cannot flip the verdict."""
    monkeypatch.setenv(faults.ENV_VAR,
                       "data.exchange.chunk:delay=2s:n=1")
    box = {}

    def job(ctx):
        ctx.Distribute(np.arange(128, dtype=np.int64)) \
            .Map(_mod_kv).ReducePair(_add).AllGather()
        box["report"] = ctx.doctor_report()

    RunLocalMock(job, 2)
    edges = box["report"]["critical_path"]
    assert edges, "critical path empty"
    assert any(e["cat"] == "exchange" for e in edges)
    # parent chains render as the ancestor path string
    deepest = max(edges, key=lambda e: e["path"].count(">"))
    assert "exchange" in deepest["path"]
    for e in edges:
        assert 0 <= e["excl_us"] <= e["dur_us"]


def test_critical_path_offline_over_merged_ranks():
    """The offline pass (tools/doctor_report.py build_report) over
    two ranks' span records picks the longest rank's chain."""
    recs = []
    for rank, base in ((0, 100), (1, 100)):
        dur = 50_000 if rank == 0 else 90_000
        recs.append({"event": "span", "cat": "service", "name": "job:a",
                     "trace": f"t{rank}", "span": 1, "rank": rank,
                     "ts": base, "dur_us": dur, "job": "a"})
        recs.append({"event": "span", "cat": "exchange",
                     "name": "phase_b", "trace": f"t{rank}", "span": 2,
                     "parent": 1, "rank": rank, "ts": base + 10,
                     "dur_us": dur - 20_000, "job": "a"})
    edges = critical_path(recs)
    assert edges[0]["rank"] == 1            # the longer rank's chain
    assert {e["name"] for e in edges} == {"job:a", "phase_b"}
    assert edges[0]["path"].startswith("service:job:a")


# ----------------------------------------------------------------------
# disabled pin + defaults
# ----------------------------------------------------------------------

def test_doctor_disabled_is_pinned_noop(monkeypatch):
    monkeypatch.setenv("THRILL_TPU_DOCTOR", "0")
    box = {}

    def job(ctx):
        assert ctx.doctor is None
        assert ctx.mesh_exec.doctor is None
        assert ctx.net.group.doctor is None
        ctx.Distribute(np.arange(64, dtype=np.int64)) \
            .Map(_mod_kv).ReducePair(_add).AllGather()
        box["stats"] = ctx.overall_stats()
        box["report"] = ctx.doctor_report()

    before = doctor_mod.RECORDS
    RunLocalMock(job, 2)
    assert doctor_mod.RECORDS == before     # zero records allocated
    st = box["stats"]
    assert st["collective_wait_s"] == 0.0
    assert st["skew_ratio"] == 0.0
    assert st["straggler_waits"] == {}
    assert box["report"] == {}


def test_doctor_on_by_default_records_exchange_waits():
    box = {}

    def job(ctx):
        ctx.Distribute(np.arange(64, dtype=np.int64)) \
            .Map(_mod_kv).ReducePair(_add).AllGather()
        box["stats"] = ctx.overall_stats()

    before = doctor_mod.RECORDS
    RunLocalMock(job, 2)
    assert doctor_mod.RECORDS > before
    # single-controller runs have no host peers: the wait ledger is
    # exchange barriers (plan syncs / deferred checks) only
    st = box["stats"]
    assert st["wait_exchange_s"] >= 0.0
    assert st["collective_wait_s"] == pytest.approx(
        st["wait_net_s"] + st["wait_exchange_s"], abs=2e-4)


# ----------------------------------------------------------------------
# perf-contract sentinel
# ----------------------------------------------------------------------

def test_sentinel_round_trip_and_fuse_regression(monkeypatch):
    """Snapshot -> identical fresh run diffs clean; a FUSE=0 run fails
    on the dispatch-count contract (the fusion-breaking regression
    class). The 1-dispatch 'chain' workload keeps this in-tier; the
    full-workload round trip is the slow twin below."""
    from thrill_tpu.tools import perf_sentinel as ps
    a = ps.snapshot(workloads=["chain"])
    assert ps.diff(a, ps.snapshot(workloads=["chain"])) == []
    monkeypatch.setenv("THRILL_TPU_FUSE", "0")
    probs = ps.diff(a, ps.snapshot(workloads=["chain"]))
    assert any("device_dispatches" in p for p in probs), probs


def test_sentinel_byte_band_and_missing_workload():
    from thrill_tpu.tools import perf_sentinel as ps
    contract = {"version": ps.VERSION, "env": {}, "workloads": {
        "wordcount": {k: 4 for k in ps.COUNTERS} | {
            "bytes_on_wire": 1000, "bytes_on_wire_raw": 1000,
            "bytes_moved": 1000},
        "ghost": {}}}
    fresh = {"version": ps.VERSION, "env": {}, "workloads": {
        "wordcount": {k: 4 for k in ps.COUNTERS} | {
            "bytes_on_wire": 2000, "bytes_on_wire_raw": 1100,
            "bytes_moved": 1000}}}
    probs = ps.diff(contract, fresh)
    assert any("ghost" in p for p in probs)
    assert any("bytes_on_wire:" in p and "band" in p for p in probs)
    # 10% drift stays inside the default 25% band
    assert not any("bytes_on_wire_raw" in p for p in probs)


def test_sentinel_serve_row_pins_elastic_machinery_idle():
    """Elastic mesh (ISSUE 16): the checked-in serve row must claim
    EXACTLY zero resizes / resize wall time / admission rejections —
    the machinery costs nothing when a serving Context never uses it —
    and a fresh resize-free serve run must match that claim."""
    from thrill_tpu.tools import perf_sentinel as ps
    path = os.path.join(os.path.dirname(__file__), "..", "..",
                        "PERF_CONTRACT.json")
    with open(path) as f:
        contract = json.load(f)
    row = contract["workloads"]["serve"]
    assert row["resizes"] == 0
    assert row["resize_time_ms"] == 0
    assert row["jobs_rejected"] == 0
    assert row["jobs_failed"] == 0
    assert row["jobs_submitted"] == 3
    fresh = ps.snapshot(workloads=["serve"])
    assert ps.diff({**contract, "workloads": {"serve": row}},
                   fresh) == []


def test_sentinel_default_path_needs_no_bench_py(tmp_path, monkeypatch):
    """From anywhere, the sentinel finds the checkout's contract by the
    contract file itself, not by a benchmark script beside it."""
    from thrill_tpu.tools import perf_sentinel as ps
    root = os.path.abspath(os.path.join(os.path.dirname(__file__),
                                        "..", ".."))
    monkeypatch.chdir(tmp_path)
    assert ps.default_path() == os.path.join(root, "PERF_CONTRACT.json")


@pytest.mark.slow
def test_repo_perf_contract_matches_fresh_run():
    """The checked-in PERF_CONTRACT.json must describe THIS tree: a
    fresh run of every contract workload diffs clean (the tier the
    perf_sentinel.sh CI hook enforces)."""
    from thrill_tpu.tools import perf_sentinel as ps
    path = os.path.join(os.path.dirname(__file__), "..", "..",
                        "PERF_CONTRACT.json")
    with open(path) as f:
        contract = json.load(f)
    fresh = ps.snapshot(workloads=contract["workloads"])
    assert ps.diff(contract, fresh) == []
