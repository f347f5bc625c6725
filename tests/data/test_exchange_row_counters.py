"""The exchange's row counters and ReduceByKey's duplicate-detection
count, on a virtual mesh of four CPU devices: ``xchg_rows_in`` is the
send matrix's total and ``xchg_rows_local`` its trace, added once per
exchange where its traffic is accounted, on the planned path, on the
optimistic path and on a healed capacity miss; both are read off what
the host already holds, so no fetch comes with them. Phase A's span
carries the duplicate-detection verdict and the register width, and its
program the scopes ``exchange/dest_sort`` and
``reduce_by_key/dup_detect``."""

import jax
import numpy as np
import pytest

from thrill_tpu.api import Context, FieldReduce
from thrill_tpu.common.partition import dense_range_bounds
from thrill_tpu.core import preshuffle
from thrill_tpu.data import exchange
from thrill_tpu.parallel.mesh import MeshExec, _CountedJit

W = 4
COUNT = FieldReduce({"w": "first", "c": "sum"})
COUNTERS = ("xchg_rows_in", "xchg_rows_local", "exchanges",
            "dup_detect_exchanges", "device_fetches", "device_dispatches",
            "cap_cache_hits", "cap_cache_misses")


@pytest.fixture(autouse=True)
def device_programs(monkeypatch):
    """What the chip runs (chipbench/run.py --rehearse sets the same)."""
    monkeypatch.setenv("THRILL_TPU_HOST_RADIX", "0")
    monkeypatch.setenv("THRILL_TPU_SORT_U32", "1")
    monkeypatch.setenv("THRILL_TPU_PACK_MOVE", "1")


@pytest.fixture
def send_matrices(monkeypatch):
    """Every send matrix the exchange accounts, in order."""
    seen = []
    real = exchange.account_traffic

    def recording(mex, S, *args, **kwargs):
        seen.append(np.array(S, dtype=np.int64))
        return real(mex, S, *args, **kwargs)

    monkeypatch.setattr(exchange, "account_traffic", recording)
    return seen


def _word_key(t):
    return t["w"]


def _words(seed, n, distinct):
    """``n`` packed 16-byte words drawn Zipf-like from ``distinct``."""
    rng = np.random.default_rng(seed)
    vocab = rng.integers(ord("a"), ord("z") + 1, (distinct, 16),
                         dtype=np.uint8)
    vocab[:, :4] = np.arange(distinct, dtype=">u4").view(np.uint8) \
        .reshape(distinct, 4)
    p = 1.0 / np.arange(1, distinct + 1) ** 1.1
    return vocab[rng.choice(distinct, n, p=p / p.sum())]


def _pre_runs(words):
    """What each worker's local fold leaves: its block's distinct words."""
    b = dense_range_bounds(len(words), W)
    return [len(np.unique(words[b[w]:b[w + 1]], axis=0)) for w in range(W)]


def _count(ctx, words):
    inp = {"w": words, "c": np.ones(len(words), np.int64)}
    got = ctx.Distribute(inp).ReduceByKey(_word_key, COUNT).AllGatherArrays()
    return {bytes(w): int(c) for w, c in zip(np.asarray(got["w"]),
                                             np.asarray(got["c"]))}


def _job(ctx, words):
    """One WordCount job; what the counters gained over it."""
    before = ctx.overall_stats()
    got = _count(ctx, words)
    after = ctx.overall_stats()
    rows, counts = np.unique(words, axis=0, return_counts=True)
    assert got == {bytes(w): int(c) for w, c in zip(rows, counts)}
    return {k: after[k] - before[k] for k in COUNTERS}


def _ctx():
    return Context(MeshExec(devices=jax.devices("cpu")[:W]))


@pytest.mark.parametrize("path", ["planned", "optimistic"])
def test_rows_in_are_the_pre_phase_runs_and_local_rows_the_trace(
        path, send_matrices, monkeypatch):
    """Each job: one exchange, its rows the runs the pre-phase left on
    every worker, its local rows the send matrix's trace. The first job
    plans (host sync of the send matrix); later ones ride the cached
    plan unless the cache is off. Fetches and dispatches per job are
    what they were before the counters."""
    monkeypatch.setenv("THRILL_TPU_DUP_DETECT", "0")
    if path == "planned":
        monkeypatch.setenv("THRILL_TPU_XCHG_CAP_CACHE", "0")
    words = _words(41, 4096, 300)
    ctx = _ctx()
    try:
        deltas = [_job(ctx, words) for _ in range(3)]
    finally:
        ctx.close()
    assert len(send_matrices) == 3
    runs = sum(_pre_runs(words))
    for S, d in zip(send_matrices, deltas):
        assert S.sum() == runs
        assert d["exchanges"] == 1
        assert d["xchg_rows_in"] == runs
        assert d["xchg_rows_local"] == np.trace(S)
        assert d["dup_detect_exchanges"] == 0
    for d in deltas[1:]:
        # the pre-phase, phase A, phase B, the post-phase
        assert d["device_dispatches"] == 4
        if path == "optimistic":
            assert (d["cap_cache_hits"], d["cap_cache_misses"]) == (1, 0)
            assert d["device_fetches"] == 1
        else:
            assert d["cap_cache_hits"] == 0
            assert d["device_fetches"] == 2     # the plan sync, the counts


def test_a_healed_capacity_miss_counts_its_rows_once(send_matrices,
                                                     monkeypatch):
    """A job whose runs outgrow the capacities the cached plan trusts:
    the optimistic phase B truncates, the deferred check finds it and
    re-runs the planned exchange; the rows are counted once, from the
    planned run's send matrix, and the table is exact."""
    monkeypatch.setenv("THRILL_TPU_DUP_DETECT", "0")
    few, many = _words(42, 4096, 60), _words(43, 4096, 3000)
    ctx = _ctx()
    try:
        _job(ctx, few)
        d = _job(ctx, many)
    finally:
        ctx.close()
    assert d["cap_cache_misses"] == 1 and d["exchanges"] == 1
    assert len(send_matrices) == 2
    S = send_matrices[-1]
    assert S.sum() == sum(_pre_runs(many)) == d["xchg_rows_in"]
    assert d["xchg_rows_local"] == np.trace(S)


@pytest.mark.parametrize("dup", ["0", "1"])
def test_phase_a_carries_the_verdict_and_the_register_scopes(
        dup, monkeypatch):
    """The ``phase_a`` span says whether the presence registers ran and
    how wide they were; the destination program carries
    ``reduce_by_key/dup_detect`` where they run and
    ``exchange/dest_sort`` always; ``dup_detect_exchanges`` counts the
    exchanges that filled registers."""
    monkeypatch.setenv("THRILL_TPU_DUP_DETECT", dup)
    lowered = {}
    dispatch = _CountedJit._dispatch

    def recording(self, args, kwargs):
        lowered.setdefault(self._label(), self.lower(*args, **kwargs)
                           .as_text(debug_info=True))
        return dispatch(self, args, kwargs)

    monkeypatch.setattr(_CountedJit, "_dispatch", recording)
    words = _words(44, 2048, 200)
    ctx = _ctx()
    try:
        d = _job(ctx, words)
        spans = [r for r in ctx.tracer.ring if r.get("cat") == "exchange"
                 and r.get("name") == "phase_a"]
    finally:
        ctx.close()
    on = dup == "1"
    assert d["dup_detect_exchanges"] == int(on)
    (span,) = spans
    assert span["dup"] is on
    # sized to the padded rows over the mesh: 2,048 words, 512 a worker
    assert span["regs"] == (preshuffle.register_width(2048) if on else 0)
    (phase_a,) = [t for label, t in lowered.items()
                  if label.startswith("xchg_a")]
    assert "exchange/dest_sort" in phase_a
    assert ("reduce_by_key/dup_detect" in phase_a) is on
    assert not any("dup_detect" in t or "dest_sort" in t
                   for label, t in lowered.items()
                   if not label.startswith("xchg_a"))
