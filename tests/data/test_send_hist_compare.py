"""The exchange counts its send matrix by comparison (core/pallas_kernels.py
``partition_histogram``, data/exchange.py ``send_counts``): on a mock mesh
of four, ``ReduceByKey`` and ``Sort`` give what they gave with the
scatter-add, and ``overall_stats()["send_hists_by_compare"]`` counts one
per dispatch of phase A or of ``Sort``'s classification, none at W = 1."""

import numpy as np
import pytest

from thrill_tpu.api import Context, FieldReduce
from thrill_tpu.core import pallas_kernels as pk
from thrill_tpu.parallel import mesh as mesh_mod
from thrill_tpu.parallel.mesh import MeshExec

N = 3000
RNG = np.random.default_rng(42)
VOCAB = RNG.integers(97, 123, (97, 16)).astype(np.uint8)
IDS = RNG.integers(0, 97, N)
KEYS = RNG.integers(0, 256, (N, 10)).astype(np.uint8)


def _word(t):
    return t["w"]


def _key(t):
    return t["k"]


def _wordcount(ctx):
    out = ctx.Distribute({"w": VOCAB[IDS], "c": np.ones(N, np.int64)}) \
        .ReduceByKey(_word, FieldReduce({"w": "first", "c": "sum"})) \
        .AllGatherArrays()
    order = np.lexsort(out["w"].T[::-1])
    return out["w"][order], out["c"][order]


def _sort(ctx):
    out = ctx.Distribute({"k": KEYS, "v": np.arange(N)}).Sort(_key) \
        .AllGatherArrays()
    return out["k"], out["v"]


def _want(job):
    if job is _wordcount:
        words, counts = np.unique(VOCAB[IDS], axis=0, return_counts=True)
        return words, counts
    order = np.lexsort(KEYS.T[::-1])
    return KEYS[order], np.arange(N)[order]


def _runs(monkeypatch, job, W, label):
    """Two runs of ``job`` (traced, then from the cache): each run's
    result, ``send_hists_by_compare`` gained and dispatches of the
    program labelled ``label``."""
    seen = []
    dispatch = mesh_mod._CountedJit._dispatch

    def counting(self, args, kwargs):
        seen.append(self._label())
        return dispatch(self, args, kwargs)

    monkeypatch.setattr(mesh_mod._CountedJit, "_dispatch", counting)
    ctx = Context(MeshExec(num_workers=W))
    out = []
    try:
        for _ in range(2):
            seen.clear()
            s0 = ctx.overall_stats()["send_hists_by_compare"]
            got = job(ctx)
            out.append((got, ctx.overall_stats()["send_hists_by_compare"]
                        - s0, seen.count(label), list(seen)))
    finally:
        ctx.close()
    return out


@pytest.mark.parametrize("max_bins", [pk.HIST_COMPARE_MAX_BINS, 0],
                         ids=["compare", "scatter"])
@pytest.mark.parametrize("job,W,label", [
    (_wordcount, 4, "xchg_a"),
    (_sort, 4, "sort_classify"),
    (_wordcount, 1, "xchg_a"),
    (_sort, 1, "sort_classify")])
def test_send_hists_by_compare_counts_per_dispatch(monkeypatch, job, W,
                                                   label, max_bins):
    """``max_bins`` 0 sends every histogram to the scatter-add, the
    mechanism before the comparison: the results are the same and the
    counter stays 0."""
    monkeypatch.setenv("THRILL_TPU_HOST_RADIX", "0")
    monkeypatch.setattr(pk, "HIST_COMPARE_MAX_BINS", max_bins)
    want = _want(job)
    for got, by_compare, dispatched, seen in _runs(monkeypatch, job, W,
                                                   label):
        for g, w in zip(got, want):
            np.testing.assert_array_equal(g, w)
        assert dispatched == (1 if W > 1 else 0), seen
        assert by_compare == (dispatched if max_bins else 0)
