"""ReduceByKey's fold hands the stitched program compact rows
(core/segmented.py ``reduce_runs``: one row per run, gathered at the run
boundaries), so ``jit_fused_ReduceLocal`` carries no compaction and
scatters no value; and the three ops that fold runs with a plain Python
function agree with numpy fused, with THRILL_TPU_FUSE=0, and on a
four-worker mesh. THRILL_TPU_HOST_RADIX=0 throughout: the jitted
engines are what the chip runs."""

import re

import numpy as np
import pytest

import jax.numpy as jnp

from thrill_tpu.api import Context, FieldReduce
from thrill_tpu.parallel import mesh as mesh_mod
from thrill_tpu.parallel.mesh import MeshExec


@pytest.fixture(autouse=True)
def _jitted_engines(monkeypatch):
    monkeypatch.setenv("THRILL_TPU_HOST_RADIX", "0")


def _word(t):
    return t["w"]


_COUNT = FieldReduce({"w": "first", "c": "sum"})


def test_fused_reduce_by_key_neither_compacts_nor_scatters_a_value(
        monkeypatch):
    """``wordcount.w1``'s program at rehearsal size: the four scatters
    that were 68 % of its device time (PERF.md section 6, PR 31) have
    nothing to come back through."""
    lowered = {}
    dispatch = mesh_mod._CountedJit._dispatch

    def recording(self, args, kwargs):
        lowered.setdefault(self._label(), self.lower(*args, **kwargs))
        return dispatch(self, args, kwargs)

    monkeypatch.setattr(mesh_mod._CountedJit, "_dispatch", recording)
    rng = np.random.default_rng(31)
    n = 4096
    ids = rng.integers(0, 256, n)
    vocab = rng.integers(97, 123, (256, 16)).astype(np.uint8)
    vocab[:, 0] = np.arange(256)
    ctx = Context(MeshExec(num_workers=1))
    try:
        got = ctx.Distribute({"w": vocab[ids], "c": np.ones(n, np.int64)}) \
            .ReduceByKey(_word, _COUNT).AllGatherArrays()
    finally:
        ctx.close()
    order = np.argsort(np.asarray(got["w"])[:, 0])
    np.testing.assert_array_equal(np.asarray(got["w"])[order],
                                  vocab[np.unique(ids)])
    np.testing.assert_array_equal(np.asarray(got["c"])[order],
                                  np.bincount(ids)[np.unique(ids)])
    program = lowered["fused_ReduceLocal"]
    text = program.as_text(debug_info=True)
    assert "/segmented_reduce/run_bounds/" in text
    assert "/segmented_reduce/run_fold/" in text
    assert "/row_move/" in text and "/compact/" not in text
    hlo = program.compiler_ir(dialect="hlo").as_hlo_text()
    scattered = re.findall(r"= (\w+\[[\d,]*\])\S* scatter\(", hlo)
    for shape in scattered:
        assert not re.match(r"(s64|u64|f64)\[", shape), shape
        assert not re.match(r"u8\[\d+,16\]", shape), shape
    assert len(re.findall(r" gather\(", hlo)) >= 2


def _runs(monkeypatch, build, want):
    """``build(ctx)`` on one worker and on four, fused and not."""
    for W in (1, 4):
        for fuse in ("1", "0"):
            monkeypatch.setenv("THRILL_TPU_FUSE", fuse)
            ctx = Context(MeshExec(num_workers=W))
            try:
                got = build(ctx)
            finally:
                ctx.close()
            assert got == want, (W, fuse)


N = 3000
RNG = np.random.default_rng(5)
KEYS = RNG.integers(0, 61, N).astype(np.int64)
VALS = RNG.integers(-10 ** 12, 10 ** 12, N).astype(np.int64)
SIZE = 80               # ReduceToIndex: rows 61..79 take no item


def _model():
    out = {}
    for k, v in zip(KEYS.tolist(), VALS.tolist()):
        lo, hi, s = out.get(k, (v, v, 0))
        out[k] = (min(lo, v), max(hi, v), s + v)
    return out


def _k(t):
    return t["k"]


def _fold(a, b):
    return {"k": a["k"], "lo": jnp.minimum(a["lo"], b["lo"]),
            "hi": jnp.maximum(a["hi"], b["hi"]), "s": a["s"] + b["s"]}


def _pair_fold(a, b):
    return (jnp.minimum(a[0], b[0]), a[1] + b[1])


def _by_key(ctx):
    d = ctx.Distribute({"k": KEYS, "lo": VALS, "hi": VALS, "s": VALS})
    return sorted((int(t["k"]), int(t["lo"]), int(t["hi"]), int(t["s"]))
                  for t in d.ReduceByKey(_k, _fold).AllGather())


def _as_pair(t):
    return (t["k"], (t["lo"], t["s"]))


def _pair(ctx):
    d = ctx.Distribute({"k": KEYS, "lo": VALS, "s": VALS}).Map(_as_pair)
    return sorted((int(k), int(v[0]), int(v[1]))
                  for k, v in d.ReducePair(_pair_fold).AllGather())


def _to_index(ctx):
    d = ctx.Distribute({"k": KEYS, "lo": VALS, "hi": VALS, "s": VALS})
    neutral = {"k": np.int64(-1), "lo": np.int64(0), "hi": np.int64(0),
               "s": np.int64(-7)}
    return [(int(t["k"]), int(t["lo"]), int(t["hi"]), int(t["s"]))
            for t in d.ReduceToIndex(_k, _fold, SIZE, neutral).AllGather()]


@pytest.mark.parametrize("op", ["ReduceByKey", "ReducePair",
                                "ReduceToIndex"])
def test_a_plain_reduce_function_folds_the_same_everywhere(op, monkeypatch):
    m = _model()
    if op == "ReduceByKey":
        _runs(monkeypatch, _by_key, sorted((k, *v) for k, v in m.items()))
    elif op == "ReducePair":
        _runs(monkeypatch, _pair, sorted((k, v[0], v[2]) for k, v in m.items()))
    else:
        _runs(monkeypatch, _to_index, [(i, *m[i]) if i in m else (-1, 0, 0, -7)
                          for i in range(SIZE)])
