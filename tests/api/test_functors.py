"""FieldReduce declarative functor (api/functors.py): fused-native /
generic-fold / jitted-device engines must agree, and unsupported leaf
shapes must fall back (correctly) rather than fail.
"""

import numpy as np
import pytest

from thrill_tpu.api import Context, FieldReduce
from thrill_tpu.parallel.mesh import MeshExec


def _run_reduce(W, red, data, env=None, monkeypatch=None):
    if monkeypatch is not None and env is not None:
        for k, v in env.items():
            monkeypatch.setenv(k, v)
    mex = MeshExec(num_workers=W)
    ctx = Context(mex)
    out = ctx.Distribute(data).ReduceByKey(lambda t: t["k"], red)
    hs = out.node.materialize().to_host_shards("test")
    rows = [it for l in hs.lists for it in l]
    ctx.close()
    return rows


def _model(data, n):
    model = {}
    for i in range(n):
        k = int(data["k"][i])
        v, f = int(data["v"][i]), float(data["f"][i])
        if k in model:
            mv, mf = model[k]
            model[k] = (mv + v, min(mf, f))
        else:
            model[k] = (v, f)
    return model


@pytest.mark.parametrize("W", [
    2,
    pytest.param(1, marks=pytest.mark.slow)])  # tier-1 budget: W=2
def test_field_reduce_matches_model_and_generic(W, monkeypatch):
    rng = np.random.default_rng(11)
    n = 20000
    data = {"k": rng.integers(0, 257, size=n).astype(np.int64),
            "v": rng.integers(-50, 50, size=n).astype(np.int64),
            "f": rng.standard_normal(n)}
    red = FieldReduce({"k": "first", "v": "sum", "f": "min"})
    rows = _run_reduce(W, red, data)
    model = _model(data, n)
    got = {int(r["k"]): (int(r["v"]), float(r["f"])) for r in rows}
    assert got == model
    # jitted device engine (host engine disabled) agrees
    monkeypatch.setenv("THRILL_TPU_HOST_RADIX", "0")
    rows_jit = _run_reduce(W, red, data)
    got_jit = {int(r["k"]): (int(r["v"]), float(r["f"]))
               for r in rows_jit}
    assert got_jit == model


def test_field_reduce_single_leaf_tree():
    """Items that ARE the key (plain array tree): spec is the op string."""
    rng = np.random.default_rng(5)
    vals = rng.integers(0, 9, size=5000).astype(np.int64)
    mex = MeshExec(num_workers=1)
    ctx = Context(mex)
    out = ctx.Distribute(vals).ReduceByKey(lambda x: x,
                                           FieldReduce("first"))
    got = sorted(int(x) for x in out.AllGather())
    ctx.close()
    assert got == sorted(set(int(v) for v in vals))


def test_field_reduce_unsupported_leaves_fall_back():
    """2-D summed leaf and bool leaf are not fuseable — the generic
    fold must take over and still be correct."""
    rng = np.random.default_rng(6)
    n = 3000
    data = {"k": rng.integers(0, 31, size=n).astype(np.int64),
            "m": rng.integers(0, 5, size=(n, 3)).astype(np.int64)}
    red = FieldReduce({"k": "first", "m": "sum"})
    rows = _run_reduce(1, red, data)
    model = {}
    for i in range(n):
        k = int(data["k"][i])
        model[k] = model.get(k, 0) + data["m"][i]
    got = {int(r["k"]): np.asarray(r["m"]) for r in rows}
    assert set(got) == set(model)
    for k in model:
        assert (got[k] == model[k]).all()


def test_field_reduce_nan_min_parity():
    """NaN-poisoned groups: fused path must propagate NaN exactly like
    np.minimum (and hence like the generic engines)."""
    n = 1000
    rng = np.random.default_rng(8)
    data = {"k": rng.integers(0, 10, size=n).astype(np.int64),
            "f": rng.standard_normal(n)}
    data["f"][::97] = np.nan
    red = FieldReduce({"k": "first", "f": "min"})
    rows = _run_reduce(1, red, data)
    model = {}
    for i in range(n):
        k = int(data["k"][i])
        model[k] = (np.minimum(model[k], data["f"][i])
                    if k in model else data["f"][i])
    got = {int(r["k"]): float(r["f"]) for r in rows}
    for k, v in model.items():
        assert np.isnan(got[k]) if np.isnan(v) else got[k] == v


def test_field_reduce_bad_op_raises():
    with pytest.raises(ValueError):
        FieldReduce({"k": "first", "v": "product"})


def test_field_reduce_content_equality():
    """Content-equal functors must hash equal (executable-cache reuse
    across pipelines constructing fresh instances inline)."""
    a = FieldReduce({"k": "first", "v": "sum"})
    b = FieldReduce({"k": "first", "v": "sum"})
    c = FieldReduce({"k": "first", "v": "max"})
    assert a == b and hash(a) == hash(b)
    assert a != c and a != "FieldReduce"


def test_malformed_reduce_fn_structure_raises():
    """A reduce_fn returning a differently-structured tree must raise,
    never silently mispair leaves (on any engine)."""
    rng = np.random.default_rng(2)
    n = 2000
    data = {"k": rng.integers(0, 7, size=n).astype(np.int64),
            "c": np.ones(n, dtype=np.int64)}

    def bad(a, b):
        return {"a": a["k"], "b": a["c"] + b["c"]}   # wrong structure

    mex = MeshExec(num_workers=1)
    ctx = Context(mex)
    with pytest.raises(Exception):
        ctx.Distribute(data).ReduceByKey(lambda t: t["k"], bad).AllGather()
    ctx.close()


def test_field_reduce_bool_first_leaf_device_engine(monkeypatch):
    """bool 'first' leaves must work on the per-field device engine
    (a gather at the run starts, any dtype)."""
    monkeypatch.setenv("THRILL_TPU_HOST_RADIX", "0")
    n = 2000
    rng = np.random.default_rng(3)
    data = {"k": rng.integers(0, 9, size=n).astype(np.int64),
            "b": (rng.integers(0, 2, size=n) == 1),
            "c": np.ones(n, dtype=np.int64)}
    red = FieldReduce({"k": "first", "b": "first", "c": "sum"})
    rows = _run_reduce(1, red, data)
    model = {}
    for k, b in zip(data["k"].tolist(), data["b"].tolist()):
        model.setdefault(int(k), bool(b))      # first occurrence wins
    got = {int(r["k"]): bool(r["b"]) for r in rows}
    assert got == model
    assert sum(int(r["c"]) for r in rows) == n


def test_field_reduce_first_preserves_negative_zero(monkeypatch):
    """float 'first' on the per-field engine must be bit-exact: a
    -0.0 first value keeps its sign bit (the engine gathers the row;
    a float sum would canonicalize -0.0 + 0.0 -> +0.0)."""
    monkeypatch.setenv("THRILL_TPU_HOST_RADIX", "0")
    data = {"k": np.array([1, 1, 2, 2], np.int64),
            "f": np.array([-0.0, 5.0, 3.0, -0.0], np.float64),
            "c": np.ones(4, np.int64)}
    red = FieldReduce({"k": "first", "f": "first", "c": "sum"})
    rows = _run_reduce(1, red, data)
    got = {int(r["k"]): float(r["f"]) for r in rows}
    assert got == {1: -0.0, 2: 3.0}
    assert np.signbit(got[1]), "-0.0 sign bit lost by the engine"


def test_inplace_mutating_reduce_fn_still_correct():
    """A black-box reduce_fn that mutates its left argument in place
    and returns it (``a['c'] += b['c']; return a``) must still produce
    correct results on the host fold engine — the identity write-back
    skip is reserved for provably pure functors."""
    rng = np.random.default_rng(13)
    n = 5000
    data = {"k": rng.integers(0, 43, size=n).astype(np.int64),
            "c": np.ones(n, dtype=np.int64)}

    def red(a, b):
        a["c"] += b["c"]
        return a

    rows = _run_reduce(1, red, data)
    got = {int(r["k"]): int(r["c"]) for r in rows}
    model = {}
    for k in data["k"]:
        model[int(k)] = model.get(int(k), 0) + 1
    assert got == model


@pytest.mark.parametrize("W", [1, 4])
@pytest.mark.parametrize("red_kind", ["field", "lambda"])
def test_reduce_to_index_host_engine_parity(W, red_kind, monkeypatch):
    """The CPU host mirror of ReduceToIndex (ufunc.at scatter for
    FieldReduce, hash-group + fold for generic fns) must agree with
    the jitted engine, including neutral fill of untouched indices."""
    rng = np.random.default_rng(23)
    n, size = 5000, 300                  # some indices never hit
    data = {"i": rng.integers(0, size, size=n).astype(np.int64),
            "v": rng.integers(-9, 9, size=n).astype(np.int64)}
    if red_kind == "field":
        red = FieldReduce({"i": "first", "v": "sum"})
    else:
        def red(a, b):
            return {"i": a["i"], "v": a["v"] + b["v"]}

    def run():
        mex = MeshExec(num_workers=W)
        ctx = Context(mex)
        out = ctx.Distribute(data).ReduceToIndex(
            lambda t: t["i"], red, size,
            neutral={"i": -1, "v": -77})
        rows = [(int(r["i"]), int(r["v"])) for r in out.AllGather()]
        ctx.close()
        return rows

    host = run()
    monkeypatch.setenv("THRILL_TPU_HOST_RADIX", "0")
    jit = run()
    assert host == jit
    model = {}
    for i, v in zip(data["i"].tolist(), data["v"].tolist()):
        model[i] = model.get(i, 0) + v
    assert host == [(i if i in model else -1,
                     model.get(i, -77)) for i in range(size)]


def test_reduce_to_index_min_sentinels_never_leak(monkeypatch):
    """min spec: untouched indices must show the neutral (or 0), never
    the internal +inf/int-max sentinel — on BOTH engines."""
    data = {"i": np.array([2, 2, 5], np.int64),
            "v": np.array([7, 3, 9], np.int64)}

    def run():
        mex = MeshExec(num_workers=1)
        ctx = Context(mex)
        out = ctx.Distribute(dict(data)).ReduceToIndex(
            lambda t: t["i"], FieldReduce({"i": "first", "v": "min"}),
            8)
        rows = [int(r["v"]) for r in out.AllGather()]
        ctx.close()
        return rows

    assert run() == [0, 0, 3, 0, 0, 9, 0, 0]
    monkeypatch.setenv("THRILL_TPU_HOST_RADIX", "0")
    assert run() == [0, 0, 3, 0, 0, 9, 0, 0]


def test_field_reduce_wordcount_matches_counter():
    """End-to-end WordCount (Zipf ids, small n) is EXACTLY
    collections.Counter."""
    import collections
    n = 20000
    rng = np.random.default_rng(1)
    ids = np.minimum(rng.zipf(1.3, size=n) - 1, 1023)
    words = np.zeros((n, 16), dtype=np.uint8)
    digits = np.char.zfill(ids.astype("U8"), 8)
    words[:, :8] = np.frombuffer(
        "".join(digits.tolist()).encode("ascii"),
        dtype=np.uint8).reshape(n, 8)
    cres = collections.Counter(
        "".join(map(chr, row)) for row in words)
    data = {"w": words, "c": np.ones(n, dtype=np.int64)}
    red = FieldReduce({"w": "first", "c": "sum"})
    mex = MeshExec(num_workers=1)
    ctx = Context(mex)
    out = ctx.Distribute(data).ReduceByKey(lambda t: t["w"], red)
    rows = out.AllGather()
    ctx.close()
    got = {"".join(map(chr, np.asarray(r["w"]))): int(r["c"])
           for r in rows}
    assert got == dict(cres)


def test_field_reduce_structure_mismatch_is_descriptive():
    """ReducePair("sum") over pytree values (round-4 advisor): the
    structure mismatch must raise an actionable TypeError naming
    FieldReduce, not jax.tree.map's internal ValueError."""
    red = FieldReduce(("first", "sum"))
    with pytest.raises(TypeError, match="FieldReduce spec structure"):
        red(("k", {"a": 1, "b": 2}), ("k", {"a": 3, "b": 4}))
