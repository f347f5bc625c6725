"""A sort hands back the keys it sorted (core/device_sort.py
``sort_words``): the same permutation as ``argsort_words`` on every
engine, the words bit for bit what a gather by it gives, the callers'
results unchanged with no gather of a sort operand left in their
programs, ``argsort_words``' callers lowered as before, and
``overall_stats()["sort_keys_reused"]`` counting what was taken.

Every jit here wraps a fresh lambda: jax keeps one trace per function
and shapes, whatever the engine a test has pinned since."""

import hashlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.extend import core as jcore

from thrill_tpu.api import Context, FieldReduce, InnerJoin
from thrill_tpu.core import device_sort, segmented
from thrill_tpu.parallel import mesh as mesh_mod
from thrill_tpu.parallel.mesh import MeshExec


def _words(n, dtype, seed):
    """An invalid word with the invalid rows first and last, then two
    key words with ties (the first over the whole range of ``dtype``,
    the second of three values)."""
    rng = np.random.default_rng(seed)
    bits = np.iinfo(dtype).bits
    invalid = np.zeros(n, np.uint32)
    invalid[:5] = invalid[-7:] = 1
    pool = rng.integers(0, 1 << (bits - 1), 64, dtype=np.uint64) * 2 + 1
    return [jnp.asarray(invalid),
            jnp.asarray(pool[rng.integers(0, 64, n)].astype(dtype)),
            jnp.asarray(rng.integers(0, 3, n).astype(dtype))]


@pytest.mark.parametrize("n", [8192, 5000])     # a power of two; padded
@pytest.mark.parametrize("split", ["0", "1"])
@pytest.mark.parametrize("dtype", [np.uint32, np.uint64])
@pytest.mark.parametrize("engine", ["xla", "chunked", "bitonic", "radix"])
def test_sort_words_is_argsort_then_take(monkeypatch, engine, dtype, split,
                                         n):
    monkeypatch.setenv("THRILL_TPU_SORT_IMPL", engine)
    monkeypatch.setenv("THRILL_TPU_SORT_U32", split)
    words = _words(n, dtype, n + len(engine))
    got, perm = jax.jit(lambda ws: device_sort.sort_words(ws))(words)
    want = jax.jit(lambda ws: device_sort.argsort_words(ws))(words)
    assert perm.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(perm), np.asarray(want))
    assert len(got) == len(words)
    for g, w in zip(got, words):
        assert g.dtype == w.dtype
        np.testing.assert_array_equal(np.asarray(g),
                                      np.asarray(jnp.take(w, perm)))


# ----------------------------------------------------------------------
# the converted callers: results bit-equal to the gather path
# ----------------------------------------------------------------------

def _gathering(words):
    """The parent's path: the permutation, then a gather of each word."""
    perm = device_sort.argsort_words(words)
    return [jnp.take(w, perm) for w in words], perm


N = 3000
RNG = np.random.default_rng(37)
VOCAB = RNG.integers(97, 123, (97, 16)).astype(np.uint8)
IDS = RNG.integers(0, 97, N)
VALS = RNG.integers(-10 ** 12, 10 ** 12, N).astype(np.int64)


def _word(t):
    return t["w"]


def _seg_sum(tree, seg_ids, nseg):
    return {"k": jax.ops.segment_max(tree["k"], seg_ids, num_segments=nseg),
            "v": jax.ops.segment_sum(tree["v"], seg_ids, num_segments=nseg)}


def _key(t):
    return t["k"]


def _pair(a, b):
    return (a["k"], a["v"], b["v"])


def _run(op, W):
    ctx = Context(MeshExec(num_workers=W))
    try:
        if op == "ReduceByKey":
            out = ctx.Distribute({"w": VOCAB[IDS], "c": VALS}).ReduceByKey(
                _word, FieldReduce({"w": "first", "c": "sum"}))
        elif op == "GroupByKey":
            out = ctx.Distribute({"k": IDS.astype(np.int64) % 13,
                                  "v": VALS}) \
                .GroupByKey(_key, device_fn=_seg_sum)
        elif op == "InnerJoin":
            left = ctx.Distribute({"k": IDS.astype(np.int64), "v": VALS})
            right = ctx.Distribute({"k": np.arange(0, 97, 2, dtype=np.int64),
                                    "v": np.arange(49, dtype=np.int64)})
            out = InnerJoin(left, right, _key, _key, _pair)
        else:
            out = ctx.Distribute({"k": VOCAB[IDS][:, :10], "v": VALS}) \
                .Sort(_key)
        return jax.tree.map(np.asarray, out.AllGatherArrays())
    finally:
        ctx.close()


@pytest.mark.parametrize("op", ["ReduceByKey", "GroupByKey", "InnerJoin",
                                "Sort"])
def test_callers_read_as_on_the_gather_path(monkeypatch, op):
    """``chunked`` with the u32 split, as on the chip, on a virtual
    mesh of four."""
    monkeypatch.setenv("THRILL_TPU_HOST_RADIX", "0")
    monkeypatch.setenv("THRILL_TPU_SORT_IMPL", "chunked")
    monkeypatch.setenv("THRILL_TPU_SORT_U32", "1")
    got = _run(op, 4)
    monkeypatch.setattr(device_sort, "sort_words", _gathering)
    want = _run(op, 4)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert g.dtype == w.dtype
        np.testing.assert_array_equal(g, w)


# ----------------------------------------------------------------------
# the programs: no gather of a sort operand; argsort callers unchanged
# ----------------------------------------------------------------------

def _gathers(jaxpr, scope=""):
    """(name scope, operand shape, rows gathered) of every gather,
    nested jaxprs too."""
    for e in jaxpr.eqns:
        at = scope + "/" + str(e.source_info.name_stack)
        if e.primitive.name == "gather":
            yield (at, tuple(e.invars[0].aval.shape),
                   e.invars[1].aval.shape[0])
        for v in e.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                if isinstance(sub, jcore.ClosedJaxpr):
                    sub = sub.jaxpr
                if isinstance(sub, jcore.Jaxpr):
                    yield from _gathers(sub, at)


def _traced_programs(monkeypatch, job, W):
    traced = {}
    dispatch = mesh_mod._CountedJit._dispatch

    def recording(self, args, kwargs):
        traced.setdefault(self._label(), self.trace(*args, **kwargs))
        return dispatch(self, args, kwargs)

    monkeypatch.setattr(mesh_mod._CountedJit, "_dispatch", recording)
    ctx = Context(MeshExec(num_workers=W))
    try:
        job(ctx)
    finally:
        ctx.close()
    return {k: list(_gathers(t.jaxpr.jaxpr)) for k, t in traced.items()}


def test_no_program_gathers_what_it_sorted(monkeypatch):
    """``wordcount.w1``'s ``ReduceLocal``: the gathers by the sort's
    permutation (scope ``row_move``) are the two payload leaves, where
    the two key words and the mask were gathered too. ``terasort.w4``'s
    ``sort_keys`` gathers no row: its payload waits for
    ``sort_classify``, and only the splitter samples are gathered."""
    for var, value in (("THRILL_TPU_HOST_RADIX", "0"),
                       ("THRILL_TPU_SORT_IMPL", "chunked"),
                       ("THRILL_TPU_SORT_U32", "1")):
        monkeypatch.setenv(var, value)
    n = 4096
    ids = np.resize(IDS, n)

    def reduce_job(ctx):
        ctx.Distribute({"w": VOCAB[ids], "c": np.ones(n, np.int64)}) \
            .ReduceByKey(_word, FieldReduce({"w": "first", "c": "sum"})) \
            .AllGatherArrays()

    gathers = _traced_programs(monkeypatch, reduce_job, 1)
    by_perm = [shape for at, shape, _ in gathers["fused_ReduceLocal"]
               if "/row_move/" in at]
    assert sorted(by_perm) == [(n,), (n, 16)]

    def sort_job(ctx):
        ctx.Distribute({"k": np.resize(VOCAB[IDS][:, :10], (n, 10)),
                        "v": np.arange(n, dtype=np.int64)}) \
            .Sort(_key).AllGatherArrays()

    gathers = _traced_programs(monkeypatch, sort_job, 4)
    cap = n // 4
    assert gathers["sort_keys"]
    assert all(rows < cap for _, _, rows in gathers["sort_keys"]), \
        gathers["sort_keys"]


def _lowered_hashes():
    """sha256 of the lowered text of the W = 1 sort (``_w1_sort_fn``,
    not full: the validity word too) and of ``sorted_fold_plan``."""
    from thrill_tpu.api.ops.sort import _w1_sort_fn
    n = 5000
    tree = {"k": jax.ShapeDtypeStruct((1, n, 10), jnp.uint8),
            "v": jax.ShapeDtypeStruct((1, n, 3), jnp.uint32)}
    leaves, treedef = jax.tree.flatten(tree)
    sort_fn = _w1_sort_fn(_key, treedef, False)
    w1 = jax.jit(lambda c, *ls: sort_fn(c, *ls)).lower(
        jax.ShapeDtypeStruct((1, 1), jnp.int32), *leaves).as_text()
    plan = jax.jit(lambda pos: segmented.sorted_fold_plan(pos, 300)).lower(
        jax.ShapeDtypeStruct((n,), jnp.int32)).as_text()
    return {name: hashlib.sha256(text.encode()).hexdigest()[:16]
            for name, text in (("w1_sort", w1), ("fold_plan", plan))}


# written from the parent of the PR that added ``sort_words`` (CPU,
# JAX 0.9.0): the programs that sort through ``argsort_words`` did not
# change with it
_PARENT_HASHES = {
    ('xla', '0'): {'w1_sort': '10841d9d2b2d351b', 'fold_plan': '7b0b38f58c8e76d3'},
    ('xla', '1'): {'w1_sort': 'efc06ce673da0fb0', 'fold_plan': '11095e96d0016eb8'},
    ('chunked', '0'): {'w1_sort': '44331a09df647e7e', 'fold_plan': 'd0f609809c4fcfff'},
    ('chunked', '1'): {'w1_sort': 'e61c8d1e2527aa65', 'fold_plan': '86a38707ac4cf021'},
    ('bitonic', '0'): {'w1_sort': '4eec986b515dbea8', 'fold_plan': 'e120f38857fa610a'},
    ('bitonic', '1'): {'w1_sort': 'be89e61d0cd0f7e9', 'fold_plan': '2a246284868f9b89'},
    ('radix', '0'): {'w1_sort': '9dbfccb19d2fbdc7', 'fold_plan': 'ec93b1cf98f3b93c'},
    ('radix', '1'): {'w1_sort': '9dbfccb19d2fbdc7', 'fold_plan': 'ec93b1cf98f3b93c'},
}


@pytest.mark.parametrize("split", ["0", "1"])
@pytest.mark.parametrize("engine", ["xla", "chunked", "bitonic", "radix"])
def test_argsort_callers_lower_as_before(monkeypatch, engine, split):
    monkeypatch.setenv("THRILL_TPU_SORT_IMPL", engine)
    monkeypatch.setenv("THRILL_TPU_SORT_U32", split)
    assert _lowered_hashes() == _PARENT_HASHES[engine, split]


# ----------------------------------------------------------------------
# the counter
# ----------------------------------------------------------------------

def _reused_per_dispatch(monkeypatch, job, W, label):
    """``sort_keys_reused`` gained by each of two runs of ``job``, and
    how often each run dispatched the program labelled ``label``."""
    seen = []
    dispatch = mesh_mod._CountedJit._dispatch

    def counting(self, args, kwargs):
        seen.append(self._label())
        return dispatch(self, args, kwargs)

    monkeypatch.setattr(mesh_mod._CountedJit, "_dispatch", counting)
    ctx = Context(MeshExec(num_workers=W))
    out = []
    try:
        for _ in range(2):          # traced, then from the cache
            seen.clear()
            s0 = ctx.overall_stats()["sort_keys_reused"]
            job(ctx)
            out.append((ctx.overall_stats()["sort_keys_reused"] - s0,
                        seen.count(label)))
    finally:
        ctx.close()
    return out


def _wordcount(ctx):
    ctx.Distribute({"w": VOCAB[IDS], "c": np.ones(N, np.int64)}) \
        .ReduceByKey(_word, FieldReduce({"w": "first", "c": "sum"})) \
        .AllGatherArrays()


def _sort(ctx):
    """Full shards, as ``terasort.w4``'s: no validity word."""
    ctx.Distribute({"k": np.resize(VOCAB[IDS][:, :10], (4096, 10)),
                    "v": np.arange(4096)}).Sort(_key).AllGatherArrays()


def _to_index(ctx):
    """A sum of 8-byte values: the fold over runs sorted by index."""
    ctx.Distribute({"k": IDS.astype(np.int64), "v": VALS}).ReduceToIndex(
        _key, FieldReduce({"k": "first", "v": "sum"}), 97) \
        .AllGatherArrays()


@pytest.mark.parametrize("job,W,label,per", [
    (_wordcount, 1, "fused_ReduceLocal", 3),   # the validity, two words
    (_sort, 4, "sort_keys", 3),                # two words, the index
    (_sort, 1, "fused_Sort", 0),               # argsort_words
    (_to_index, 1, "fused_ReduceToIndex", 0)])   # sorted_fold_plan
def test_sort_keys_reused_counts_per_dispatch(monkeypatch, job, W, label,
                                              per):
    monkeypatch.setenv("THRILL_TPU_HOST_RADIX", "0")
    for reused, dispatched in _reused_per_dispatch(monkeypatch, job, W,
                                                   label):
        assert dispatched == 1
        assert reused == per * dispatched


def _mod16(x):
    return x % 16


def _add(a, b):
    return a + b


def _fold16(d):
    """16 rows in, 16 out: the generic engine sorts (validity, index)."""
    return d.ReduceToIndex(_mod16, _add, 16, neutral=0)


@pytest.mark.parametrize("fori", ["1", "0"])
def test_a_loop_counts_its_sorts_in_every_iteration(monkeypatch, fori):
    """A whole-loop dispatch counts its calls' sorted words once per
    iteration (api/loop.py ``run_fori``); a tape replayed call by call
    counts them in every dispatch."""
    from thrill_tpu.api.loop import Iterate
    monkeypatch.setenv("THRILL_TPU_HOST_RADIX", "0")
    monkeypatch.setenv("THRILL_TPU_LOOP_FORI", fori)
    ctx = Context(MeshExec(num_workers=1))
    try:
        out = Iterate(ctx, _fold16,
                      ctx.Distribute(np.arange(16, dtype=np.int64)), 5,
                      name="sorts")
        out.AllGather()
        stats = ctx.overall_stats()
    finally:
        ctx.close()
    assert stats["loop_plan_builds"] == 1
    assert stats["loop_fori_iters"] == (4 if fori == "1" else 0)
    assert stats["sort_keys_reused"] == 2 * 5
