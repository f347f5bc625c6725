"""A stitched chain compacts only where its valid rows are no prefix
(api/fusion.py ``FusionPlan._execute_inner``).

A source's rows are a prefix (its mask is made from its count), so are
those behind a segment that says ``already_compact`` (Sort,
ReduceToIndex), and a stack of maps keeps them where they are. Such a
chain hands on what it computed with no compaction scatter: the counts
are the source's, the valid rows are the first ``count`` of every
worker, and the rows past them hold whatever the maps made of the
padding (they held zeros behind the compaction). Nothing downstream may
read them: every consumer below gives what numpy gives on the valid
rows alone, also across a checkpoint and a restore.
"""

import numpy as np
import pytest

import jax

from thrill_tpu.api import Context, FieldReduce, Run, Zip
from thrill_tpu.common.config import Config
from thrill_tpu.parallel.mesh import MeshExec

N = 37              # no multiple of a mesh width: every shard is padded
ROWS = 11
DATA = (np.arange(N, dtype=np.int64) * 2654435761) % 1000
WANT = DATA * 3 + 7          # the padding's zeros become sevens


def _affine(x):
    return x * 3 + 7


def _affine_field(t):
    return {"k": t["k"], "v": t["v"] * 3.0 + 7.0}


def _key(x):
    return x


def _mod_rows(x):
    return x % ROWS


def _field_k(t):
    return t["k"]


def _pair(a, b):
    return a + b


_SUM = FieldReduce("sum")
_FIRST_SUM = FieldReduce({"k": "first", "v": "sum"})

_CTX = {}


@pytest.fixture(scope="module")
def ctx_of():
    def get(W):
        if W not in _CTX:
            _CTX[W] = Context(MeshExec(num_workers=W))
        return _CTX[W]
    yield get
    for ctx in _CTX.values():
        ctx.close()
    _CTX.clear()


def _source_map(ctx):
    return ctx.Distribute(DATA).Map(_affine)


def _sort_map(ctx):
    return ctx.Distribute(DATA).Sort(_key).Map(_affine)


CHAINS = {"source_map": (_source_map, WANT),
          "sort_map": (_sort_map, np.sort(DATA) * 3 + 7)}


def _rows(shards):
    leaf = np.asarray(jax.tree.leaves(shards.tree)[0])
    return leaf, shards.counts


@pytest.mark.parametrize("W", [1, 2, 4])
@pytest.mark.parametrize("chain", sorted(CHAINS))
def test_valid_rows_are_a_prefix_and_counts_the_sources(ctx_of, chain, W):
    make, want = CHAINS[chain]
    ctx = ctx_of(W)
    before = ctx.overall_stats()["fused_dispatches"]
    leaf, counts = _rows(make(ctx).Cache()._link().pull(True))
    assert ctx.overall_stats()["fused_dispatches"] > before
    assert counts.sum() == N
    got = np.concatenate([leaf[w, :c] for w, c in enumerate(counts)])
    np.testing.assert_array_equal(got, want)
    # the chain was not compacted: past the counts stands what the map
    # made of the padding, and the tests below show nobody reads it
    assert leaf.shape[1] > counts.min()
    if chain == "source_map":
        for w, c in enumerate(counts):
            np.testing.assert_array_equal(leaf[w, c:], 7)


def _sum(d):
    return int(d.Sum())


def _size(d):
    return int(d.Size())


def _gather(d):
    return np.asarray(d.AllGatherArrays()).tolist()


def _sorted(d):
    return np.asarray(d.Sort(_key).AllGatherArrays()).tolist()


def _reduced(d):
    out = d.ReduceToIndex(_mod_rows, _SUM, ROWS)
    return np.asarray(out.AllGatherArrays()).tolist()


def _zipped(d):
    return np.asarray(
        Zip(d.Keep(), d, zip_fn=_pair).AllGatherArrays()).tolist()


def _prefix(d):
    return np.asarray(d.PrefixSum().AllGatherArrays()).tolist()


def _np_reduced(want):
    out = np.zeros(ROWS, np.int64)
    np.add.at(out, want % ROWS, want)
    return out.tolist()


CONSUMERS = {
    "sum": (_sum, lambda w: int(w.sum())),
    "size": (_size, lambda w: N),
    "gather": (_gather, lambda w: w.tolist()),
    "sort": (_sorted, lambda w: np.sort(w).tolist()),
    "reduce_to_index": (_reduced, _np_reduced),
    "zip": (_zipped, lambda w: (w + w).tolist()),
    "prefix_sum": (_prefix, lambda w: np.cumsum(w).tolist()),
}


@pytest.mark.parametrize("W", [1, 4])
@pytest.mark.parametrize("consumer", sorted(CONSUMERS))
@pytest.mark.parametrize("chain", sorted(CHAINS))
def test_no_consumer_reads_the_rows_past_the_count(ctx_of, chain,
                                                   consumer, W):
    make, want = CHAINS[chain]
    run, ref = CONSUMERS[consumer]
    assert run(make(ctx_of(W)).Keep()) == ref(want)


@pytest.mark.parametrize("W", [1, 2, 4])
def test_a_map_behind_reduce_to_index_keeps_the_dense_rows(ctx_of, W):
    """PageRank's chain: the dense rows of ReduceToIndex are a prefix,
    and the dampening ``Map`` behind them is not compacted."""
    ctx = ctx_of(W)
    keys = DATA % ROWS
    vals = DATA.astype(np.float64) / 8.0
    d = ctx.Distribute({"k": keys, "v": vals}).ReduceToIndex(
        _field_k, _FIRST_SUM, ROWS,
        neutral={"k": 0, "v": 0.0}).Map(_affine_field).Cache().Keep(3)
    sums = np.zeros(ROWS)
    np.add.at(sums, keys, vals)
    assert d._link().pull(True).counts.sum() == ROWS
    got = d.AllGatherArrays()
    np.testing.assert_allclose(np.asarray(got["v"]), sums * 3.0 + 7.0,
                               rtol=1e-12, atol=0)
    assert float(d.Map(_value).Sum()) == pytest.approx(
        float((sums * 3.0 + 7.0).sum()), rel=1e-12)


def _value(t):
    return t["v"]


@pytest.mark.parametrize("W", [1, 4])
@pytest.mark.parametrize("chain", sorted(CHAINS))
def test_such_shards_survive_a_checkpoint_and_a_restore(tmp_path,
                                                        monkeypatch, chain,
                                                        W):
    for var in ("THRILL_TPU_CKPT_DIR", "THRILL_TPU_RESUME",
                "THRILL_TPU_CKPT_AUTO"):
        monkeypatch.delenv(var, raising=False)
    make, want = CHAINS[chain]

    def job(ctx):
        d = make(ctx).Checkpoint().Keep(2)
        return (np.asarray(d.AllGatherArrays()).tolist(), int(d.Sum()),
                ctx.overall_stats())

    def cfg():
        return Config(ckpt_dir=str(tmp_path / "ckpt"), num_workers=W)

    rows, total, _ = Run(job, cfg())
    assert rows == want.tolist() and total == int(want.sum())
    rows2, total2, stats2 = Run(job, cfg(), resume=True)
    assert rows2 == rows and total2 == total
    assert stats2["resume_skipped_ops"] >= 1
