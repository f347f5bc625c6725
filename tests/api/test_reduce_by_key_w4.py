"""WordCount's ReduceByKey across four workers against a plain numpy
reference, on a virtual mesh of four CPU devices: packed 16-byte words,
``FieldReduce({"w": "first", "c": "sum"})``, duplicate detection forced
off, forced on and left to the cost model. Zipf words sit on every
worker, so the registers keep about nothing local; words partly unique
to one worker are kept where they are. Every word once, every count
exact, either way."""

import jax
import numpy as np
import pytest

from thrill_tpu.api import Context, FieldReduce
from thrill_tpu.common.partition import dense_range_bounds
from thrill_tpu.parallel.mesh import MeshExec

W = 4
N = 8192
COUNT = FieldReduce({"w": "first", "c": "sum"})


@pytest.fixture(autouse=True)
def device_programs(monkeypatch):
    """What the chip runs (chipbench/run.py --rehearse sets the same)."""
    monkeypatch.setenv("THRILL_TPU_HOST_RADIX", "0")
    monkeypatch.setenv("THRILL_TPU_SORT_U32", "1")
    monkeypatch.setenv("THRILL_TPU_PACK_MOVE", "1")


def _word_key(t):
    return t["w"]


def _vocab(rng, size, first):
    """``size`` distinct zero-padded words of 4..16 letters; the first
    letter is ``first``, the next four spell the index in base 26."""
    v = rng.integers(ord("a"), ord("z") + 1, (size, 16), dtype=np.uint8)
    idx = np.arange(size)
    for j in range(4):
        v[:, 1 + j] = ord("a") + (idx // 26 ** j) % 26
    v[:, 0] = first
    lens = rng.integers(5, 17, size)
    v[np.arange(16)[None, :] >= lens[:, None]] = 0
    return v


def _zipf(rng, vocab, n):
    p = 1.0 / np.arange(1, len(vocab) + 1) ** 1.1
    return vocab[rng.choice(len(vocab), n, p=p / p.sum())]


def _words(how, seed):
    rng = np.random.default_rng(seed)
    shared = _vocab(rng, 512, ord("z"))
    if how == "zipf":
        return _zipf(rng, shared, N)
    # each worker's block: half Zipf words of the shared vocabulary,
    # half words of its own that no other worker holds
    b = dense_range_bounds(N, W)
    blocks = []
    for w in range(W):
        n = int(b[w + 1] - b[w])
        own = _vocab(rng, 300, ord("a") + w)
        block = np.concatenate([_zipf(rng, shared, n - n // 2),
                                own[rng.integers(0, 300, n // 2)]])
        blocks.append(block[rng.permutation(n)])
    return np.concatenate(blocks)


def _reference(words):
    """Sort the packed words, count the runs."""
    rows, counts = np.unique(words, axis=0, return_counts=True)
    return {bytes(w): int(c) for w, c in zip(rows, counts)}


@pytest.mark.parametrize("how", ["zipf", "partly_unique"])
@pytest.mark.parametrize("dup", ["0", "1", None])
def test_word_counts_over_four_workers_equal_the_reference(
        dup, how, monkeypatch):
    if dup is None:
        monkeypatch.delenv("THRILL_TPU_DUP_DETECT", raising=False)
    else:
        monkeypatch.setenv("THRILL_TPU_DUP_DETECT", dup)
    words = _words(how, 4100 + len(how))
    ctx = Context(MeshExec(devices=jax.devices("cpu")[:W]))
    try:
        s0 = ctx.overall_stats()
        got = ctx.Distribute({"w": words,
                              "c": np.ones(N, np.int64)}) \
            .ReduceByKey(_word_key, COUNT).AllGatherArrays()
        s1 = ctx.overall_stats()
        decided = [r for r in ctx.decisions.snapshot()
                   if r.get("kind") == "prune"]
    finally:
        ctx.close()
    w, c = np.asarray(got["w"]), np.asarray(got["c"])
    want = _reference(words)
    # every word once, every count exact
    assert len(w) == len(want)
    assert {bytes(x): int(y) for x, y in zip(w, c)} == want
    on = s1["dup_detect_exchanges"] - s0["dup_detect_exchanges"]
    (verdict,) = decided
    assert verdict["chosen"] == f"dup:{'on' if on else 'off'}"
    if dup is None:
        # the cost model's: 8,192 rows of 24 bytes are too few to pay
        # for 2 x 65,536 register bytes (2^24 rows on the chip do)
        assert verdict["reason"] == "cost model" and not on
    else:
        assert on == int(dup)
    local = s1["xchg_rows_local"] - s0["xchg_rows_local"]
    rows = s1["xchg_rows_in"] - s0["xchg_rows_in"]
    share = local / rows
    if on and how == "partly_unique":
        # the own words (about 300 of some 520 runs a worker) stay home
        assert share > 0.6
    else:
        # a hash partition keeps about a quarter where it is
        assert 0.15 < share < 0.35
