"""Skew-proof exchange + sticky capacities.

Reference analogs: 1-factor round scheduling (thrill/net/group.hpp:
90-107) and MixStream's skew tolerance (data/mix_stream.hpp:126).
"""

import numpy as np
import pytest

import jax

from thrill_tpu.api import Context
from thrill_tpu.parallel.mesh import MeshExec


def _ctx(W, monkeypatch=None, mode=None):
    if monkeypatch is not None and mode is not None:
        monkeypatch.setenv("THRILL_TPU_EXCHANGE", mode)
    return Context(MeshExec(devices=jax.devices("cpu")[:W]))


def _key(t):
    return t[0]


def _count(k, items):
    return (k, len(list(items)))


def _skewed_job(ctx, n=40_000):
    """GroupByKey with ONE hot (source, destination) pair: a single
    worker holds ~n items of one key, everyone else a trickle. No
    pre-reduction collapses groups (unlike ReduceByKey), so the hash
    exchange really ships the hot run — a genuinely skewed pair."""
    W = ctx.num_workers
    rng = np.random.default_rng(0)
    per_worker = []
    for w in range(W):
        if w == min(3, W - 1):
            vals = np.full(n, 7, dtype=np.int64)          # the hot run
        else:
            vals = rng.integers(8, 1000, 64).astype(np.int64)
        per_worker.append(vals)
    d = ctx.ConcatToDIA(per_worker, storage="device").Map(lambda x: (x, 1))
    out = d.GroupByKey(_key, _count)
    got = {int(k): int(c) for k, c in out.AllGather()}
    want = {}
    for vals in per_worker:
        for v in vals.tolist():
            want[v] = want.get(v, 0) + 1
    assert got == want


# tier-1 budget: W=2 keeps end-to-end onefactor in-tier, the wider
# worker sweep rides the unfiltered run
@pytest.mark.parametrize("W", [
    2,
    pytest.param(5, marks=pytest.mark.slow),
    pytest.param(8, marks=pytest.mark.slow)])
def test_onefactor_exchange_correct(W, monkeypatch):
    monkeypatch.setenv("THRILL_TPU_EXCHANGE", "onefactor")
    ctx = _ctx(W)
    _skewed_job(ctx, n=5000)
    # uniform data too
    vals = np.arange(3000, dtype=np.int64)
    srt = ctx.Distribute(vals[::-1].copy()).Sort()
    assert [int(x) for x in srt.AllGather()] == vals.tolist()
    ctx.close()


def test_skew_padding_proportional_to_data(monkeypatch):
    """Under ~100:1 skew the auto plan (1-factor rounds) must allocate
    far fewer padded rows than the uniform all_to_all plan."""
    W = 8
    n = 40_000
    monkeypatch.setenv("THRILL_TPU_EXCHANGE", "dense")
    ctx = _ctx(W)
    _skewed_job(ctx, n=n)
    auto_rows = ctx.mesh_exec.stats_padded_rows
    ctx.close()

    monkeypatch.setenv("THRILL_TPU_EXCHANGE", "onefactor")
    ctx = _ctx(W)
    _skewed_job(ctx, n=n)
    onefactor_rows = ctx.mesh_exec.stats_padded_rows
    ctx.close()

    # the exchange actually ran on the device path (not vacuous)
    assert auto_rows > 0 and onefactor_rows > 0
    # dense mode auto-detects the skew and switches to 1-factor rounds
    assert auto_rows == onefactor_rows
    # padded rows track the data (one hot pair), far below the uniform
    # plan's W * round_up_pow2(hot_pair) = W * 65536
    uniform_rows = W * (1 << 16)
    assert onefactor_rows < uniform_rows / 4


def test_dense_vs_onefactor_padding_ratio(monkeypatch):
    """Directly compare: force uniform padding via a low-skew guard
    bypass (small data keeps _skewed False) vs the explicit 1-factor
    mode on the same skewed matrix."""
    from thrill_tpu.data import exchange as ex

    S = np.zeros((8, 8), dtype=np.int64)
    S[:, 0] = 100          # everyone sends a bit to worker 0
    S[3, 0] = 40_000       # one hot pair
    ctx = _ctx(8)
    mex = ctx.mesh_exec
    # measured cost model: the hot pair's padding waste clears the
    # per-round launch overhead -> 1-factor
    assert ex._skewed(S, 16, mex)
    # small balanced neighbor shift: the padding saved is below the
    # measured per-round launch cost -> stays on the single all_to_all
    Sb = np.zeros((8, 8), dtype=np.int64)
    for w in range(8):
        Sb[w, (w + 1) % 8] = 100
    assert not ex._skewed(Sb, 16, mex)
    # ...but a LARGE sparse matrix flips: dense would pad W*W cells to
    # the shift size, and that waste dwarfs 7 launches (this is the
    # cost model improving on the old max-vs-mean heuristic, which
    # kept any balanced matrix dense no matter how much it padded)
    assert ex._skewed(Sb * 1000, 16, mex)
    ctx.close()
    # uniform plan rows: W * round_up_pow2(max) = 8 * 65536
    uniform_rows = 8 * (1 << 16)
    onefactor_rows = sum(
        max(int(S[np.arange(8), (np.arange(8) + r) % 8].max()), 1)
        for r in range(1, 8))
    assert onefactor_rows * 8 < uniform_rows


def test_multislice_tier_pure_rounds(monkeypatch):
    """With THRILL_TPU_SLICES=2 on W=8, the 1-factor schedule must be
    tier-pure (each round fully intra- or fully cross-slice), cover
    every ordered pair once, and group the DCN rounds last."""
    from thrill_tpu.data import exchange as ex

    monkeypatch.setenv("THRILL_TPU_SLICES", "2")
    mex = MeshExec(devices=jax.devices("cpu")[:8])
    assert mex.num_slices == 2
    rounds = ex.one_factor_rounds(mex)
    assert len(rounds) == 7
    sid = mex.slice_id
    seen = set()
    tiers = []
    for to in rounds:
        pair_tiers = {bool(sid[w] != sid[to[w]]) for w in range(8)}
        assert len(pair_tiers) == 1, "mixed-tier round"
        tiers.append(pair_tiers.pop())
        assert sorted(to.tolist()) == list(range(8))   # a permutation
        for w in range(8):
            assert to[w] != w
            seen.add((w, int(to[w])))
    assert len(seen) == 8 * 7                          # full coverage
    assert tiers == sorted(tiers), "ICI rounds must precede DCN rounds"


def test_multislice_exchange_correct_and_accounted(monkeypatch):
    """The sliced 1-factor exchange produces identical results and the
    ICI/DCN byte split sums to the total moved bytes."""
    monkeypatch.setenv("THRILL_TPU_SLICES", "2")
    monkeypatch.setenv("THRILL_TPU_EXCHANGE", "onefactor")
    ctx = _ctx(8)
    assert ctx.mesh_exec.num_slices == 2
    _skewed_job(ctx, n=5000)
    vals = np.arange(3000, dtype=np.int64)
    srt = ctx.Distribute(vals[::-1].copy()).Sort()
    assert [int(x) for x in srt.AllGather()] == vals.tolist()
    mex = ctx.mesh_exec
    assert mex.stats_bytes_dcn > 0 and mex.stats_bytes_ici > 0
    assert mex.stats_bytes_ici + mex.stats_bytes_dcn == \
        mex.stats_bytes_moved
    ctx.close()


def test_sticky_capacities_stop_recompile_churn(monkeypatch):
    """Across loop iterations with wiggling counts, executables and
    capacities must reach a fixed point (no unbounded cache growth)."""
    ctx = _ctx(5)
    mex = ctx.mesh_exec
    rng = np.random.default_rng(1)

    def map_fn(x):          # defined once: loop bodies must not mint
        return (x, 1)       # fresh lambdas or nothing can ever cache

    def red_fn(a, b):
        return a + b

    sizes = []
    for it in range(6):
        # sizes wiggle around a power-of-two boundary
        n = 4000 + int(rng.integers(-300, 300))
        vals = rng.integers(0, 50, n).astype(np.int64)
        out = ctx.Distribute(vals).Map(map_fn).ReducePair(red_fn)
        assert out.Size() == len(set(vals.tolist()))
        sizes.append(len(mex._cache))
    # after warmup the executable cache stops growing: capacities are
    # sticky, so count wiggles reuse the same compiled programs
    assert sizes[-1] == sizes[2], sizes
    ctx.close()


@pytest.mark.parametrize("W", [
    2,
    # W sweep tails ride the unfiltered sweep only (tier-1 wall-clock
    # budget; W=2 is the in-tier representative — PR-9 precedent)
    pytest.param(5, marks=pytest.mark.slow),
    pytest.param(8, marks=pytest.mark.slow)])
def test_reduce_shuffle_matches_dictionary(W):
    rng = np.random.default_rng(W)
    vals = rng.integers(0, 40, 6000).astype(np.int64)
    want = {}
    for v in vals.tolist():
        want[v % 17] = want.get(v % 17, 0) + v
    ctx = _ctx(W)
    out = ctx.Distribute(vals).Map(lambda x: (x % 17, x)).ReducePair(
        lambda a, b: a + b)
    assert dict((int(k), int(v)) for k, v in out.AllGather()) == want
    ctx.close()


def test_reduce_shuffle_cap_stays_linear():
    """The post phase's capacity stays linear in the rows actually
    received: ~1000 distinct keys over W=8 are ~125 a worker, and a
    capacity fed back through round_up_pow2 once per source would
    exceed 2^15."""
    ctx = _ctx(8)
    vals = np.arange(20000, dtype=np.int64)
    out = ctx.Distribute(vals).Map(lambda x: (x % 1000, 1)).ReducePair(
        lambda a, b: a + b)
    sh = out.node.materialize(consume=False)
    assert sh.cap <= 8192, f"post-phase cap blew up: {sh.cap}"
    got = dict((int(k), int(v)) for k, v in out.AllGather())
    assert len(got) == 1000 and all(v == 20 for v in got.values())
    ctx.close()


def test_reduce_shuffle_on_sliced_mesh(monkeypatch):
    monkeypatch.setenv("THRILL_TPU_SLICES", "2")
    ctx = _ctx(8)
    assert ctx.mesh_exec.num_slices == 2
    vals = np.arange(5000, dtype=np.int64)
    out = ctx.Distribute(vals).Map(lambda x: (x % 9, 1)).ReducePair(
        lambda a, b: a + b)
    got = dict((int(k), int(v)) for k, v in out.AllGather())
    assert sum(got.values()) == 5000 and len(got) == 9
    ctx.close()
