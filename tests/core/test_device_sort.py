"""Bitonic vs XLA sort engine equivalence."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from thrill_tpu.core import device_sort


@pytest.mark.parametrize("n", [1, 2, 64, 1024])
@pytest.mark.parametrize("nwords", [1, 2, 3])
def test_bitonic_matches_xla(monkeypatch, n, nwords):
    rng = np.random.default_rng(n * 10 + nwords)
    # include duplicates to exercise the stability tiebreak
    words = [jnp.asarray(rng.integers(0, max(n // 4, 2), n).astype(np.uint64))
             for _ in range(nwords)]

    monkeypatch.setenv("THRILL_TPU_SORT_IMPL", "xla")
    perm_xla = np.asarray(jax.jit(device_sort.argsort_words)(words))
    monkeypatch.setenv("THRILL_TPU_SORT_IMPL", "bitonic")
    perm_bit = np.asarray(jax.jit(device_sort._bitonic_argsort)(words))
    # with the iota tiebreak the stable permutation is unique
    assert np.array_equal(perm_xla, perm_bit)


def test_bitonic_large_random():
    rng = np.random.default_rng(0)
    n = 1 << 14
    w = jnp.asarray(rng.integers(0, 1 << 60, n).astype(np.uint64))
    perm = np.asarray(jax.jit(device_sort._bitonic_argsort)([w]))
    sorted_w = np.asarray(w)[perm]
    assert np.all(sorted_w[1:] >= sorted_w[:-1])
    assert len(np.unique(perm)) == n


def test_pipeline_on_bitonic_engine(monkeypatch):
    monkeypatch.setenv("THRILL_TPU_SORT_IMPL", "bitonic")
    from thrill_tpu.api import RunLocalMock

    def job(ctx):
        rng = np.random.default_rng(1)
        vals = rng.integers(0, 500, 3000).astype(np.int64)
        assert [int(x) for x in ctx.Distribute(vals).Sort().AllGather()] \
            == sorted(vals.tolist())
        hist = ctx.Distribute(vals).Map(lambda x: (x % 7, 1)) \
            .ReducePair(lambda a, b: a + b)
        got = dict((int(k), int(v)) for k, v in hist.AllGather())
        want = {}
        for v in vals.tolist():
            want[v % 7] = want.get(v % 7, 0) + 1
        assert got == want
    RunLocalMock(job, 4)


@pytest.mark.parametrize("n", [
    1, 2, 64, 1024,
    # the 5000-row tail (multi-chunk path at every word count) rides
    # the unfiltered sweep only; 1024 is the in-tier representative
    pytest.param(5000, marks=pytest.mark.slow)])
@pytest.mark.parametrize("nwords", [1, 2, 3])
def test_chunked_matches_xla(monkeypatch, n, nwords):
    rng = np.random.default_rng(n * 31 + nwords)
    words = [jnp.asarray(rng.integers(0, max(n // 4, 2), n).astype(np.uint64))
             for _ in range(nwords)]

    monkeypatch.setenv("THRILL_TPU_SORT_IMPL", "xla")
    perm_xla = np.asarray(jax.jit(device_sort.argsort_words)(words))
    # small chunk forces several merge-tree levels even at modest n
    perm_ch = np.asarray(jax.jit(
        lambda ws: device_sort._chunked_argsort(ws, chunk=256))(words))
    # with the iota tiebreak the stable permutation is unique
    assert np.array_equal(perm_xla, perm_ch)


@pytest.mark.slow  # tier-1 budget: chunked engine covered in-tier by test_chunked_matches_xla
def test_chunked_all_ones_and_presorted():
    """Padding sentinel (max words) must not displace real max-valued
    keys, and already-sorted input must round-trip."""
    maxu = np.uint64(0xFFFFFFFFFFFFFFFF)
    w = jnp.asarray(np.array([maxu, 3, maxu, 1, 2], dtype=np.uint64))
    perm = np.asarray(device_sort._chunked_argsort([w], chunk=2))
    assert perm.tolist() == [3, 4, 1, 0, 2]  # stable among the two maxu
    srt = jnp.asarray(np.arange(1000, dtype=np.uint64))
    perm2 = np.asarray(device_sort._chunked_argsort([srt], chunk=64))
    assert perm2.tolist() == list(range(1000))


def test_pipeline_on_chunked_engine(monkeypatch):
    monkeypatch.setenv("THRILL_TPU_SORT_IMPL", "chunked")
    from thrill_tpu.api import RunLocalMock

    def job(ctx):
        rng = np.random.default_rng(2)
        vals = rng.integers(0, 500, 3000).astype(np.int64)
        assert [int(x) for x in ctx.Distribute(vals).Sort().AllGather()] \
            == sorted(vals.tolist())
    RunLocalMock(job, 4)


@pytest.mark.parametrize("impl", ["xla", "chunked", "bitonic"])
@pytest.mark.parametrize("n", [1, 5, 1000])
def test_u32_split_matches_u64(monkeypatch, impl, n):
    """The uint32 word-split path (TPU: no native 64-bit integer ALU)
    must produce the identical stable permutation."""
    rng = np.random.default_rng(n * 7 + len(impl))
    words = [jnp.asarray((rng.integers(0, 1 << 62, n, dtype=np.int64)
                          ).astype(np.uint64)),
             jnp.asarray(rng.integers(0, 3, n).astype(np.uint64))]
    monkeypatch.setenv("THRILL_TPU_SORT_IMPL", impl)
    monkeypatch.setenv("THRILL_TPU_SORT_U32", "0")
    perm64 = np.asarray(device_sort.argsort_words(words))
    monkeypatch.setenv("THRILL_TPU_SORT_U32", "1")
    perm32 = np.asarray(device_sort.argsort_words(words))
    assert np.array_equal(perm64, perm32)


def test_merge_sorted_runs():
    """C sorted runs in, one sorted sequence out (no base-case sort)."""
    rng = np.random.default_rng(9)
    C, L = 4, 256
    key = np.sort(rng.integers(0, 1000, (C, L)).astype(np.uint64), axis=1)
    iota = np.arange(C * L, dtype=np.uint64).reshape(C, L)
    out = device_sort.merge_sorted_runs(
        [jnp.asarray(key), jnp.asarray(iota)])
    merged_key = np.asarray(out[0]).reshape(-1)
    merged_iota = np.asarray(out[1]).reshape(-1)
    order = np.lexsort((iota.reshape(-1), key.reshape(-1)))
    assert np.array_equal(merged_key, key.reshape(-1)[order])
    assert np.array_equal(merged_iota, iota.reshape(-1)[order])


@pytest.mark.parametrize("n", [1, 64, 1024, 5000])
@pytest.mark.parametrize("nwords", [1, 2])
def test_radix_matches_xla(monkeypatch, n, nwords):
    """The radix engine (lax.scan partition fallback on CPU) produces
    the identical stable permutation — the unique one, thanks to the
    iota tiebreak — as the xla engine."""
    rng = np.random.default_rng(n * 13 + nwords)
    words = [jnp.asarray(rng.integers(0, max(n // 4, 2), n)
                         .astype(np.uint64)) for _ in range(nwords)]
    monkeypatch.setenv("THRILL_TPU_SORT_IMPL", "xla")
    perm_xla = np.asarray(jax.jit(device_sort.argsort_words)(words))
    monkeypatch.setenv("THRILL_TPU_SORT_IMPL", "radix")
    perm_rad = np.asarray(jax.jit(device_sort.argsort_words)(words))
    assert np.array_equal(perm_xla, perm_rad)


_CLIFF = device_sort.XLA_SORT_MAX_N

# (backend, n, uint64 key words, radix_ok, THRILL_TPU_SORT_IMPL) ->
# (engine, a fragment of the reason; None where the engine is pinned),
# written from the parent of the PR that made the choice one function
# (sort_engine_policy + _impl + argsort_words' own read of the pin)
_PARENT_TABLE = [
    ("cpu", 1 << 20, 1, True, None, "xla", "healthy"),
    ("cpu", 1 << 24, 40, True, None, "xla", "healthy"),
    ("tpu", _CLIFF, 1, True, None, "xla", "healthy"),
    ("tpu", _CLIFF + 1, 1, False, None, "chunked", "radix ineligible"),
    ("tpu", 1 << 22, 1, False, None, "chunked", "radix ineligible"),
    ("tpu", 1 << 22, 1, True, None, "radix", "radix eligible"),
    # many wide words: enough passes to price radix past chunked
    ("tpu", 1 << 22, 40, True, None, "chunked", "radix eligible"),
    ("cpu", 1 << 22, 1, True, "xla", "xla", None),
    ("cpu", 1 << 22, 1, True, "bitonic", "bitonic", None),
    ("cpu", 1 << 22, 1, False, "chunked", "chunked", None),
    ("tpu", 1 << 10, 1, False, "radix", "radix", None),
]


@pytest.mark.parametrize(
    "backend,n,nwords,radix_ok,pin,engine,why", _PARENT_TABLE)
def test_choose_engine_matches_parent_table(
        monkeypatch, backend, n, nwords, radix_ok, pin, engine, why):
    """The one engine choice is the parent's for every input it looks
    at, and writes its ``sort_engine`` record only where it chose."""
    from types import SimpleNamespace
    from thrill_tpu.common.decisions import DecisionLedger
    from thrill_tpu.parallel import mesh
    monkeypatch.setattr(device_sort.jax, "default_backend",
                        lambda: backend)
    if pin is None:
        monkeypatch.delenv("THRILL_TPU_SORT_IMPL", raising=False)
    else:
        monkeypatch.setenv("THRILL_TPU_SORT_IMPL", pin)
    led = DecisionLedger(enabled=True)
    monkeypatch.setattr(
        mesh, "current_mex", lambda: SimpleNamespace(decisions=led))
    words = [jax.ShapeDtypeStruct((n,), jnp.uint64)] * nwords
    assert device_sort.choose_engine(n, words, radix_ok=radix_ok) \
        == engine
    if pin is not None:
        assert not led.snapshot()  # a pinned engine is no choice
        return
    (rec,) = led.snapshot()
    assert rec["kind"] == "sort_engine" and rec["chosen"] == engine
    assert rec["site"] == f"sort:n{n}:w{nwords}" and why in rec["reason"]
    assert rec["inputs"]["n"] == n
    assert rec["inputs"]["total_bits"] == 64 * nwords
    costs = dict(rec.get("rejected", ()), **{engine: rec["predicted"]})
    assert set(costs) == ({"xla"} if engine == "xla" else
                          {"chunked", "radix"} if radix_ok
                          else {"chunked"})
    assert min(costs, key=costs.get) == engine
    # the merge of presorted runs asks without a record of its own
    assert device_sort.choose_engine(n, words, radix_ok=radix_ok,
                                     record=False) == engine
    assert len(led.snapshot()) == 1


def test_sort_and_run_merge_ask_the_one_choice(monkeypatch):
    """``argsort_words`` and Sort's fused exchange-merge both take
    their engine from ``choose_engine``."""
    from thrill_tpu.api import Context
    from thrill_tpu.parallel.mesh import MeshExec
    monkeypatch.setenv("THRILL_TPU_HOST_RADIX", "0")
    asked = []
    real = device_sort.choose_engine

    def spy(n, words, **kw):
        asked.append(kw.get("record", True))
        return real(n, words, **kw)

    monkeypatch.setattr(device_sort, "choose_engine", spy)
    ctx = Context(MeshExec(devices=jax.devices("cpu")[:2]))
    vals = np.random.default_rng(3).permutation(5000).astype(np.int64)
    assert [int(x) for x in ctx.Distribute(vals).Sort().AllGather()] \
        == list(range(5000))
    recorded = [d for d in ctx.decisions.snapshot()
                if d["kind"] == "sort_engine"]
    ctx.close()
    assert True in asked and False in asked, asked
    assert len(recorded) == asked.count(True)


@pytest.mark.parametrize("w", [
    4,
    pytest.param(1, marks=pytest.mark.slow),   # tier-1 budget: W=4
    pytest.param(2, marks=pytest.mark.slow)])  # exercises the sweep
def test_pipeline_on_radix_engine(w, monkeypatch):
    """Full Sort pipeline on the radix engine at W in {1, 2, 4}:
    bit-identical results vs the default engine (stable sorts share the
    unique permutation, so equality is exact, not just sorted-equal)."""
    from thrill_tpu.api import RunLocalMock

    def job(ctx):
        rng = np.random.default_rng(4)
        vals = rng.integers(0, 500, 3000).astype(np.int64)
        assert [int(x) for x in ctx.Distribute(vals).Sort().AllGather()] \
            == sorted(vals.tolist())
    monkeypatch.setenv("THRILL_TPU_SORT_IMPL", "radix")
    RunLocalMock(job, w)


def test_pipeline_u32_engine(monkeypatch):
    """Full Sort pipeline (incl. the fused run-merge exchange) on the
    u32 split path across worker counts incl. non-power-of-two."""
    monkeypatch.setenv("THRILL_TPU_SORT_U32", "1")
    from thrill_tpu.api import RunLocalMock

    def job(ctx):
        rng = np.random.default_rng(3)
        vals = rng.integers(0, 200, 5000).astype(np.int64)
        assert [int(x) for x in ctx.Distribute(vals).Sort().AllGather()] \
            == sorted(vals.tolist())
    for w in (1, 2, 5, 8):
        RunLocalMock(job, w)
