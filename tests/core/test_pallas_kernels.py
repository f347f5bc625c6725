"""Pallas kernel equivalence tests (interpret mode on CPU)."""

import numpy as np
import pytest

import jax.numpy as jnp

from thrill_tpu.core import pallas_kernels as pk


@pytest.mark.parametrize("n,bins", [(10, 4), (512, 8), (2000, 17),
                                    (4096, 256)])
def test_partition_histogram_matches_bincount(n, bins):
    rng = np.random.default_rng(n)
    dest = rng.integers(0, bins, n).astype(np.int32)
    got = np.asarray(pk.partition_histogram_pallas(
        jnp.asarray(dest), bins, interpret=True))
    want = np.bincount(dest, minlength=bins)
    assert np.array_equal(got, want)


def test_partition_histogram_ignores_sentinel():
    dest = np.array([0, 1, 1, 7, 7, 7, -1], dtype=np.int32)  # 7 = "W"
    got = np.asarray(pk.partition_histogram_pallas(
        jnp.asarray(dest), 4, interpret=True))
    assert got.tolist() == [1, 2, 0, 0]


def _ids(order, n, bins):
    """``n`` ids over ``bins`` bins in ``order``, with the sentinels
    ``bins`` (the exchange's W) and -1 among them where ``n`` allows."""
    rng = np.random.default_rng(n + bins)
    if order == "one_bin":
        d = np.full(n, bins // 2, np.int32)
    else:
        d = rng.integers(0, bins, n).astype(np.int32)
    d[n // 3::97] = bins
    d[n // 2::89] = -1
    return np.sort(d) if order == "sorted" else d


@pytest.mark.parametrize("bins", [1, 4, 5, pk.HIST_COMPARE_MAX_BINS,
                                  pk.HIST_COMPARE_MAX_BINS + 1])
@pytest.mark.parametrize("order,n", [("random", 3000), ("sorted", 3000),
                                     ("one_bin", 1000), ("empty", 0)])
def test_histogram_xla_path_matches_bincount(order, n, bins):
    """The XLA path, by comparison up to ``HIST_COMPARE_MAX_BINS`` and by
    scatter-add above it: ``int32[bins]``, sentinels outside
    ``[0, bins)`` not counted, any input order."""
    d = _ids(order, n, bins)
    assert pk.histogram_path(n, bins) == (
        "compare" if bins <= pk.HIST_COMPARE_MAX_BINS else "scatter")
    got = pk.partition_histogram(jnp.asarray(d), bins)
    assert got.dtype == jnp.int32 and got.shape == (bins,)
    want = np.bincount(d[(d >= 0) & (d < bins)], minlength=bins)
    np.testing.assert_array_equal(np.asarray(got), want)


@pytest.mark.parametrize("bins,scatter", [
    (4, False), (pk.HIST_COMPARE_MAX_BINS + 1, True)])
def test_send_histogram_lowers_without_scatter(bins, scatter):
    """``send_counts``' histogram at W = 4 lowers to a compare and a
    reduce, with no scatter; past the constant the scatter is back, so
    the text is where a scatter would show."""
    import jax
    text = jax.jit(lambda d: pk.partition_histogram(d, bins)).lower(
        jax.ShapeDtypeStruct((1 << 16,), jnp.int32)).as_text()
    assert ("scatter" in text) == scatter


@pytest.mark.parametrize("n,segs", [(100, 5), (1000, 300)])
def test_segment_sum_matches_numpy(n, segs):
    rng = np.random.default_rng(n)
    ids = rng.integers(0, segs, n).astype(np.int32)
    vals = rng.normal(size=n).astype(np.float32)
    got = np.asarray(pk.segment_sum_pallas(
        jnp.asarray(ids), jnp.asarray(vals), segs, interpret=True))
    want = np.zeros(segs, np.float32)
    np.add.at(want, ids, vals)
    assert np.allclose(got, want, atol=1e-4)


def test_dispatch_fallback_off_tpu():
    # on CPU the dispatcher must use the jnp fallback and still be right
    dest = jnp.asarray(np.array([0, 2, 2, 5], dtype=np.int32))
    got = np.asarray(pk.partition_histogram(dest, 6))
    assert got.tolist() == [1, 0, 2, 0, 0, 1]


@pytest.mark.parametrize("n,M", [(10, 4), (512, 64), (3000, 500),
                                 (4096, 1024)])
def test_presence_fill_matches_scatter(n, M):
    rng = np.random.default_rng(n + M)
    h = rng.integers(0, M, n).astype(np.int32)
    valid = (rng.random(n) < 0.7)
    got = np.asarray(pk.presence_fill_pallas(
        jnp.asarray(h), jnp.asarray(valid), M, interpret=True))
    want = np.zeros(M, np.uint8)
    want[h[valid]] = 1
    assert got.dtype == np.uint8
    assert np.array_equal(got, want)


def test_presence_fill_ignores_sentinel_and_invalid():
    # -1 padding sentinel, >= M overflow values, and valid=0 rows are
    # all ignored by BOTH engines
    h = np.array([0, -1, 3, 99, 3, 2], dtype=np.int32)
    valid = np.array([1, 1, 1, 1, 0, 1], dtype=bool)
    a = np.asarray(pk.presence_fill_pallas(
        jnp.asarray(h), jnp.asarray(valid), 4, interpret=True))
    b = np.asarray(pk.presence_fill(jnp.asarray(h), jnp.asarray(valid), 4))
    assert a.tolist() == [1, 0, 1, 1]   # 0, 2, and the valid 3
    assert np.array_equal(a, b)


def test_presence_fill_empty_input():
    h = np.zeros(0, np.int32)
    valid = np.zeros(0, bool)
    a = np.asarray(pk.presence_fill_pallas(
        jnp.asarray(h), jnp.asarray(valid), 8, interpret=True))
    b = np.asarray(pk.presence_fill(jnp.asarray(h), jnp.asarray(valid), 8))
    assert a.tolist() == [0] * 8
    assert np.array_equal(a, b)


def test_segment_sum_empty_input():
    ids = jnp.zeros(0, jnp.int32)
    vals = jnp.zeros(0, jnp.float32)
    got = np.asarray(pk.segment_sum_pallas(ids, vals, 5, interpret=True))
    assert got.tolist() == [0.0] * 5


def test_histogram_empty_input():
    got = np.asarray(pk.partition_histogram_pallas(
        jnp.zeros(0, jnp.int32), 4, interpret=True))
    assert got.tolist() == [0] * 4


def test_refusal_gates_pinned():
    """Size gates the dispatchers refuse past: >2^24 rows (f32 one-hot
    accumulation would lose exactness), oversized register/segment
    columns (one-hot cost crosses over vs XLA scatter)."""
    assert pk.rows_ok(pk.MAX_ROWS - 1)
    assert not pk.rows_ok(pk.MAX_ROWS)
    assert pk.presence_fill_ok(pk.PRESFILL_MAX_REGS - 1, 100)
    assert not pk.presence_fill_ok(pk.PRESFILL_MAX_REGS + 1, 100)
    assert not pk.presence_fill_ok(10, pk.MAX_ROWS)
    assert pk.segment_sum_ok(pk.SEGSUM_MAX_SEGS - 1, 100)
    assert not pk.segment_sum_ok(pk.SEGSUM_MAX_SEGS + 1, 100)
    assert not pk.segment_sum_ok(10, pk.MAX_ROWS)


def test_pallas_knob_cached_at_mesh_construction(monkeypatch):
    """THRILL_TPU_PALLAS is captured ONCE when the mesh is built (the
    _env_exchange pattern): flipping os.environ afterwards must not
    change a live mesh's engine choice mid-run."""
    class _Mex:
        pass

    monkeypatch.setattr(pk.jax, "default_backend", lambda: "tpu")
    mex_off = _Mex()
    mex_off._env_pallas = None          # built with the var unset
    mex_on = _Mex()
    mex_on._env_pallas = "1"            # built with the var set
    monkeypatch.setenv("THRILL_TPU_PALLAS", "1")
    assert not pk.pallas_enabled(mex_off)
    assert pk.pallas_enabled(mex_on)
    monkeypatch.delenv("THRILL_TPU_PALLAS")
    assert pk.pallas_enabled(mex_on)    # cached value survives env loss
    # no mesh in scope: the live env read is the documented fallback
    monkeypatch.setenv("THRILL_TPU_PALLAS", "1")
    assert pk.pallas_enabled(_Mex())


def _pallas_calls(jaxpr):
    for e in jaxpr.eqns:
        if e.primitive.name == "pallas_call":
            yield e
        for v in e.params.values():
            inner = getattr(v, "jaxpr", v)
            if hasattr(inner, "eqns"):
                yield from _pallas_calls(inner)


@pytest.mark.parametrize("kernel", [
    "partition_histogram", "segment_sum", "presence_fill",
    "stable_partition_offsets"])
def test_block_index_maps_trace_to_i32_under_x64(kernel):
    """The package runs with x64 on, where a Python ``0`` in a BlockSpec
    index map traces to i64 beside the i32 grid index — Mosaic refuses
    that mix on the chip while interpret mode accepts it silently."""
    import jax
    from thrill_tpu.core import pallas_sort as ps

    assert jax.config.jax_enable_x64
    ids = jnp.zeros(600, jnp.int32)
    vals = jnp.zeros(600, jnp.float32)
    fn = {
        "partition_histogram":
            lambda: pk.partition_histogram_pallas(ids, 4, interpret=True),
        "segment_sum":
            lambda: pk.segment_sum_pallas(ids, vals, 4, interpret=True),
        "presence_fill":
            lambda: pk.presence_fill_pallas(ids, vals > 0, 4,
                                            interpret=True),
        "stable_partition_offsets":
            lambda: ps.stable_partition_offsets_pallas(ids, 4,
                                                       interpret=True),
    }[kernel]
    calls = list(_pallas_calls(jax.make_jaxpr(fn)().jaxpr))
    assert calls
    for eqn in calls:
        for bm in eqn.params["grid_mapping"].block_mappings:
            dts = [v.aval.dtype for v in bm.index_map_jaxpr.jaxpr.outvars]
            assert all(dt == np.int32 for dt in dts), dts
