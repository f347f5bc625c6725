"""What a job of prefix-doubling rounds needs of the operators: padded
windows (n windows for n items, built by slices, on one worker, on
four, and on the host path), a window function bound to an operand that
changes from round to round without a new program, an additive
PrefixSum that rides the stitched chain and leaves its rows where they
are, the named scopes ``window`` and ``prefix_sum``, the ``pulls``
counter, and the example's ``suffix_array`` as ONE pipeline of DIA
operators."""

import os
import re
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from thrill_tpu.api import Bind, Context
from thrill_tpu.parallel import mesh as mesh_mod
from thrill_tpu.parallel.mesh import MeshExec

_EXAMPLES = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "examples")
sys.path.insert(0, _EXAMPLES)
import suffix_sorting as ss  # noqa: E402


@pytest.fixture
def device_programs(monkeypatch):
    """The jitted programs a chip runs, not the CPU's native sort."""
    monkeypatch.setenv("THRILL_TPU_HOST_RADIX", "0")


def _ctx(workers):
    return Context(MeshExec(num_workers=workers))


# ---------------------------------------------------------- padded windows

def _sum_dev(w):
    return jnp.sum(w, axis=1)


def _weighted(w, scale):
    """A window function with an operand: slot t weighs scale ** t."""
    k = w.shape[1]
    return sum(w[:, t] * scale ** t for t in range(k))


def _padded_model(x, k, f):
    ext = np.concatenate([x, np.zeros(k - 1, x.dtype)])
    return [f(ext[j:j + k]) for j in range(len(x))]


@pytest.mark.parametrize("workers", [1, 4])
@pytest.mark.parametrize("k", [2, 4])
@pytest.mark.parametrize("n", [1, 3, 64, 1000])
def test_a_padded_window_starts_at_every_item(device_programs, workers, k, n):
    x = np.arange(1, n + 1, dtype=np.int64) * 3
    ctx = _ctx(workers)
    try:
        got = ctx.Distribute(x).Window(
            k, lambda i, w: sum(int(v) for v in w), device_fn=_sum_dev,
            pad=True).AllGather()
        plain = ctx.Distribute(x).Window(
            k, lambda i, w: sum(int(v) for v in w),
            device_fn=_sum_dev).AllGather()
    finally:
        ctx.close()
    want = _padded_model(x, k, lambda w: int(w.sum()))
    assert [int(v) for v in got] == want
    # the unpadded window is the same windows without the last k - 1
    assert [int(v) for v in plain] == want[:max(0, n - k + 1)]


def test_the_host_path_pads_with_the_items_zero(device_programs):
    """Host storage, tree items: the pad item is the item type's zero
    and the host function sees the window's start index."""
    items = [{"a": np.uint32(i + 1), "b": np.uint8(7)} for i in range(5)]
    seen = []

    def fn(i, w):
        seen.append(i)
        return (int(w[0]["a"]), int(w[1]["a"]), int(w[1]["b"]))

    ctx = _ctx(1)
    try:
        got = ctx.Distribute(items, storage="host").Window(
            2, fn, pad=True).AllGather()
    finally:
        ctx.close()
    assert got == [(1, 2, 7), (2, 3, 7), (3, 4, 7), (4, 5, 7), (5, 0, 0)]
    assert seen == [0, 1, 2, 3, 4]


def test_pad_is_the_sliding_windows(device_programs):
    ctx = _ctx(1)
    try:
        with pytest.raises(ValueError, match="pad=True is the sliding"):
            from thrill_tpu.api.ops import window
            window.Window(ctx.Distribute(np.arange(8)), 2, None,
                          device_fn=_sum_dev, disjoint=True, pad=True)
    finally:
        ctx.close()


@pytest.mark.parametrize("workers", [1, 4])
def test_a_bound_window_function_rebinds_without_a_new_program(
        device_programs, workers):
    x = np.arange(1, 513, dtype=np.int64)
    ctx = _ctx(workers)
    try:
        def run(scale):
            s = np.int64(scale)
            return ctx.Distribute(x).Window(
                3, lambda i, w: _weighted(np.asarray(w)[None], s)[0],
                device_fn=Bind(_weighted, s), pad=True).AllGather()

        first = run(2)
        c0 = ctx.overall_stats()["compiles"]
        second = run(5)
        assert ctx.overall_stats()["compiles"] == c0
    finally:
        ctx.close()
    for scale, got in ((2, first), (5, second)):
        assert [int(v) for v in got] == _padded_model(
            x, 3, lambda w: int(w[0] + w[1] * scale + w[2] * scale ** 2))


def test_a_second_value_of_the_operand_compiles_nothing(device_programs):
    x = np.arange(1, 513, dtype=np.int64)
    ctx = _ctx(1)
    try:
        def run(scale):
            return ctx.Distribute(x).Window(
                3, None, device_fn=Bind(_weighted, np.int64(scale)),
                pad=True).AllGatherArrays()

        run(2)
        c0 = ctx.overall_stats()["compiles"]
        got = np.asarray(run(9))
        assert ctx.overall_stats()["compiles"] == c0
    finally:
        ctx.close()
    assert got.tolist() == _padded_model(
        x, 3, lambda w: int(w[0] + w[1] * 9 + w[2] * 81))


# ------------------------------- the stitched chain: Sort, Window, PrefixSum

def _key(t):
    return t["k"]


def _differs(w):
    return (w["k"][:, 0] != w["k"][:, 1]).astype(jnp.uint32)


def _record_lowered(monkeypatch):
    lowered = {}
    dispatch = mesh_mod._CountedJit._dispatch

    def recording(self, args, kwargs):
        lowered.setdefault(self._label(), self.lower(*args, **kwargs))
        return dispatch(self, args, kwargs)

    monkeypatch.setattr(mesh_mod._CountedJit, "_dispatch", recording)
    return lowered


def test_sort_window_prefixsum_is_one_program_without_gather_or_scatter(
        device_programs, monkeypatch):
    """Names as the suffix sorter makes them: group ends by a padded
    Window(2) over the sorted rows, 1 + their exclusive sum. One
    dispatch; the windows are slices, the scan leaves its rows where
    they are (no compaction), and both carry their scopes."""
    lowered = _record_lowered(monkeypatch)
    rng = np.random.default_rng(34)
    k = rng.integers(0, 50, 4096).astype(np.uint32)
    ctx = _ctx(1)
    try:
        d0 = ctx.overall_stats()["device_dispatches"]
        names = ctx.Distribute({"k": k}).Sort(_key).Window(
            2, None, device_fn=_differs, pad=True).ExPrefixSum(initial=1)
        got = np.asarray(names.AllGatherArrays())
        assert ctx.overall_stats()["device_dispatches"] - d0 == 1
    finally:
        ctx.close()
    dense = np.unique(np.sort(k), return_inverse=True)[1] + 1
    assert got.dtype == np.uint32 and np.array_equal(got, dense)
    (label, program), = lowered.items()
    assert label.startswith("fused_Sort_Window_ExPrefixSum")
    text = program.as_text(debug_info=True)
    assert "/window/" in text and "/prefix_sum/" in text
    assert "/sort_engine/" in text and "/compact/" not in text
    hlo = program.compiler_ir(dialect="hlo").as_hlo_text()
    assert " scatter(" not in hlo
    # the one gather is the sort's payload by its permutation
    assert len(re.findall(r" gather\(", hlo)) == 1


def test_the_unpadded_window_is_slices_too(device_programs, monkeypatch):
    lowered = _record_lowered(monkeypatch)
    x = np.arange(4096, dtype=np.int64)
    ctx = _ctx(1)
    try:
        got = np.asarray(ctx.Distribute(x).Window(
            4, None, device_fn=_sum_dev).AllGatherArrays())
    finally:
        ctx.close()
    assert got.tolist() == [4 * j + 6 for j in range(4093)]
    hlo = "\n".join(p.compiler_ir(dialect="hlo").as_hlo_text()
                    for p in lowered.values())
    # the halo's k - 1 rows are the only gather left
    assert [m for m in re.findall(r"= \w+\[([\d,]*)\]\S* gather\(", hlo)
            if m and int(m.split(",")[0]) > 8] == []


@pytest.mark.parametrize("workers", [1, 4])
def test_prefix_sums_on_the_device_path(device_programs, workers):
    x = (np.arange(1000, dtype=np.uint32) % 7) + 1
    ctx = _ctx(workers)
    try:
        f0 = ctx.overall_stats()["host_fallbacks"]
        incl = np.asarray(ctx.Distribute(x).PrefixSum().AllGatherArrays())
        excl = np.asarray(ctx.Distribute(x).ExPrefixSum(initial=5)
                          .AllGatherArrays())
        assert ctx.overall_stats()["host_fallbacks"] == f0
    finally:
        ctx.close()
    assert np.array_equal(incl, np.cumsum(x, dtype=np.uint32))
    assert np.array_equal(excl, np.cumsum(x, dtype=np.uint32) - x + 5)


# ------------------------------------------------------------------ pulls

def test_pulls_count_the_root_stages(device_programs):
    x = np.arange(256, dtype=np.int64)
    ctx = _ctx(1)
    try:
        p0 = ctx.overall_stats()["pulls"]
        d = ctx.Distribute(x).Sort().Keep()
        assert d.Max() == 255               # pulls Distribute and Sort
        assert d.Size() == 256
        assert ctx.overall_stats()["pulls"] - p0 == 2
        roots = [r for r in ctx.tracer.ring
                 if r["cat"] == "stage" and "parent" not in r]
        assert [r["name"] for r in roots[-2:]] == ["MinMax", "Size"]
        assert len({r["pipe"] for r in roots[-2:]}) == 1
    finally:
        ctx.close()


def test_pulls_stay_zero_with_the_tracer_off(device_programs, monkeypatch):
    monkeypatch.setenv("THRILL_TPU_TRACE", "0")
    ctx = _ctx(1)
    try:
        assert ctx.Distribute(np.arange(16)).Size() == 16
        assert ctx.overall_stats()["pulls"] == 0
    finally:
        ctx.close()


# ------------------------------------------------------------ the example

@pytest.mark.parametrize("workers", [1, 4])
def test_suffix_array_is_one_pipeline_of_operators(device_programs, workers):
    rng = np.random.default_rng(3)
    text = rng.integers(97, 100, 1500).astype(np.uint8)
    ctx = _ctx(workers)
    try:
        stats = {}
        ss.suffix_array(ctx, text)      # the small plan arrays go up once
        s0 = ctx.overall_stats()
        n0 = ctx.tracer.records_written
        sa = ss.suffix_array(ctx, text, stats=stats)
        s1 = ctx.overall_stats()
        assert not ctx.tracer.wrapped
        roots = [r for r in list(ctx.tracer.ring)[n0:]
                 if r["cat"] == "stage" and "parent" not in r]
    finally:
        ctx.close()
    assert sa.dtype == np.uint32 and ss.check_sa(text, sa)
    assert np.array_equal(sa, ss.suffix_array_dense(text))
    rounds = stats["rounds"]
    assert rounds >= 2 and stats["h"] == 4 << rounds
    # the text goes up ONCE; names, every round and the result pull
    assert s1["device_uploads"] - s0["device_uploads"] == 1
    assert s1["pulls"] - s0["pulls"] == rounds + 2
    assert len({r["pipe"] for r in roots}) == 1
    assert [r["name"] for r in roots] == \
        ["MinMax"] * (rounds + 1) + ["AllGatherArrays"]
    assert s1["host_fallbacks"] == s0["host_fallbacks"]


def test_every_round_runs_the_same_programs(device_programs):
    """``h`` is an operand: a text of many rounds compiles what a text
    of two does, and a second job compiles nothing."""
    ctx = _ctx(1)
    try:
        two = np.random.default_rng(1).integers(97, 100, 2048) \
            .astype(np.uint8)
        stats = {}
        ss.suffix_array(ctx, two, stats=stats)
        assert 1 <= stats["rounds"] <= 3
        c0 = ctx.overall_stats()["compiles"]
        many = np.full(2048, ord("a"), np.uint8)
        sa = ss.suffix_array(ctx, many, stats=stats)
        assert stats["rounds"] == 9             # 4 * 2^9 = 2048
        assert np.array_equal(sa, np.arange(2047, -1, -1))
        assert ctx.overall_stats()["compiles"] == c0
    finally:
        ctx.close()


def test_the_end_of_the_text_is_below_character_zero(device_programs):
    """A text of zero bytes: the padding is zeros too, and only the
    index column tells them apart."""
    for n in (1, 2, 5, 9):
        text = np.zeros(n, np.uint8)
        ctx = _ctx(1)
        try:
            sa = ss.suffix_array(ctx, text)
        finally:
            ctx.close()
        assert sa.tolist() == list(range(n - 1, -1, -1))
    text = np.array([0, 1, 0, 0, 1, 0, 0, 0], np.uint8)
    ctx = _ctx(1)
    try:
        sa = ss.suffix_array(ctx, text)
    finally:
        ctx.close()
    assert np.array_equal(sa, ss.suffix_array_dense(text))


def test_the_empty_text(device_programs):
    ctx = _ctx(1)
    try:
        sa = ss.suffix_array(ctx, np.zeros(0, np.uint8))
    finally:
        ctx.close()
    assert sa.shape == (0,) and sa.dtype == np.uint32

