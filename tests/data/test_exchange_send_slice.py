"""The send side of the exchange cuts blocks, it scatters nothing
(ISSUE 33): a destination's send block is a slice of the dest-sorted
rows, zeroed behind its count. The model the helper is held to is the
scatter it replaced (``buf.at[send_idx].set(x)`` into a zeroed
``W*M+1``-row buffer), in numpy, bit for bit."""

import re

import jax
import numpy as np
import pytest

from thrill_tpu.api import Context
from thrill_tpu.data import exchange
from thrill_tpu.parallel.mesh import MeshExec, _CountedJit

TRAILS = {"1d": (), "n3": (3,), "packed23": (23,)}


@pytest.fixture(autouse=True)
def device_programs(monkeypatch):
    """What the chip runs (chipbench/run.py --rehearse sets the same)."""
    monkeypatch.setenv("THRILL_TPU_HOST_RADIX", "0")
    monkeypatch.setenv("THRILL_TPU_SORT_U32", "1")
    monkeypatch.setenv("THRILL_TPU_PACK_MOVE", "1")


def _ctx(W):
    return Context(MeshExec(devices=jax.devices("cpu")[:W]))


def _rows(rng, cap, trail):
    # no zero among the values: a zero row in the output is the mask's
    return rng.integers(1, 1 << 32, (cap,) + trail,
                        dtype=np.uint64).astype(np.uint32)


def scatter_model(x, S_row, M, lo=0, hi=None):
    """The old send side: dest-sorted ``x`` (valid rows first, grouped
    by destination), scattered to slot ``d * M_j + (slot - lo)`` of a
    zeroed buffer with a dump row; ``lo:hi`` is a chunk's window."""
    W, cap = len(S_row), x.shape[0]
    hi = M if hi is None else hi
    M_j = hi - lo
    off = np.cumsum(S_row) - S_row
    dest = np.searchsorted(np.cumsum(S_row), np.arange(cap), side="right")
    dc = np.clip(dest, 0, W - 1)
    slot = np.arange(cap) - off[dc]
    sel = (dest < W) & (slot >= lo) & (slot < hi)
    idx = np.where(sel, dc * M_j + (slot - lo), W * M_j)
    buf = np.zeros((W * M_j + 1,) + x.shape[1:], x.dtype)
    buf[idx] = x
    return buf[:W * M_j].reshape((W, M_j) + x.shape[1:])


# S_row, M, cap: what each case is there for
CASES = {
    "uniform_tail_invalid": ([5, 6, 5, 4], 8, 24),
    "empty_destination": ([7, 0, 9, 0], 16, 20),
    "block_fills_M": ([8, 3, 8, 1], 8, 24),
    "window_passes_cap": ([2, 3, 1, 6], 8, 12),     # off[3] + M = 14 > 12
    "all_to_one": ([0, 0, 16, 0], 16, 16),
    "no_rows": ([0, 0, 0, 0], 4, 8),
    "two_workers": ([3, 9], 16, 12),
}


@pytest.mark.parametrize("trail", sorted(TRAILS))
@pytest.mark.parametrize("case", sorted(CASES))
def test_send_slice_equals_the_scatter_it_replaced(case, trail):
    S_row, M, cap = CASES[case]
    S_row = np.asarray(S_row, np.int32)
    x = _rows(np.random.default_rng(len(case)), cap, TRAILS[trail])
    off = np.cumsum(S_row) - S_row
    got = jax.jit(lambda a, s, n: exchange.send_slice(a, s, n, M))(
        x, off, S_row)
    assert np.array_equal(np.asarray(got), scatter_model(x, S_row, M))


@pytest.mark.parametrize("lo,hi", [(0, 3), (3, 6), (6, 8), (2, 7)])
def test_send_slice_cuts_a_chunk_window_out_of_a_block(lo, hi):
    """The chunked exchange's window ``lo:hi``: ``start = off + lo``,
    ``n = clip(S_row - lo, 0, hi - lo)``; windows split blocks, lie
    behind short ones, and their starts pass ``cap``."""
    S_row = np.asarray([8, 2, 5, 0], np.int32)
    M, cap = 8, 15                          # no invalid row: off[3] = cap
    x = _rows(np.random.default_rng(lo), cap, (3,))
    off = np.cumsum(S_row) - S_row
    got = jax.jit(lambda a, s, n: exchange.send_slice(a, s, n, hi - lo))(
        x, off + lo, np.clip(S_row - lo, 0, hi - lo))
    assert np.array_equal(np.asarray(got),
                          scatter_model(x, S_row, M, lo, hi))


def test_send_slice_of_a_block_longer_than_M_is_its_first_M_rows():
    """A capacity overflow: nothing crashes and nothing shifts; the
    caller's flag (next test) routes the exchange to the synced plan."""
    S_row = np.asarray([3, 9, 2], np.int32)
    x = _rows(np.random.default_rng(9), 16, (2,))
    off = np.cumsum(S_row) - S_row
    got = np.asarray(jax.jit(
        lambda a, s, n: exchange.send_slice(a, s, n, 4))(x, off, S_row))
    assert np.array_equal(got[1], x[3:7])
    assert np.array_equal(got[0], np.concatenate([x[0:3], x[:1] * 0]))
    assert np.array_equal(got[2], np.concatenate([x[12:14], x[:2] * 0]))


def _dest_sorted(rng, S, cap, trails):
    """Per worker: rows grouped by destination, valid first, W behind."""
    W = S.shape[0]
    dest = np.full((W, cap), W, np.int32)
    for w in range(W):
        dest[w, :S[w].sum()] = np.repeat(np.arange(W), S[w])
    leaves = [np.stack([_rows(rng, cap, t) for _ in range(W)])
              for t in trails]
    return dest, leaves


def _exchanged(S, leaves, out_cap):
    """What worker d holds after the exchange: source 0's block for d,
    then source 1's ..., zeros behind (numpy, from S alone)."""
    W = S.shape[0]
    off = np.cumsum(S, axis=1) - S
    outs = []
    for l in leaves:
        out = np.zeros((W, out_cap) + l.shape[2:], l.dtype)
        for d in range(W):
            rows = np.concatenate(
                [l[w, off[w, d]:off[w, d] + S[w, d]] for w in range(W)])
            out[d, :len(rows)] = rows
        outs.append(out)
    return outs


def _run_chunked(mex, S, dest, leaves, M_pad, out_cap):
    treedef = jax.tree.structure(list(range(len(leaves))))
    out, counts, flag = exchange._dispatch_chunked(
        mex, treedef, mex.put(dest), [mex.put(l) for l in leaves],
        mex.put_small(S.astype(np.int32), replicated=True), M_pad, out_cap)
    return ([np.asarray(o) for o in out], np.asarray(counts),
            int(np.asarray(flag).max()))


@pytest.mark.parametrize("chunks", [1, 3])
@pytest.mark.parametrize("W", [2, 4])
def test_dense_exchange_on_a_mesh_equals_the_model(W, chunks, monkeypatch):
    """``xchg_chunk`` on a virtual mesh, bulk and in three windows that
    split the blocks: an empty destination, a block that fills M_pad,
    windows that pass cap, invalid rows at the tail, three leaf shapes."""
    monkeypatch.setenv("THRILL_TPU_XCHG_CHUNKS", str(chunks))
    rng = np.random.default_rng(W)
    S = rng.integers(0, 9, (W, W))
    S[0, 1] = 0
    S[W - 1, 0] = 8                           # fills M_pad exactly
    M_pad, cap, out_cap = 8, int(S.sum(axis=1).max()) + 3, 8 * W
    dest, leaves = _dest_sorted(rng, S, cap, list(TRAILS.values()))
    ctx = _ctx(W)
    try:
        mex = ctx.mesh_exec
        got, counts, flag = _run_chunked(mex, S, dest, leaves, M_pad,
                                         out_cap)
        assert flag == 0
        assert counts.reshape(-1).tolist() == S.sum(axis=0).tolist()
        for g, want in zip(got, _exchanged(S, leaves, out_cap)):
            assert np.array_equal(g, want)
        n_chunks = min(chunks, M_pad)
        assert mex.stats_xchg_send_slices == n_chunks * W * len(leaves)
        assert ctx.overall_stats()["xchg_send_slices"] \
            == mex.stats_xchg_send_slices
        span = [r for r in ctx.tracer.ring
                if r.get("cat") == "exchange"
                and r.get("name") == "phase_b"]
        assert [r["send_slices"] for r in span] \
            == [n_chunks * W * len(leaves)]
    finally:
        ctx.close()


def test_dense_exchange_raises_its_flag_on_a_block_longer_than_M_pad():
    W = 2
    S = np.asarray([[3, 9], [2, 2]])
    rng = np.random.default_rng(1)
    dest, leaves = _dest_sorted(rng, S, 12, [(3,)])
    ctx = _ctx(W)
    try:
        got, _, flag = _run_chunked(ctx.mesh_exec, S, dest, leaves,
                                    M_pad=4, out_cap=16)
        assert flag == 1
        # what fitted arrived unshifted: worker 0's rows from both
        assert np.array_equal(got[0][0, :3], leaves[0][0, :3])
        assert np.array_equal(got[0][0, 3:5], leaves[0][1, :2])
    finally:
        ctx.close()


def _records(rng, n, keys):
    return {"key": keys.astype(np.uint8),
            "value": rng.integers(0, 256, (n, 90), dtype=np.uint8)}


def _keys(rng, n, how):
    if how == "uniform":
        return rng.integers(0, 256, (n, 10), dtype=np.uint8)
    # skewed: four distinct keys, one of them on 85 % of the rows, so
    # one destination's block holds most of every worker's rows
    pool = rng.integers(0, 256, (4, 10), dtype=np.uint8)
    return pool[rng.choice(4, n, p=[0.85, 0.05, 0.05, 0.05])]


def _reference_sort(inp):
    order = np.lexsort(tuple(inp["key"][:, k] for k in range(9, -1, -1)))
    return inp["key"][order], inp["value"][order]


@pytest.mark.parametrize("how", ["uniform", "skewed"])
@pytest.mark.parametrize("mode", ["dense", "onefactor"])
@pytest.mark.parametrize("W", [2, 4])
def test_sort_across_workers_equals_the_reference_sort(W, mode, how,
                                                       monkeypatch):
    """The fused exchange-merge and the 1-factor rounds behind Sort, on
    uniform keys and under skew (where the ``dense`` plan takes the
    rounds by itself). Equal keys come out by input position (the sort
    ties on the global index), as numpy's stable reference has them."""
    monkeypatch.setenv("THRILL_TPU_EXCHANGE", mode)
    rng = np.random.default_rng(W * 7 + len(how))
    n = 3000
    inp = _records(rng, n, _keys(rng, n, how))
    ctx = _ctx(W)
    try:
        got = ctx.Distribute(inp).Sort(key_fn=lambda r: r["key"]) \
            .AllGatherArrays()
        labels = [r["name"] for r in ctx.tracer.ring
                  if r.get("cat") == "dispatch"]
        fused = [r["send_slices"] for r in ctx.tracer.ring
                 if r.get("cat") == "exchange"
                 and r.get("name") == "sort_fused"]
        stats = ctx.overall_stats()
    finally:
        ctx.close()
    want_k, want_v = _reference_sort(inp)
    assert np.array_equal(np.asarray(got["key"]), want_k)
    assert np.array_equal(np.asarray(got["value"]), want_v)
    if mode == "dense" and how == "uniform":
        assert "sort_fused" in labels
        # key words, index, and the two payload leaves: W blocks each
        assert stats["xchg_send_slices"] == 4 * W
        assert fused == [4 * W]
    else:
        assert "xchg_of" in labels
        assert stats["xchg_send_slices"] == 4 * (W - 1)


def _lowered(monkeypatch, job, W, **env):
    """StableHLO (with locations) of every program ``job`` dispatches."""
    for k, v in env.items():
        monkeypatch.setenv(k, v)
    seen = {}
    dispatch = _CountedJit._dispatch

    def recording(self, args, kwargs):
        seen.setdefault(self._label(), self.lower(*args, **kwargs)
                        .as_text(debug_info=True))
        return dispatch(self, args, kwargs)

    monkeypatch.setattr(_CountedJit, "_dispatch", recording)
    ctx = _ctx(W)
    try:
        job(ctx)
    finally:
        ctx.close()
    return seen


def _scatters_by_scope(text):
    """(scatters under exchange/send_slice, scatters elsewhere, slices
    under exchange/send_slice) of one lowered program."""
    locs = dict(re.findall(r"^(#loc\d+) = (.*)$", text, re.M))

    def scope(line):
        m = re.search(r"loc\((#loc\d+)\)", line)
        name, seen = (m[1] if m else ""), set()
        out = []
        while name and name not in seen:    # follow nested locations
            seen.add(name)
            out.append(locs.get(name, ""))
            nxt = re.search(r"#loc\d+", out[-1])
            name = nxt[0] if nxt else ""
        return " ".join(out)

    send = other = slices = 0
    for line in text.splitlines():
        if "stablehlo.scatter" in line:
            if "send_slice" in scope(line):
                send += 1
            else:
                other += 1
        elif "stablehlo.dynamic_slice" in line \
                and "exchange/send_slice" in scope(line):
            slices += 1
    return send, other, slices


def _sort_job(ctx):
    rng = np.random.default_rng(3)
    ctx.Distribute(_records(rng, 512, _keys(rng, 512, "uniform"))) \
        .Sort(key_fn=lambda r: r["key"]).AllGatherArrays()


def _group_job(ctx):
    vals = np.arange(2000, dtype=np.int64) % 37
    ctx.Distribute(vals).Map(lambda x: (x, 1)) \
        .GroupByKey(lambda t: t[0], lambda k, it: (k, len(list(it)))) \
        .AllGather()


@pytest.mark.parametrize("label,job,env,recv_scatters", [
    # the fused exchange-merge never scattered on the receive side
    ("sort_fused", _sort_job, {}, 0),
    # one receive-side scatter per leaf (key and count of the pair)
    ("xchg_chunk", _group_job, {"THRILL_TPU_XCHG_CHUNKS": "1"}, 2),
    # the local round and W - 1 rounds, each one per leaf
    ("xchg_of", _group_job, {"THRILL_TPU_EXCHANGE": "onefactor"}, 8),
])
def test_no_scatter_on_the_send_side_of_the_lowered_program(
        label, job, env, recv_scatters, monkeypatch):
    W = 4
    text = _lowered(monkeypatch, job, W, **env)[label]
    send, other, slices = _scatters_by_scope(text)
    assert send == 0
    assert other == recv_scatters       # the parent lowered one more a block
    assert slices > 0
    # textual order is program order: nothing scatters ahead of the
    # first collective of a program whose receive side scatters too
    coll = re.search(r"stablehlo\.(all_to_all|collective_permute)", text)
    first = text.find("stablehlo.scatter")
    if label != "xchg_of":              # its local round scatters first
        assert first == -1 or first > coll.start()
