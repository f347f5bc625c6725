"""How host arrays become the ``[W, cap, ...]`` tree of a ``put``
(ISSUE 27): a view of the caller's memory where nothing is padded, one
write into a zeroed buffer where something is, and in both cases the
input taken as it stands while the stage runs.

On the CPU mesh the address of an input matters: jax's CPU client makes a
64-byte aligned host array the device buffer itself, so there the staging
copies after all. Inputs are therefore placed at a chosen address modulo
64 (``placed``): 16 for what the chip sees, 0 for the CPU's own rule."""

import jax
import numpy as np
import pytest

from thrill_tpu.api import Context
from thrill_tpu.common import trace
from thrill_tpu.data.shards import DeviceShards
from thrill_tpu.parallel.mesh import MeshExec


def placed(values: np.ndarray, offset: int = 16) -> np.ndarray:
    """``values`` in C order at an address that is ``offset`` modulo 64."""
    values = np.ascontiguousarray(values)
    raw = np.empty(values.nbytes + 128, np.uint8)
    start = (offset - raw.ctypes.data) % 64
    out = raw[start:start + values.nbytes].view(values.dtype).reshape(
        values.shape)
    out[...] = values
    assert not out.size or out.ctypes.data % 64 == offset
    return out


def rows(n: int, seed: int = 0) -> dict:
    """Leaves of different dtypes and ranks."""
    rng = np.random.default_rng(seed)
    return {"key": placed(rng.integers(0, 256, (n, 16), dtype=np.uint8)),
            "id": placed(rng.integers(1, 1 << 40, n, dtype=np.int64)),
            "m": placed(rng.random((n, 2, 4)).astype(np.float32) + 1.0)}


def tree_bytes(tree) -> int:
    return sum(leaf.nbytes for leaf in jax.tree.leaves(tree))


class Staged:
    """A mesh of ``W`` CPU devices whose ``put`` remembers the host
    arrays it was handed."""

    def __init__(self, W: int):
        self.mex = MeshExec(num_workers=W)
        self.ctx = Context(self.mex)
        self.host = []
        put = self.mex.put

        def remembering(arr):
            self.host.append(arr)
            return put(arr)
        self.mex.put = remembering

    def run(self, build):
        """``build(mex)`` -> DeviceShards, with the bytes the staging
        copied, the arrays it put and whether it waited for them."""
        before = self.ctx.overall_stats()["stage_copy_bytes"]
        del self.host[:]
        written = len(self.mex.tracer.ring)
        shards = build(self.mex)
        after = self.ctx.overall_stats()["stage_copy_bytes"]
        waits = [r for r in list(self.mex.tracer.ring)[written:]
                 if r["cat"] == "wait" and r["name"] == "upload"]
        return shards, after - before, list(self.host), len(waits)


def lent(staged: list, tree) -> bool:
    leaves = jax.tree.leaves(tree)
    assert len(staged) == len(leaves)
    return all(np.shares_memory(s, leaf) for s, leaf in zip(staged, leaves))


def assert_same(got, want) -> None:
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert g.dtype == w.dtype and np.array_equal(g, w)


# -- (a), (b): nothing to pad, nothing copied -------------------------------

@pytest.mark.parametrize("W,n", [(1, 1), (1, 256), (1, 4096), (4, 4 * 64),
                                 (4, 4 * 1024), (2, 2 * 128), (3, 3 * 64)])
def test_exact_fit_is_a_view_of_the_input(W, n):
    st = Staged(W)
    inp = rows(n)
    shards, copied, staged, waits = st.run(
        lambda mex: DeviceShards.from_global_numpy(mex, inp))
    assert copied == 0
    assert lent(staged, inp)
    assert all(s.shape[:2] == (W, n // W) for s in staged)
    assert waits == 1            # lent memory: the stage waits for the put
    assert list(shards.counts) == [n // W] * W
    assert_same(shards.to_global_numpy(), inp)


def test_one_worker_filling_cap_is_a_view_of_its_leaf():
    st = Staged(1)
    inp = rows(512)
    shards, copied, staged, waits = st.run(
        lambda mex: DeviceShards.from_worker_arrays(mex, [inp]))
    assert (copied, waits) == (0, 1) and lent(staged, inp)
    assert_same(shards.to_global_numpy(), inp)


@pytest.mark.parametrize("W", [1, 4])
def test_cpu_client_keeps_an_aligned_array_so_it_is_copied(W):
    """The CPU's own rule: a device buffer made of a 64-byte aligned
    host array is that memory for good, so no view goes up."""
    st = Staged(W)
    n = W * 256
    inp = {k: placed(v, 0) for k, v in rows(n).items()}
    shards, copied, staged, waits = st.run(
        lambda mex: DeviceShards.from_global_numpy(mex, inp))
    assert copied == tree_bytes(inp) and waits == 0
    assert not any(np.shares_memory(s, leaf) for s, leaf in
                   zip(staged, jax.tree.leaves(inp)))
    assert_same(shards.to_global_numpy(), inp)


# -- (c): something to pad, one write ---------------------------------------

def padded_bytes(W: int, cap: int, tree) -> int:
    """``W * cap * row_bytes`` per leaf."""
    return sum(W * cap * leaf.dtype.itemsize * int(np.prod(leaf.shape[1:]))
               for leaf in jax.tree.leaves(tree))


def assert_pad_rows_zero(st, shards, lengths) -> None:
    for leaf in jax.tree.leaves(st.mex.fetch_tree(shards.tree)):
        for w, k in enumerate(lengths):
            assert not leaf[w, k:].any()


@pytest.mark.parametrize("W,n,cap", [(4, 1001, 256), (4, 4 * 96, 128),
                                     (1, 1000, 1024), (3, 100, 64),
                                     (4, 3, 1), (1, 0, 1)])
def test_global_split_that_pads_is_written_once(W, n, cap):
    st = Staged(W)
    inp = rows(n)
    shards, copied, staged, waits = st.run(
        lambda mex: DeviceShards.from_global_numpy(mex, inp))
    assert copied == padded_bytes(W, cap, inp) and waits == 0
    assert all(s.shape[:2] == (W, cap) for s in staged)
    assert shards.cap == cap
    assert_pad_rows_zero(st, shards, shards.counts)
    assert_same(shards.to_global_numpy(), inp)


WORKER_CASES = {
    "cap_above_every_worker": ([64, 64, 64, 64], 256, None),
    "ragged": ([100, 7, 64, 33], 0, None),
    "one_empty_worker": ([128, 0, 128, 128], 0, None),
    "all_full_but_four_workers": ([64, 64, 64, 64], 0, None),
    # data/multiplexer.py host_to_device: agreed counts and cap, empty
    # leaves for the workers another process holds
    "counts_for_rows_held_elsewhere": ([50, 0, 0, 20], 64, [50, 41, 64, 20]),
}


@pytest.mark.parametrize("case", sorted(WORKER_CASES))
def test_worker_arrays_that_pad_are_written_once(case):
    lengths, cap, counts = WORKER_CASES[case]
    st = Staged(4)
    whole = rows(sum(lengths), seed=5)
    bounds = np.concatenate([[0], np.cumsum(lengths)])
    per_worker = [jax.tree.map(lambda a: a[bounds[w]:bounds[w + 1]], whole)
                  for w in range(4)]
    shards, copied, staged, waits = st.run(
        lambda mex: DeviceShards.from_worker_arrays(
            mex, per_worker, cap=cap,
            counts=None if counts is None else np.array(counts)))
    want_cap = cap or 1 << (max(lengths) - 1).bit_length()
    assert shards.cap == want_cap
    assert copied == padded_bytes(4, want_cap, whole) and waits == 0
    assert not any(np.shares_memory(s, leaf) for s in staged
                   for leaf in jax.tree.leaves(whole))
    assert list(shards.counts) == (counts or lengths)
    assert_pad_rows_zero(st, shards, lengths)
    if counts is None:
        assert_same(shards.to_global_numpy(), whole)
    else:       # the rows this process holds are where their worker's are
        host = st.mex.fetch_tree(shards.tree)
        for w, k in enumerate(lengths):
            assert_same(jax.tree.map(lambda a: a[w, :k], host), per_worker[w])


def test_a_leaf_longer_than_cap_is_refused():
    st = Staged(1)
    with pytest.raises(ValueError):
        DeviceShards.from_worker_arrays(st.mex, [rows(65)], cap=64)


# -- (d): layouts ------------------------------------------------------------

def every_other(n):
    return jax.tree.map(lambda a: a[::2], rows(2 * n))


def fortran(n):
    """Fortran order at a chosen address: the transpose of a placed
    C-ordered array."""
    return jax.tree.map(lambda a: placed(a.T).T, rows(n))


def read_only(n):
    inp = rows(n)
    for leaf in inp.values():
        leaf.flags.writeable = False
    return inp


def transposed(n):
    return {"t": placed(np.arange(8 * n, dtype=np.int32).reshape(8, n)).T}


def nested(n):
    r = rows(n)
    return {"a": r["key"], "b": {"c": r["id"], "d": [r["m"], r["id"][::-1]]}}


LAYOUTS = {"every_other": every_other, "fortran": fortran,
           "read_only": read_only, "transposed": transposed,
           "nested": nested}


@pytest.mark.parametrize("n", [256, 250])
@pytest.mark.parametrize("W", [1, 4])
@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_any_layout_comes_back_exactly(layout, W, n):
    st = Staged(W)
    inp = LAYOUTS[layout](n)
    before = jax.tree.map(np.copy, inp)
    shards, copied, staged, waits = st.run(
        lambda mex: DeviceShards.from_global_numpy(mex, inp))
    assert_same(shards.to_global_numpy(), before)
    assert_same(inp, before)
    # splitting axis 0 never needs a copy, whatever the strides: a view
    # where nothing is padded, one write of every leaf where something is
    if n == 256:
        assert (copied, waits) == (0, 1) and lent(staged, inp)
    else:
        assert copied == sum(s.nbytes for s in staged) and waits == 0


# -- (e): the input as it stands when the stage runs ------------------------

@pytest.mark.parametrize("offset", [0, 16, 32])
@pytest.mark.parametrize("W,n", [(1, 1 << 21), (4, 1 << 21), (1, 200_000),
                                 (4, 200_001)])
def test_input_may_be_overwritten_once_the_stage_has_run(W, n, offset):
    """At 2^21 rows (32 + 16 MB) the lent cases fail every time without
    the wait after the ``put``: the client is still reading when the
    overwrite lands."""
    ctx = Context(MeshExec(num_workers=W))
    rng = np.random.default_rng(n + offset)
    inp = {"key": placed(rng.integers(0, 256, (n, 16), dtype=np.uint8),
                         offset),
           "id": placed(rng.integers(1, 1 << 40, n, dtype=np.int64), offset)}
    want = jax.tree.map(np.copy, inp)
    dia = ctx.Distribute(inp)
    dia.Keep()
    dia.node.materialize()              # the stage has run; nothing fetched
    for leaf in inp.values():
        leaf[...] = 0
    assert dia.Size() == n
    assert_same(dia.AllGatherArrays(), want)


# -- the counter's other face: the covering stage span ----------------------

@pytest.mark.parametrize("n,copied_of", [(1024, lambda inp: 0),
                                         (1000, lambda inp: padded_bytes(
                                             4, 256, inp))])
def test_stage_span_carries_copied_bytes(n, copied_of):
    ctx = Context(MeshExec(num_workers=4))
    inp = rows(n)
    before = ctx.overall_stats()["stage_copy_bytes"]
    assert ctx.Distribute(inp).Size() == n
    spans = [r for r in ctx.tracer.ring
             if r["cat"] == "stage" and r["name"] == "Distribute"]
    assert [r["copied_bytes"] for r in spans] == [copied_of(inp)]
    assert ctx.overall_stats()["stage_copy_bytes"] - before \
        == copied_of(inp)
    assert all("copied_bytes" not in r for r in ctx.tracer.ring
               if r["cat"] == "stage" and r["name"] != "Distribute")


def test_add_to_open_finds_the_innermost_span_of_the_category():
    tr = trace.Tracer(enabled=True, ring=16)
    tr.add_to_open("stage", "copied_bytes", 5)       # none open: nothing
    with tr.span("stage", "outer"):
        with tr.span("stage", "inner"):
            with tr.span("fusion", "f"):
                tr.add_to_open("stage", "copied_bytes", 0)
                tr.add_to_open("stage", "copied_bytes", 7)
    by_name = {r["name"]: r for r in tr.ring}
    assert by_name["inner"]["copied_bytes"] == 7
    assert "copied_bytes" not in by_name["outer"]
    assert "copied_bytes" not in by_name["f"]
    off = trace.Tracer(enabled=False, ring=16)
    created = trace.SPANS_CREATED
    off.add_to_open("stage", "copied_bytes", 7)
    assert trace.SPANS_CREATED == created and not off.ring
