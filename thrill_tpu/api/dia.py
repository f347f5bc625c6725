"""The DIA handle: a lazily evaluated distributed immutable array.

Equivalent of the reference's ``DIA<ValueType, Stack>``
(reference: thrill/api/dia.hpp:141): a cheap handle = node pointer +
stack of fused local operations. Chaining ``Map``/``Filter``/``FlatMap``
never touches data — it extends the stack; distributed operations cut
the stack by constructing a new DAG node; actions trigger execution.
"""

from __future__ import annotations

from typing import Any, Callable, Optional

from .dia_base import DIABase, ParentLink
from .stack import Stack, StackOp


class DIA:
    def __init__(self, node: DIABase, stack: Stack = ()) -> None:
        self.node = node
        self.stack = stack

    @property
    def context(self):
        return self.node.context

    def _link(self) -> ParentLink:
        return ParentLink(self.node, self.stack)

    # ------------------------------------------------------------------
    # local ops (stack pushes; reference api/dia.hpp:358,405,458)
    # ------------------------------------------------------------------
    def Map(self, fn: Callable) -> "DIA":
        return DIA(self.node, self.stack + (StackOp("map", fn),))

    def Filter(self, fn: Callable) -> "DIA":
        return DIA(self.node, self.stack + (StackOp("filter", fn),))

    def FlatMap(self, fn: Callable, device_fn: Optional[Callable] = None,
                factor: int = 1) -> "DIA":
        """Host: ``fn(item) -> iterable``. Device storage additionally
        needs the batched form ``device_fn(tree) -> (tree[n,k,...],
        valid[n,k])`` with static ``factor`` k; without it the pipeline
        falls back to host storage at this point."""
        from .ops import lop_nodes
        if device_fn is not None:
            return DIA(self.node, self.stack +
                       (StackOp("flat_map", device_fn, factor),))
        return lop_nodes.flat_map_host(self, fn)

    def BernoulliSample(self, p: float, seed: int = 0) -> "DIA":
        from .ops import sample
        return sample.BernoulliSample(self, p, seed)

    # ------------------------------------------------------------------
    # distributed ops
    # ------------------------------------------------------------------
    def ReduceByKey(self, key_fn: Callable, reduce_fn: Callable,
                    dup_detection=None) -> "DIA":
        """``dup_detection`` (reference: DuplicateDetectionTag) skips
        shuffling globally-unique keys: the device path folds a
        presence-register psum into the destination program, the host
        path exchanges Golomb fingerprints. None — the default —
        defers to the plan-time cost model (core/preshuffle.py,
        forced either way with THRILL_TPU_DUP_DETECT=0/1); True/False
        force it per call.

        Output order is UNSPECIFIED (as in the reference's
        hash-partitioned tables): the device engine emits key-sorted
        order, the CPU-backend native hash-group emits
        first-appearance order — sort before comparing across
        backends. Dup detection additionally changes which worker
        holds a unique key's result (it stays local instead of
        travelling to its hash home) — the result SET is identical."""
        from .ops import reduce as _r
        return _r.ReduceByKey(self, key_fn, reduce_fn, dup_detection)

    def ReducePair(self, reduce_fn: Callable) -> "DIA":
        """Items are (key, value) pairs; reduce_fn combines values."""
        from .ops import reduce as _r
        return _r.ReducePair(self, reduce_fn)

    def ReduceToIndex(self, index_fn: Callable, reduce_fn: Callable,
                      size: int, neutral: Any = None) -> "DIA":
        from .ops import reduce as _r
        return _r.ReduceToIndex(self, index_fn, reduce_fn, size, neutral)

    def GroupByKey(self, key_fn: Callable, group_fn: Callable = None,
                   device_fn: Callable = None) -> "DIA":
        """Group order is UNSPECIFIED (reference: hash-partitioned
        grouping): the device engine yields key-sorted groups, the
        CPU-backend hash-group yields first-appearance order — sort
        before comparing across backends."""
        from .ops import groupby
        return groupby.GroupByKey(self, key_fn, group_fn,
                                  device_fn=device_fn)

    def GroupToIndex(self, index_fn: Callable, group_fn: Callable = None,
                     size: int = 0, neutral: Any = None,
                     device_fn: Callable = None) -> "DIA":
        from .ops import groupby
        return groupby.GroupToIndex(self, index_fn, group_fn, size, neutral,
                                    device_fn=device_fn)

    def Sort(self, key_fn: Optional[Callable] = None,
             compare_fn: Optional[Callable] = None) -> "DIA":
        from .ops import sort as _s
        return _s.Sort(self, key_fn, compare_fn, stable=False)

    def SortStable(self, key_fn: Optional[Callable] = None,
                   compare_fn: Optional[Callable] = None) -> "DIA":
        from .ops import sort as _s
        return _s.Sort(self, key_fn, compare_fn, stable=True)

    def PrefixSum(self, fn: Callable = None, initial: Any = 0) -> "DIA":
        """Inclusive scan. Without ``fn`` it is the additive scan and
        runs on the device, inside the stitched program of the chain it
        ends; a generic ``fn`` folds on the host (api/ops/prefix_sum.py
        says what a job that must stay on the device spells instead)."""
        from .ops import prefix_sum as _p
        return _p.PrefixSum(self, fn, initial, inclusive=True)

    def ExPrefixSum(self, fn: Callable = None, initial: Any = 0) -> "DIA":
        """Exclusive scan starting at ``initial``; device and host
        paths as ``PrefixSum``'s."""
        from .ops import prefix_sum as _p
        return _p.PrefixSum(self, fn, initial, inclusive=False)

    def ZipWithIndex(self, zip_fn: Callable = None) -> "DIA":
        from .ops import zip_ as _z
        return _z.ZipWithIndex(self, zip_fn)

    def Window(self, k: int, fn: Callable,
               device_fn: Optional[Callable] = None,
               pad: bool = False) -> "DIA":
        """``pad=True``: the sequence is read as continued by k-1 zero
        items, so every item starts a window (n windows for n items;
        api/ops/window.py). ``device_fn`` may be a ``Bind``."""
        from .ops import window as _w
        return _w.Window(self, k, fn, device_fn, disjoint=False, pad=pad)

    def FlatWindow(self, k: int, fn: Callable = None,
                   device_fn: Optional[Callable] = None,
                   factor: int = 0) -> "DIA":
        from .ops import window as _w
        return _w.FlatWindow(self, k, fn, device_fn=device_fn,
                             factor=factor)

    def DisjointWindow(self, k: int, fn: Callable,
                       device_fn: Optional[Callable] = None,
                       partial_fn: Optional[Callable] = None) -> "DIA":
        """``partial_fn(start, items)`` additionally receives the
        trailing block of fewer than k items (reference:
        partial_window_function, api/window.hpp:389); passing it keeps
        the op on the host path (dynamic-length tail)."""
        from .ops import window as _w
        return _w.Window(self, k, fn, device_fn, disjoint=True,
                         partial_fn=partial_fn)

    def Concat(self, other: "DIA") -> "DIA":
        from .ops import concat as _c
        return _c.Concat(self, other)

    def Union(self, *others: "DIA") -> "DIA":
        from .ops import union as _u
        return _u.Union(self, *others)

    def Rebalance(self) -> "DIA":
        from .ops import rebalance as _rb
        return _rb.Rebalance(self)

    def Sample(self, k: int, seed: int = 0) -> "DIA":
        from .ops import sample as _sm
        return _sm.Sample(self, k, seed)

    # ------------------------------------------------------------------
    # consume control / materialization nodes
    # ------------------------------------------------------------------
    def ToHost(self) -> "DIA":
        """Explicitly demote to host item-list storage (logged)."""
        from .ops import lop_nodes
        return lop_nodes.to_host(self)

    def ToDevice(self) -> "DIA":
        """Explicitly promote host items to columnar device storage."""
        from .ops import lop_nodes
        return lop_nodes.to_device(self)

    def Keep(self, n: int = 1) -> "DIA":
        self.node.keep(n)
        return self

    def Cache(self) -> "DIA":
        from .ops import cache as _ca
        return _ca.Cache(self)

    def Collapse(self) -> "DIA":
        from .ops import cache as _ca
        return _ca.Collapse(self)

    def Checkpoint(self, name: Optional[str] = None) -> "DIA":
        """Materialize here and seal the result into a durable epoch
        (api/checkpoint.py) when ``THRILL_TPU_CKPT_DIR`` is set; a
        resumed run (``resume=True`` / ``THRILL_TPU_RESUME=1``) reloads
        the newest committed epoch and skips this node's entire
        upstream subgraph. Without a checkpoint dir this is a plain
        materialization barrier (Cache-like)."""
        from .checkpoint import make_checkpoint_node
        return make_checkpoint_node(self, name)

    def Execute(self) -> "DIA":
        self.node.materialize()
        return self

    def Dispose(self) -> None:
        self.node.dispose()

    def explain(self) -> str:
        """Annotated physical plan of THIS DIA's upstream subgraph:
        ops, fused segments, exchange strategy per shuffle edge, and
        every recorded decision with its reason and (post-run) audit
        verdict (common/decisions.py; ``ctx.explain()`` renders the
        whole Context). Purely observational — reads the decision
        ledger, changes no plan or state."""
        from ..common.decisions import render_plan
        nodes, stack = [], [self.node]
        seen = set()
        while stack:
            n = stack.pop()
            if n.id in seen:
                continue
            seen.add(n.id)
            nodes.append(n)
            stack.extend(p.node for p in n.parents)
        return render_plan(
            [{"id": n.id, "label": n.label, "state": n.state,
              "parents": [p.node.id for p in n.parents]}
             for n in nodes],
            self.context.decisions.snapshot(),
            W=self.context.num_workers,
            title=f"{self.node.label}#{self.node.id}")

    # ------------------------------------------------------------------
    # actions
    # ------------------------------------------------------------------
    def Size(self) -> int:
        from .ops import actions
        return actions.Size(self)

    # Future variants defer execution until .get() — reference:
    # api/action_node.hpp Future<T>. Creation reserves one consume-budget
    # unit so issue order (not get order) governs consumption: actions
    # run between issue and get cannot starve the future.
    def _future(self, thunk) -> "ActionFuture":
        from .future import ActionFuture
        self.node.keep(1)
        return ActionFuture(thunk)

    def SizeFuture(self):
        from .ops import actions
        return self._future(lambda: actions.Size(self))

    def AllGatherFuture(self):
        from .ops import actions
        return self._future(lambda: actions.AllGather(self))

    def SumFuture(self, fn: Callable = None, initial: Any = 0):
        from .ops import actions
        if fn is not None:
            return self._future(lambda: actions.AllReduce(self, fn, initial))
        return self._future(lambda: actions.Sum(self, initial))

    def AllGather(self) -> list:
        from .ops import actions
        return actions.AllGather(self)

    def AllGatherArrays(self):
        """Columnar AllGather: one pytree of stacked leaves [total, ...]
        — device arrays on the device path (no host sync; feed them to
        the next iteration's Bind directly)."""
        from .ops import actions
        return actions.AllGatherArrays(self)

    def Gather(self, root: int = 0) -> list:
        from .ops import actions
        return actions.Gather(self, root)

    def Print(self, label: str = "", limit: int = 100) -> "DIA":
        from .ops import actions
        actions.Print(self, label, limit)
        return self

    def AllReduce(self, fn: Callable, initial: Any = None) -> Any:
        from .ops import actions
        return actions.AllReduce(self, fn, initial)

    def Sum(self, fn: Callable = None, initial: Any = 0,
            device: bool = False) -> Any:
        """``device=True`` (device storage, no custom fn): the summed
        pytree stays on device — feed it back into a Bind without a
        host sync (zero-sync iterative loops)."""
        from .ops import actions
        if fn is not None:
            return actions.AllReduce(self, fn, initial)
        return actions.Sum(self, initial, device=device)

    def Min(self) -> Any:
        from .ops import actions
        return actions.MinMax(self, is_min=True)

    def Max(self) -> Any:
        from .ops import actions
        return actions.MinMax(self, is_min=False)

    def HyperLogLog(self, precision: int = 14) -> float:
        from .ops import hll
        return hll.HyperLogLog(self, precision)

    def WriteLines(self, path_pattern: str) -> None:
        from .ops import read_write
        read_write.WriteLines(self, path_pattern)

    def WriteLinesOne(self, path: str) -> None:
        from .ops import read_write
        read_write.WriteLinesOne(self, path)

    def WriteBinary(self, path_pattern: str) -> None:
        from .ops import read_write
        read_write.WriteBinary(self, path_pattern)


# ----------------------------------------------------------------------
# free functions over multiple DIAs
# ----------------------------------------------------------------------

def Zip(*dias: DIA, zip_fn: Callable = None, mode: str = "strict") -> DIA:
    from .ops import zip_ as _z
    return _z.Zip(list(dias), zip_fn, mode)

def ZipWindow(window: tuple, *dias: DIA, zip_fn: Callable = None,
              device_fn: Callable = None) -> DIA:
    from .ops import zip_ as _z
    return _z.ZipWindowOp(list(dias), window, zip_fn, device_fn)


def Merge(*dias: DIA, key_fn: Callable = None) -> DIA:
    from .ops import merge as _m
    return _m.Merge(list(dias), key_fn)


def Concat(*dias: DIA) -> DIA:
    from .ops import concat as _c
    return _c.ConcatMany(list(dias))


def Union(*dias: DIA) -> DIA:
    from .ops import union as _u
    return _u.UnionMany(list(dias))


def InnerJoin(left: DIA, right: DIA, left_key_fn: Callable,
              right_key_fn: Callable, join_fn: Callable,
              location_detection=None,
              out_size_hint=None, dense_right_index=None) -> DIA:
    """``location_detection`` (reference: LocationDetectionTag) prunes
    items whose key exists on only one side before the shuffle, on
    both the device path (presence-register filter) and the host path
    (Golomb fingerprint exchange). None — the default — defers to the
    plan-time cost model (core/preshuffle.py, forced either way with
    THRILL_TPU_LOCATION_DETECT=0/1); True/False force it per call.
    ``out_size_hint``: optional per-worker match-count upper bound —
    the device path then skips its blocking size sync (overflow raises
    at the next host fetch, never silently truncates).
    ``dense_right_index=n``: the right side is a dense index table
    (row at global position g has key g, n rows total) — the join runs
    as a pure device gather, no sort/exchange/sync at any W."""
    from .ops import join as _j
    return _j.InnerJoin(left, right, left_key_fn, right_key_fn, join_fn,
                        location_detection=location_detection,
                        out_size_hint=out_size_hint,
                        dense_right_index=dense_right_index)
