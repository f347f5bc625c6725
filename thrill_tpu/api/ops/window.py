"""Window / FlatWindow / DisjointWindow.

Reference: thrill/api/window.hpp:32 — overlapping k-windows fetch the
k-1 predecessor items from the previous worker via
FlowControlChannel::Predecessor (net/flow_control_channel.hpp:653).

Device path: the predecessor fetch is a **ppermute halo exchange** over
the mesh axis — each worker passes its last k-1 items to its successor,
the 1-D sharded-sequence pattern that generalizes to ring-style
sequence parallelism (this is where the long-context halo primitive
lives in this framework). Window functions are applied batched over
[n_windows, k] stacks; DisjointWindow is the same machinery with a
start-alignment mask, and FlatWindow uses the FlatMap contract (a
static output factor + validity mask). Workers with fewer than k-1
items (rare, tiny inputs) fall back to the host path.

**How the [cap, k] windows are built.** The worker's rows and the halo
make one array ``ext`` of cap + k - 1 rows, and slot ``t`` of every
window is the static slice ``ext[t : t + cap]``: k slices stacked
(``_stack_windows``), so a k = 2 window is two slices of ``ext``, which
the compiler fuses into the window function's own pass. Not a gather:
``jnp.take(ext, j + t)`` pays per ROW (6.6-7 ns on a v5e, 55 ms a leaf
at 2^22 rows and k = 2), a slice runs at copy speed (PERF.md, PR 33).
The device operations carry the named scope ``window``.

**Padded windows** (``Window(..., pad=True)``). The sequence is read as
continued by k - 1 zero items past its end, and window ``j`` holds
items ``j .. j + k - 1``: n windows for n items, the last k - 1 of them
reaching into the padding. This is what Thrill's users spell with a
``FlatWindow`` that emits the tail's items from the last full window
(examples/suffix_sorting/prefix_doubling.cpp: the k-mers of the last
k - 1 positions, ``rank2 = 0`` past the end); a static-shape program
cannot emit a variable number, but it can pad. A pad item is the item
type's zero, so the window function tells it by content (an index
column that is never 0 behind a row, a rank that starts at 1). Output
row ``j`` belongs to input row ``j``: per-worker counts are preserved
and nothing is compacted. The halo runs the other way (each worker
passes its FIRST k-1 items to its predecessor), so every worker but
the first needs k-1 items, else the host path.

A ``Window``'s ``device_fn`` may be a
:class:`~thrill_tpu.api.stack.Bind`: its operands enter the program as
replicated arguments, so a value that changes from call to call (prefix
doubling's ``h``) re-binds and never recompiles.
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from ...data.shards import DeviceShards, HostShards, compact_valid
from ...parallel.mesh import AXIS
from ..dia import DIA
from ..dia_base import DIABase
from ..stack import Bind
from ...common.partition import dense_range_bounds

# HLO metadata only (jax.named_scope adds no operation): a device
# profile tells the windows' slices, halos and the window function's
# own operations by this name in their op_name
SCOPE = "window"


def _stack_windows(ext, cap: int, k: int):
    """[cap, k, ...] windows over ``ext``'s rows ``j .. j + k - 1``:
    k static slices, stacked (the module's docstring says why not a
    gather)."""
    return jnp.stack([lax.slice_in_dim(ext, t, t + cap, axis=0)
                      for t in range(k)], axis=1)


def _device_windows(tree, cap, count, off, k, W):
    """Traced helper: halo exchange + batched [cap, k, ...] windows.

    Window j ends at local item j (covers global positions
    off+j-(k-1) .. off+j); the k-1 halo items come from the predecessor
    worker via a ppermute ring step. Returns (windows_tree, ends_valid,
    g_start) where ends_valid marks windows whose full extent exists.
    """
    def halo_of(leaf):
        idx = jnp.clip(count - (k - 1) + jnp.arange(k - 1), 0, cap - 1)
        h = jnp.take(leaf, idx, axis=0)
        perm = [(i, i + 1) for i in range(W - 1)]
        return lax.ppermute(h, AXIS, perm) if W > 1 else \
            jnp.zeros_like(h)

    halo = jax.tree.map(halo_of, tree)
    ext = jax.tree.map(lambda h, x: jnp.concatenate([h, x], axis=0),
                       halo, tree)
    windows = jax.tree.map(lambda e: _stack_windows(e, cap, k), ext)
    g_end = off + jnp.arange(cap, dtype=jnp.int64)
    ends_valid = (jnp.arange(cap) < count) & (g_end >= k - 1)
    g_start = g_end - (k - 1)
    return windows, ends_valid, g_start


def _padded_windows(tree, cap, count, k, W):
    """Traced helper for ``pad=True``: window ``j`` holds global items
    ``j .. j + k - 1``, zeros past the end of the sequence. The k-1
    successors of a worker's last items are the FIRST k-1 items of the
    next worker (a ppermute ring step the other way round; the last
    worker receives zeros, which is the padding). Rows past ``count``
    are zeroed first, so the halo can be written at ``count`` and what
    lies behind it reads as padding too."""
    def ext_of(leaf):
        m = (jnp.arange(cap) < count).reshape(
            (cap,) + (1,) * (leaf.ndim - 1))
        ext = jnp.concatenate(
            [jnp.where(m, leaf, 0),
             jnp.zeros((k - 1,) + leaf.shape[1:], leaf.dtype)], axis=0)
        if W > 1:
            halo = lax.ppermute(ext[:k - 1], AXIS,
                                [(i + 1, i) for i in range(W - 1)])
            ext = lax.dynamic_update_slice_in_dim(ext, halo, count,
                                                  axis=0)
        return _stack_windows(ext, cap, k)

    return jax.tree.map(ext_of, tree)


def _fn_token(fn):
    """What a device function adds to a program's cache key: the
    function, or for a ``Bind`` the function and its operands' shapes
    (never their values)."""
    return fn.cache_token() if isinstance(fn, Bind) else fn


def _fn_bound(fn) -> tuple:
    """The runtime operands of a device function, as the one entry of
    a segment's ``bound`` (empty without a ``Bind``)."""
    return (fn.operands,) if isinstance(fn, Bind) else ()


def _call_fn(fn, windows, bound_t):
    """``fn(windows)``, a ``Bind``'s with the TRACED operands."""
    if isinstance(fn, Bind):
        return fn.fn(windows, *bound_t[0])
    return fn(windows)


def _windowed_device_program(shards: DeviceShards, k: int, cache_tag,
                             make_output, bound=(), pad: bool = False):
    """Shared driver for all windowed device ops: one jitted program
    building halo windows, applying ``make_output(windows, ends_valid,
    g_start, bound_t) -> (out_tree, keep_mask)`` and compacting the
    kept rows. ``bound`` enters as replicated arguments. ``pad``: the
    padded windows, whose output rows are the input rows' (the counts
    stand, nothing is compacted)."""
    mex = shards.mesh_exec
    W = mex.num_workers
    cap = shards.cap
    offsets = np.concatenate([[0], np.cumsum(shards.counts)])[:-1]
    leaves, treedef = jax.tree.flatten(shards.tree)
    b_leaves, b_def = jax.tree.flatten(bound)
    b_leaves = mex.asarray_blessed(b_leaves)
    key = ("windowed",) + tuple(cache_tag) + (
        k, cap, pad, treedef,
        tuple((l.dtype, l.shape[2:]) for l in leaves))
    holder = {}
    nd = 2 + len(leaves)

    def build():
        def f(counts_dev, off_dev, *ls):
            count = counts_dev[0, 0]
            off = off_dev[0, 0]
            tree = jax.tree.unflatten(treedef,
                                      [l[0] for l in ls[:len(leaves)]])
            bound_t = jax.tree.unflatten(b_def, list(ls[len(leaves):]))
            with jax.named_scope(SCOPE):
                if pad:
                    windows = _padded_windows(tree, cap, count, k, W)
                    valid = jnp.arange(cap) < count
                    out, _keep = make_output(windows, valid, None,
                                             bound_t)
                    cnt = count
                else:
                    windows, valid, g_start = _device_windows(
                        tree, cap, count, off, k, W)
                    out_tree, keep = make_output(windows, valid,
                                                 g_start, bound_t)
                    out, cnt = compact_valid(out_tree, keep)
            out_leaves, out_td = jax.tree.flatten(out)
            holder["treedef"] = out_td
            return (cnt[None, None].astype(jnp.int32),
                    *[l[None] for l in out_leaves])

        return mex.smap(
            f, nd + len(b_leaves),
            in_specs=(P(AXIS),) * nd + (P(),) * len(b_leaves)), holder

    f, h = mex.cached(key, build)
    out = f(shards.counts_device(),
            mex.put_small(offsets.astype(np.int64)[:, None]), *leaves,
            *b_leaves)
    tree = jax.tree.unflatten(h["treedef"], list(out[1:]))
    return DeviceShards(mex, tree,
                        shards.counts.copy() if pad else out[0])


def _halo_ok(counts: np.ndarray, k: int, pad: bool = False) -> bool:
    """Does every worker that must lend k-1 items hold them? All but
    the last lend to their successor; under ``pad`` all but the first
    lend to their predecessor."""
    lenders = counts[1:] if pad else counts[:-1]
    return bool(np.all(lenders >= k - 1))


def _fused_window_plan(node, pad: bool = False):
    """Shared Window/FlatWindow fusion gate: the halo eligibility check
    (``_halo_ok``) needs host counts, so the op fuses only when the
    pending chain provably keeps KNOWN counts of rows that are a prefix
    (``FusionPlan.rows_are_a_known_prefix``: behind a source or a Zip);
    anything else finishes the chain and takes the per-op path."""
    from .. import fusion
    plan = fusion.pull_plan(node.parents[0])
    if plan.rows_are_a_known_prefix() \
            and _halo_ok(plan.known_counts, node.k, pad):
        plan.append(node._fuse_segment())
        return plan
    return fusion.wrap(node._compute_on(plan.finish()))


def _zero_item(item):
    """The item type's zero, for the host path's padding."""
    return jax.tree.map(lambda x: np.zeros_like(np.asarray(x))[()], item)


class WindowNode(DIABase):
    def __init__(self, ctx, link, k: int, fn: Optional[Callable],
                 device_fn: Optional[Callable], disjoint: bool,
                 partial_fn: Optional[Callable] = None,
                 pad: bool = False) -> None:
        super().__init__(ctx, "DisjointWindow" if disjoint else "Window",
                         [link])
        self.k = int(k)
        self.fn = fn
        self.device_fn = device_fn
        self.disjoint = disjoint
        self.pad = bool(pad)
        if self.pad and disjoint:
            raise ValueError(
                "pad=True is the sliding Window's (DisjointWindow has "
                "partial_fn for its trailing block)")
        # reference: DisjointWindow delivers the trailing (< k) block
        # to a separate partial_window_function (api/window.hpp:389);
        # its dynamic length keeps it on the host path
        if partial_fn is not None and not disjoint:
            raise ValueError(
                "partial_fn only applies to DisjointWindow (the sliding "
                "Window has no trailing partial block)")
        self.partial_fn = partial_fn

    def _fuse_segment(self):
        from .. import fusion
        k = self.k
        disjoint = self.disjoint
        pad = self.pad
        fn = self.device_fn
        W = self.context.num_workers

        def trace(fctx, tree, mask, bound_t):
            cap = mask.shape[0]
            count = jnp.sum(mask.astype(jnp.int32))
            with jax.named_scope(SCOPE):
                if pad:
                    windows = _padded_windows(tree, cap, count, k, W)
                    return (_call_fn(fn, windows, bound_t),
                            jnp.arange(cap) < count)
                off = fctx.exclusive_offset(mask)
                windows, valid, g_start = _device_windows(
                    tree, cap, count, off, k, W)
                if disjoint:
                    valid = valid & (g_start % k == 0)
                return _call_fn(fn, windows, bound_t), valid

        return fusion.Segment(label=self.label,
                              token=("window_fused", _fn_token(fn),
                                     disjoint, k, pad),
                              trace=trace, bound=_fn_bound(fn),
                              preserves_counts=pad, already_compact=pad,
                              dia_id=self.id)

    def compute_plan(self):
        if self.device_fn is None or self.partial_fn is not None:
            return None
        return _fused_window_plan(self, self.pad)

    def compute(self):
        plan = self.compute_plan()
        if plan is not None:
            return plan.finish()
        return self._compute_on(self.parents[0].pull())

    def _compute_on(self, shards):
        k = self.k
        if isinstance(shards, DeviceShards) and self.device_fn is not None \
                and self.partial_fn is None \
                and _halo_ok(shards.counts, k, self.pad):
            return self._compute_device(shards)
        if self.fn is None:
            raise ValueError(
                f"{self.label} fell back to the host path (host storage, "
                f"a worker with fewer than k-1 items, or partial_fn — "
                f"which is host-only) but no host fn was given — pass fn "
                f"alongside device_fn")
        if isinstance(shards, DeviceShards):
            shards = shards.to_host_shards("window-host-fn")
        return self._compute_host(shards)

    def _compute_host(self, shards: HostShards):
        k = self.k
        fn = self.fn
        from ...data import multiplexer
        mex = self.context.mesh_exec
        shards = multiplexer.ensure_replicated(mex, shards, "window-host")
        flat = [it for l in shards.lists for it in l]
        if self.disjoint:
            wins = [flat[i:i + k] for i in range(0, len(flat) - k + 1, k)]
        elif self.pad and flat:
            ext = flat + [_zero_item(flat[0])] * (k - 1)
            wins = [ext[i:i + k] for i in range(len(flat))]
        else:
            wins = [flat[i:i + k] for i in range(len(flat) - k + 1)]
        out = [fn(i * (k if self.disjoint else 1), w)
               for i, w in enumerate(wins)]
        if self.disjoint and self.partial_fn is not None \
                and len(flat) % k:
            rest = flat[len(flat) - len(flat) % k:]
            out.append(self.partial_fn(len(flat) - len(rest), rest))
        W = shards.num_workers
        bounds = dense_range_bounds(len(out), W).tolist()
        return multiplexer.localize(
            mex, HostShards(W, [out[bounds[w]:bounds[w + 1]]
                                for w in range(W)]))

    def _compute_device(self, shards: DeviceShards):
        k = self.k
        disjoint = self.disjoint
        fn = self.device_fn

        def make_output(windows, valid, g_start, bound_t):
            if disjoint:
                # keep only windows aligned to a k boundary
                valid = valid & (g_start % k == 0)
            return _call_fn(fn, windows, bound_t), valid  # [cap, ...]

        return _windowed_device_program(
            shards, k, ("window_dev", _fn_token(fn), disjoint),
            make_output, bound=_fn_bound(fn), pad=self.pad)


class FlatWindowNode(DIABase):
    """fn(index, window) -> iterable of outputs.

    Device path (``device_fn`` + ``factor``): like FlatMap's device
    contract — ``device_fn(windows)`` receives the batched
    [cap, k, ...] window tree and returns ``(outputs, mask)`` where
    outputs' leaves are [cap, factor, ...] and mask is [cap, factor]
    bool (which of each window's factor slots are real). Windows whose
    extent is incomplete are masked automatically.
    """

    def __init__(self, ctx, link, k: int, fn: Callable,
                 device_fn: Optional[Callable] = None,
                 factor: int = 0) -> None:
        super().__init__(ctx, "FlatWindow", [link])
        self.k = int(k)
        self.fn = fn
        self.device_fn = device_fn
        self.factor = int(factor)
        if device_fn is not None and self.factor <= 0:
            raise ValueError(
                "FlatWindow device_fn requires factor > 0 (static "
                "outputs per window)")
        if fn is None and device_fn is None:
            raise ValueError("FlatWindow needs fn and/or device_fn")

    def _fuse_segment(self):
        from .. import fusion
        k = self.k
        factor = self.factor
        fn = self.device_fn
        W = self.context.num_workers

        def trace(fctx, tree, mask, _bound):
            cap = mask.shape[0]
            count = jnp.sum(mask.astype(jnp.int32))
            off = fctx.exclusive_offset(mask)
            with jax.named_scope(SCOPE):
                windows, valid, g_start = _device_windows(
                    tree, cap, count, off, k, W)
                out, fmask = fn(windows)         # [cap, factor, ...]
                flat_tree = jax.tree.map(
                    lambda l: l.reshape((cap * factor,) + l.shape[2:]),
                    out)
                return flat_tree, (valid[:, None] & fmask).reshape(-1)

        return fusion.Segment(label="FlatWindow",
                              token=("flatwindow_fused", fn, factor, k),
                              trace=trace, dia_id=self.id)

    def compute_plan(self):
        if self.device_fn is None or self.factor <= 0:
            return None
        return _fused_window_plan(self)

    def compute(self):
        plan = self.compute_plan()
        if plan is not None:
            return plan.finish()
        return self._compute_on(self.parents[0].pull())

    def _compute_on(self, shards):
        k = self.k
        if isinstance(shards, DeviceShards) and self.device_fn is not None \
                and self.factor > 0 \
                and _halo_ok(shards.counts, k):
            return self._compute_device(shards)
        if self.fn is None:
            raise ValueError(
                "FlatWindow fell back to the host path (host storage "
                "or a worker with fewer than k-1 items) but no host "
                "fn was given — pass fn alongside device_fn")
        if isinstance(shards, DeviceShards):
            shards = shards.to_host_shards("flatwindow")
        from ...data import multiplexer
        mex = self.context.mesh_exec
        shards = multiplexer.ensure_replicated(mex, shards,
                                               "flatwindow-host")
        flat = [it for l in shards.lists for it in l]
        out = []
        for i in range(len(flat) - self.k + 1):
            out.extend(self.fn(i, flat[i:i + self.k]))
        W = shards.num_workers
        bounds = dense_range_bounds(len(out), W).tolist()
        return multiplexer.localize(
            mex, HostShards(W, [out[bounds[w]:bounds[w + 1]]
                                for w in range(W)]))

    def _compute_device(self, shards: DeviceShards):
        k = self.k
        factor = self.factor
        fn = self.device_fn

        def make_output(windows, valid, g_start, _bound):
            out, mask = fn(windows)          # [cap, factor, ...], mask
            cap = valid.shape[0]
            flat_tree = jax.tree.map(
                lambda l: l.reshape((cap * factor,) + l.shape[2:]), out)
            return flat_tree, (valid[:, None] & mask).reshape(-1)

        return _windowed_device_program(
            shards, k, ("flatwindow_dev", fn, factor), make_output)


def Window(dia: DIA, k: int, fn, device_fn=None, disjoint=False,
           partial_fn=None, pad=False) -> DIA:
    return DIA(WindowNode(dia.context, dia._link(), k, fn, device_fn,
                          disjoint, partial_fn=partial_fn, pad=pad))


def FlatWindow(dia: DIA, k: int, fn, device_fn=None, factor: int = 0
               ) -> DIA:
    return DIA(FlatWindowNode(dia.context, dia._link(), k, fn,
                              device_fn=device_fn, factor=factor))
