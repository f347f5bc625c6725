"""Distributed item storage: the DIA data plane.

The reference stores DIA data as serialized byte Blocks in a BlockPool
with spill-to-disk (reference: thrill/data/block.hpp:52,
block_pool.hpp:42, file.hpp:56). The TPU-native design replaces
serialized row storage with **columnar struct-of-arrays**: a pytree of
arrays with leading shape ``[W, cap]`` sharded over the worker mesh axis,
plus per-worker valid-item counts. Static ``cap`` keeps XLA shapes
static; ragged per-worker sizes (the essence of DIA partitions, e.g.
after Filter) live in the counts.

Two storage classes implement one concept:

* ``DeviceShards`` — HBM-resident columnar blocks (the hot path).
* ``HostShards``   — per-worker Python lists for arbitrary objects
  (strings, tuples of variable length...), the analog of the
  reference's host-side serialized Files.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..common.config import round_up, round_up_pow2
from ..parallel.mesh import MeshExec
from ..common.partition import dense_range_bounds


def resplit_leaves(per_worker_leaves: List[List[np.ndarray]],
                   new_w: int) -> List[List[np.ndarray]]:
    """Re-split per-worker leaf lists across a NEW worker count: the
    concatenation (old worker-rank order) sliced by
    ``dense_range_bounds(total, new_w)`` — exactly the layout a fresh
    ``new_w``-wide run of the same pipeline would produce, which is
    what keeps a resized mesh's results bit-identical to a fixed-W
    run (api/checkpoint.py repartition)."""
    if not per_worker_leaves:
        return [[] for _ in range(new_w)]
    nleaves = len(per_worker_leaves[0])
    merged = [np.concatenate([pw[i] for pw in per_worker_leaves],
                             axis=0)
              for i in range(nleaves)]
    n = merged[0].shape[0] if merged else 0
    bounds = dense_range_bounds(n, new_w).tolist()
    return [[leaf[bounds[w]:bounds[w + 1]] for leaf in merged]
            for w in range(new_w)]


def tree_leaves(tree):
    return jax.tree.leaves(tree)


def columnarize(items, treedef):
    """List of fixed-shape pytree items -> one pytree of stacked
    columns. Flattens each item once (shared by HostShards.to_device
    and the multi-controller multiplexer.host_to_device)."""
    flat = [jax.tree.leaves(it) for it in items]
    cols = [np.asarray([f[i] for f in flat])
            for i in range(treedef.num_leaves)]
    return jax.tree.unflatten(treedef, cols)


def itemize(tree) -> list:
    """Columnar pytree -> list of per-item trees, with scalar (1-D)
    columns unboxed to native Python scalars and bare-leaf items
    unwrapped. THE unboxing used everywhere device columns become host
    items (to_host_shards, the GroupByKey radix path) — item types must
    not depend on which engine materialized them."""
    leaves, treedef = jax.tree.flatten(tree)
    if not leaves:
        return []
    # columnar slices: one tolist()/list() per leaf, not one python
    # round trip per item per leaf
    cols = [leaf.tolist() if leaf.ndim == 1 else list(leaf)
            for leaf in leaves]
    if treedef == jax.tree.structure(0):
        return cols[0]
    return [jax.tree.unflatten(treedef, vals) for vals in zip(*cols)]


def tree_map(fn, *trees):
    return jax.tree.map(fn, *trees)


class DeviceShards:
    """Columnar device storage: leaves [W, cap, ...], sharded on axis 0.

    Per-worker valid counts live in EITHER form and convert lazily:

    * host (numpy [W] int64) — needed by plan steps (exchange sizing,
      splitters, action results);
    * device (sharded [W, 1] int32, a program output) — enough to feed
      the next jitted program.

    A chain of device operators therefore never blocks on a
    device->host counts fetch between programs: jax's async dispatch
    keeps the device running ahead, and the host syncs only where a
    plan genuinely needs the numbers (the analog of the reference's
    overlapped post-phase thread, api/reduce_by_key.hpp:142-168).
    """

    def __init__(self, mesh_exec: MeshExec, tree: Any, counts) -> None:
        self.mesh_exec = mesh_exec
        self.tree = tree
        if isinstance(counts, np.ndarray):
            self._counts_host: Optional[np.ndarray] = counts
            self._counts_dev = None
        else:
            self._counts_host = None
            self._counts_dev = counts          # sharded [W, 1] int32
        # optional deferred validation run when lazy device counts are
        # first realized on the host (e.g. InnerJoin out_size_hint
        # overflow detection — the op skipped its blocking size sync
        # and owes the check at the next natural host realization)
        self._counts_check: Optional[Callable[[np.ndarray], None]] = None

    @property
    def counts(self) -> np.ndarray:
        """Host counts; fetches (and caches) from device on first use."""
        if self._counts_host is None:
            counts = self.mesh_exec.fetch(
                self._counts_dev).reshape(-1).astype(np.int64)
            if self._counts_check is not None:
                # validate BEFORE caching: if the check raises (sticky
                # overflow), the next access re-validates instead of
                # silently serving truncated counts. A RECOVERING check
                # (hinted-join lineage retry) heals self.tree in place
                # and may return REPLACEMENT counts (a fused-chain
                # recovery recomputes downstream counts too).
                fixed = self._counts_check(counts)
                self._counts_check = None
                if fixed is not None:
                    counts = fixed
            self._counts_host = counts
        return self._counts_host

    def validate_pending(self) -> None:
        """Run a deferred counts check NOW (no-op without one).

        Called by the stage driver when these shards flow into a
        downstream operator (api/dia_base.py ParentLink.pull): a
        hinted-join overflow must be detected — and recovered — BEFORE
        any consumer bakes truncated columns into its own program. The
        transfer rides ``_fetch_raw`` (untracked): the producing op
        started it asynchronously at compute time, so by pull time it
        usually only confirms an already-landed host copy instead of
        stalling the dispatch stream like a plan sync would.
        """
        if self._counts_check is None:
            return
        if self._counts_host is not None:
            counts = self._counts_host
        else:
            counts = self.mesh_exec._fetch_raw(
                self._counts_dev).reshape(-1).astype(np.int64)
        fixed = self._counts_check(counts)  # sticky: stays set on raise
        self._counts_check = None
        if fixed is not None:
            self._counts_host = fixed
        elif self._counts_host is None:
            self._counts_host = counts

    @property
    def num_workers(self) -> int:
        return self.mesh_exec.num_workers

    @property
    def cap(self) -> int:
        return tree_leaves(self.tree)[0].shape[1]

    @property
    def total(self) -> int:
        return int(self.counts.sum())

    def counts_device(self) -> jax.Array:
        """Counts as a sharded [W, 1] device array (one scalar per
        shard); cached so repeated programs reuse one transfer."""
        if self._counts_dev is None:
            self._counts_dev = self.mesh_exec.put_small(
                self.counts.astype(np.int32)[:, None])
        return self._counts_dev

    # -- conversion -----------------------------------------------------
    @staticmethod
    def _put_staged(mesh_exec: MeshExec, stage: Callable, trees: Sequence[Any],
                    counts: np.ndarray) -> "DeviceShards":
        """Upload the ``[W, cap, ...]`` host tree that ``stage(*leaves)
        -> (array, lent)`` makes of corresponding leaves of ``trees``.

        ``lent`` says the array is the caller's own memory under
        another shape (nothing was written). jax keeps reading a numpy
        argument after ``device_put`` has returned, so a stage that
        lent memory waits for its uploads: the caller's array is taken
        as it stands while the stage runs and is the caller's again
        when it ends. The CPU client alone never copies a 64-byte
        aligned array (the device buffer IS that memory for good), so
        there such a leaf is copied after all. ``stage_copy_bytes``
        (and ``copied_bytes`` on the covering ``stage`` span) count
        the bytes of the arrays written here; a lent one counts 0."""
        copied = 0
        any_lent = False

        def one(*leaves):
            nonlocal copied, any_lent
            staged, lent = stage(*leaves)
            if lent and mesh_exec.keeps_host_memory(staged):
                staged, lent = np.array(staged, order="C"), False
            if lent:
                any_lent = True
            else:
                copied += staged.nbytes
            return staged

        host_tree = tree_map(one, *trees)
        mesh_exec.stats_stage_copy_bytes += copied
        tracer = mesh_exec.tracer
        if tracer is not None:
            tracer.add_to_open("stage", "copied_bytes", copied)
        dev_tree = mesh_exec.put_tree(host_tree)
        if any_lent:
            mesh_exec.wait_uploaded(dev_tree)
        return DeviceShards(mesh_exec, dev_tree, counts)

    @staticmethod
    def from_worker_arrays(mesh_exec: MeshExec, per_worker: Sequence[Any],
                           cap: int = 0,
                           counts: Optional[np.ndarray] = None
                           ) -> "DeviceShards":
        """Build from W per-worker pytrees of numpy arrays (item axis 0).

        ``counts`` overrides the per-worker lengths (multi-controller
        builds pass globally agreed counts while supplying data only
        for the workers this process owns).

        Staging, per leaf: one worker whose leaf fills ``cap`` is
        uploaded as a view of that leaf; anything else is written once
        into a zeroed ``[W, cap, ...]`` buffer. Either way the leaves
        are read while this call runs and never afterwards
        (:meth:`_put_staged`)."""
        W = mesh_exec.num_workers
        assert len(per_worker) == W
        if counts is None:
            counts = np.array(
                [np.shape(tree_leaves(t)[0])[0] if tree_leaves(t) else 0
                 for t in per_worker], dtype=np.int64)
        if cap <= 0:
            cap = max(1, round_up_pow2(int(counts.max()) if len(counts) else 1))

        def stage(*leaves):
            leaves = [np.asarray(leaf) for leaf in leaves]
            # by the leaf's own length, never by counts: a process may
            # hold no rows of a worker whose count it knows
            if W == 1 and leaves[0].shape[0] == cap:
                return leaves[0][None], True
            buf = np.zeros((W, cap) + leaves[0].shape[1:],
                           np.result_type(*leaves))
            for w, leaf in enumerate(leaves):
                buf[w, :leaf.shape[0]] = leaf
            return buf, False

        return DeviceShards._put_staged(mesh_exec, stage, per_worker, counts)

    @staticmethod
    def from_global_numpy(mesh_exec: MeshExec, tree: Any) -> "DeviceShards":
        """Evenly range-split one global pytree (item axis 0) across workers.

        Leaves that are ALREADY device arrays (single-controller) split
        on device for any n/W: one eager gather per leaf, all async —
        no device->host round trip. An iterative driver can therefore
        feed an ``AllGatherArrays`` result (or any eager jnp math on
        it) straight back into ``Distribute`` without leaving jax's
        dispatch stream (the suffix-sorting doubling loop pattern).

        Staging of host leaves: where the split is exact and pads
        nothing (``n == W * cap``) a leaf is uploaded as its own
        ``[W, cap, ...]`` reshape, a view whatever its strides
        (splitting axis 0 needs no copy); otherwise its rows are written once
        (:meth:`from_worker_arrays`). Either way the leaves are read
        while this call runs and never afterwards: the caller may
        overwrite them as soon as it returns."""
        W = mesh_exec.num_workers
        leaves = tree_leaves(tree)
        n = leaves[0].shape[0] if leaves else 0
        all_device = bool(leaves) and all(
            isinstance(l, jax.Array) for l in leaves) and \
            getattr(mesh_exec, "num_processes", 1) == 1
        bnd = dense_range_bounds(n, W)
        counts = np.diff(bnd)
        cap = max(1, round_up_pow2(int(counts.max())))
        if all_device and n > 0:
            # device-side split for ANY n/W: one eager gather per leaf
            # builds the [W, cap] layout (rows past each worker's count
            # repeat row n-1 — masked by counts like all pad rows).
            # Validity counts are host-known (n is), so no sync.
            idx = jnp.asarray(np.minimum(
                np.arange(cap)[None, :] + bnd[:W, None], n - 1
            ).reshape(-1))

            def place(leaf):
                arr = jnp.take(leaf, idx, axis=0).reshape(
                    (W, cap) + leaf.shape[1:])
                return jax.device_put(arr, mesh_exec.sharded)

            return DeviceShards(mesh_exec, tree_map(place, tree), counts)
        if n == W * cap:
            def stage(leaf):
                leaf = np.asarray(leaf)
                staged = leaf.reshape((W, cap) + leaf.shape[1:])
                return staged, np.may_share_memory(staged, leaf)

            return DeviceShards._put_staged(mesh_exec, stage, [tree], counts)
        bounds = bnd.tolist()
        per_worker = [tree_map(lambda a: np.asarray(a)[bounds[w]:bounds[w + 1]], tree)
                      for w in range(W)]
        return DeviceShards.from_worker_arrays(mesh_exec, per_worker)

    def to_worker_arrays(self, local_only: bool = False) -> List[Any]:
        """Fetch to host: W pytrees of numpy arrays trimmed to counts.

        ``local_only`` (multi-controller): read only this process's
        addressable device shards — no cross-process allgather of the
        bulk data — and return ``None`` for non-local workers."""
        # deferred producer validation BEFORE the bulk fetch: a
        # recovering check swaps self.tree, and fetching first would
        # materialize the pre-recovery columns
        self.validate_pending()
        if local_only and getattr(self.mesh_exec, "num_processes", 1) > 1:
            return self._local_worker_arrays()
        host_tree = self.mesh_exec.fetch_tree(self.tree)
        out = []
        for w in range(self.num_workers):
            c = int(self.counts[w])
            out.append(tree_map(lambda a: a[w, :c], host_tree))
        return out

    def _local_worker_arrays(self) -> List[Any]:
        """Per-worker arrays from addressable shards only (None for
        workers owned by other processes)."""
        leaves, treedef = jax.tree.flatten(self.tree)
        per_leaf: List[dict] = []
        for leaf in leaves:
            m: dict = {}
            for sh in leaf.addressable_shards:
                w0 = sh.index[0].start or 0
                data = np.asarray(sh.data)
                for i in range(data.shape[0]):
                    m[w0 + i] = data[i]
            per_leaf.append(m)
        out: List[Any] = []
        local = set(per_leaf[0]) if per_leaf else set(
            getattr(self.mesh_exec, "local_workers", []))
        for w in range(self.num_workers):
            if w not in local:
                out.append(None)
                continue
            c = int(self.counts[w])
            out.append(jax.tree.unflatten(
                treedef, [pl[w][:c] for pl in per_leaf]))
        return out

    def to_global_numpy(self) -> Any:
        """Concatenate all workers' valid items in worker-rank order."""
        per_worker = self.to_worker_arrays()
        return tree_map(lambda *leaves: np.concatenate(leaves, axis=0),
                        *per_worker)

    def to_host_shards(self, reason: str = "unspecified") -> "HostShards":
        """Itemize into per-worker Python lists (scalars unboxed).

        This is a device->host DEMOTION: the pipeline leaves columnar
        device storage and continues at Python speed. Every demotion is
        logged (``reason`` says which operator path forced it) so users
        can see why a "device" pipeline slowed down.
        """
        log = getattr(self.mesh_exec, "logger", None)
        if log is not None and log.enabled:
            log.line(event="device_to_host", reason=reason,
                     items=int(self.counts.sum()))
        lists: List[List[Any]] = []
        # multi-controller: materialize only this process's workers
        # (the host-storage invariant, data/multiplexer.py) — the bulk
        # data never crosses processes on a demotion
        for tree in self.to_worker_arrays(local_only=True):
            lists.append([] if tree is None else itemize(tree))
        return HostShards(self.num_workers, lists)


@dataclasses.dataclass
class HostShards:
    """Per-worker Python item lists (the generic fallback storage)."""

    num_workers: int
    lists: List[List[Any]]

    @property
    def counts(self) -> np.ndarray:
        return np.array([len(l) for l in self.lists], dtype=np.int64)

    @property
    def total(self) -> int:
        return sum(len(l) for l in self.lists)

    def validate_pending(self) -> None:
        """Host storage carries no deferred device validations; the
        no-op keeps the fused-boundary contract uniform (a plan's
        memory-pressure host fallback returns HostShards through
        ``FusionPlan.finish``, which validates unconditionally)."""

    def repartition(self, new_w: int) -> "HostShards":
        """Re-split the items across ``new_w`` workers by the dense
        range layout (concatenate in worker-rank order, slice by
        ``dense_range_bounds`` — the same split every layout site
        uses, common/partition.py)."""
        merged: List[Any] = []
        for items in self.lists:
            merged.extend(items)
        bounds = dense_range_bounds(len(merged), new_w).tolist()
        return HostShards(new_w,
                          [merged[bounds[w]:bounds[w + 1]]
                           for w in range(new_w)])

    def to_device(self, mesh_exec: MeshExec) -> DeviceShards:
        """Columnarize (requires items be fixed-shape pytrees of numbers)."""
        if getattr(mesh_exec, "num_processes", 1) > 1:
            # capacity/counts/schema must be agreed across controllers
            from . import multiplexer
            if multiplexer.multiprocess(mesh_exec):
                return multiplexer.host_to_device(mesh_exec, self)
        per_worker = []
        for items in self.lists:
            if items:
                per_worker.append(columnarize(
                    items, jax.tree.structure(items[0])))
            else:
                per_worker.append(None)
        # empty workers: borrow structure from a non-empty one
        template = next((t for t in per_worker if t is not None), None)
        if template is None:
            raise ValueError("cannot infer schema of an entirely empty DIA")
        empty = tree_map(lambda a: a[:0], template)
        per_worker = [t if t is not None else empty for t in per_worker]
        return DeviceShards.from_worker_arrays(mesh_exec, per_worker)


@jax.named_scope("compact")
def compact_valid(tree, mask):
    """Inside-jit compaction: move valid items to the front, stably.

    tree leaves: [n, ...]; mask: [n] bool. Returns (tree, count).
    O(n) cumsum + scatter (invalid items land in a dropped overflow
    slot) — cheaper than a sort and independent of the sort lowering.
    """
    n = mask.shape[0]
    pos = jnp.where(mask, jnp.cumsum(mask.astype(jnp.int32)) - 1, n)

    def scatter(leaf):
        buf = jnp.zeros((n + 1,) + leaf.shape[1:], leaf.dtype)
        return buf.at[pos].set(leaf)[:n]

    out = tree_map(scatter, tree)
    return out, jnp.sum(mask.astype(jnp.int32))
