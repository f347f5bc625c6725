"""Actions: DAG sinks that trigger execution and return results.

Reference: thrill/api/size.hpp:28 (local count + AllReduce),
all_gather.hpp:28, gather.hpp:28, all_reduce.hpp:28, sum.hpp, min.hpp,
max.hpp, print.hpp. On the device path reductions run as one jitted
SPMD program (masked local fold + psum/pmax/pmin over the mesh axis) —
the analog of local fold + FlowControlChannel::AllReduce.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ...data import multiplexer
from ...data.shards import DeviceShards, HostShards
from ...parallel.mesh import AXIS
from ..dia_base import staged_action


def _pull(dia, consume: bool = True):
    return dia._link().pull(consume)


@staged_action
def Size(dia) -> int:
    shards = _pull(dia)
    if isinstance(shards, HostShards):
        return multiplexer.global_total(dia.context.mesh_exec, shards)
    return int(shards.counts.sum())


@staged_action
def AllGather(dia) -> list:
    shards = _pull(dia)
    if isinstance(shards, DeviceShards):
        shards = shards.to_host_shards("allgather-action")
    return multiplexer.all_items(dia.context.mesh_exec, shards)


@staged_action
def AllGatherArrays(dia):
    """Columnar egress: the DIA's items as ONE pytree of stacked
    arrays, leaves ``[total, ...]``. On the device path the leaves are
    DEVICE arrays assembled by async slicing — no host fetch, no
    per-item boxing — so an iterative driver (the k-means centroid
    update) can compute on the result and feed it straight back into
    the next ``Bind`` without ever leaving jax's dispatch stream.
    TPU-native extension: the reference's AllGather materializes a
    std::vector of items host-side (api/all_gather.hpp:28), a blocking
    device->host sync per iteration.

    Host-storage DIAs return numpy-stacked leaves (same tree shape);
    an EMPTY host-storage DIA returns ``[]`` (item structure is
    unknowable without items — the device path, whose columns carry
    their structure, returns zero-length leaves instead). Scalar items
    come back as a single stacked array."""
    shards = _pull(dia)
    mex = dia.context.mesh_exec
    # device-native egress never goes through mex.fetch on a single
    # controller: drain deferred validations here so a hinted-join
    # overflow can never ride out through columnar results
    mex.drain_checks()
    if isinstance(shards, HostShards):
        items = multiplexer.all_items(mex, shards)
        if not items:
            return items
        return jax.tree.map(lambda *ls: np.stack(ls), *items)
    counts = shards.counts               # host plan values (often known)
    W = len(counts)
    tree = shards.tree
    if multiplexer.multiprocess(mex):
        # leaves span non-addressable devices: realize on every
        # controller (numpy result — the zero-sync device contract
        # only holds single-controller)
        tree = jax.tree.map(mex.fetch, tree)

    leaves, treedef = jax.tree.flatten(tree)
    if mex.loop_recorder is not None and leaves \
            and all(isinstance(l, jax.Array) for l in leaves):
        # under an armed LoopPlan recorder (api/loop.py capture), run
        # the egress as ONE cached program (slice valid prefixes,
        # all_gather, concatenate): the whole action is then a
        # RECORDABLE dispatch, so iterative drivers that close their
        # loop through AllGatherArrays (k-means centroids) replay
        # device-resident. Outside a capture the eager slicing below
        # is equivalent (and compiles nothing), so dispatch budgets
        # are untouched. Keyed on the counts vector — static shapes;
        # loop-invariant counts compile once.
        from jax.sharding import PartitionSpec as P
        cap = shards.cap
        cnt = tuple(int(c) for c in counts)
        key = ("allgather_arrays", cap, cnt, treedef,
               tuple((l.dtype, l.shape[2:]) for l in leaves))

        def build():
            def f(*ls):
                outs = []
                for l in ls:
                    g = lax.all_gather(l[0], AXIS)      # [W, cap, ...]
                    parts = [g[w, :cnt[w]] for w in range(W) if cnt[w]]
                    outs.append(jnp.concatenate(parts, axis=0)
                                if parts else g[0, :0])
                return tuple(outs)

            return mex.smap(f, len(leaves), out_specs=P())

        fn = mex.cached(key, build)
        return jax.tree.unflatten(treedef, list(fn(*leaves)))

    def cat(leaf):
        parts = [leaf[w, :int(counts[w])] for w in range(W)
                 if int(counts[w])]
        if not parts:
            return leaf[0, :0]
        if len(parts) == 1:
            return parts[0]
        xp = np if isinstance(leaf, np.ndarray) else jnp
        return xp.concatenate(parts, axis=0)

    return jax.tree.map(cat, tree)


@staged_action
def Gather(dia, root: int = 0) -> list:
    """Items of the whole DIA, delivered to worker ``root`` only
    (reference: api/gather.hpp:28). Single-controller runs ARE every
    worker, so they receive the list; in multi-controller runs only the
    process hosting worker ``root`` gets the items — the others get []
    (the reference's non-root workers likewise emit nothing)."""
    shards = _pull(dia)
    mex = dia.context.mesh_exec
    mex.drain_checks()                   # egress: no unrun validations
    root = root % max(mex.num_workers, 1)
    if isinstance(shards, DeviceShards):
        shards = shards.to_host_shards("gather-action")
    if multiplexer.multiprocess(mex):
        owner = int(mex.worker_process[root])
        items = multiplexer.all_items(mex, shards)
        return items if owner == mex.process_index else []
    return [it for l in shards.lists for it in l]


def Print(dia, label: str = "", limit: int = 100) -> None:
    items = AllGather(dia)
    head = items[:limit]
    suffix = f" ... (+{len(items) - limit} more)" if len(items) > limit else ""
    print(f"[{label or 'DIA'}] n={len(items)}: {head}{suffix}")


def _device_reduce(shards: DeviceShards, mode: str,
                   keep_device: bool = False):
    """One SPMD program: masked local fold + cross-worker collective.

    ``keep_device``: return the reduced leaves as (replicated) DEVICE
    arrays with no host fetch — iterative drivers feed them straight
    back into a Bind (the SGD/logistic-regression update pattern)."""
    mex = shards.mesh_exec
    cap = shards.cap
    leaves, treedef = jax.tree.flatten(shards.tree)
    key = ("reduce_action", mode, cap, treedef,
           tuple((l.dtype, l.shape[2:]) for l in leaves))

    def build():
        def f(counts_dev, *ls):
            mask = jnp.arange(cap) < counts_dev[0, 0]
            outs = []
            for l in ls:
                x = l[0]
                m = mask.reshape(mask.shape + (1,) * (x.ndim - 1))
                if mode == "sum":
                    local = jnp.sum(jnp.where(m, x, 0), axis=0)
                    outs.append(lax.psum(local, AXIS))
                elif mode == "min":
                    big = _dtype_max(x.dtype)
                    local = jnp.min(jnp.where(m, x, big), axis=0)
                    outs.append(lax.pmin(local, AXIS))
                else:
                    small = _dtype_min(x.dtype)
                    local = jnp.max(jnp.where(m, x, small), axis=0)
                    outs.append(lax.pmax(local, AXIS))
            return tuple(outs)

        from jax.sharding import PartitionSpec as P
        return mex.smap(f, 1 + len(leaves), out_specs=P())

    fn = mex.cached(key, build)
    out = fn(shards.counts_device(), *leaves)
    if keep_device:
        return jax.tree.unflatten(treedef, list(out))
    vals = [mex.fetch(o) for o in out]
    vals = [v.item() if v.ndim == 0 else v for v in vals]
    return jax.tree.unflatten(treedef, vals)


def _dtype_max(dt):
    return jnp.inf if jnp.issubdtype(dt, jnp.floating) else jnp.iinfo(dt).max


def _dtype_min(dt):
    return -jnp.inf if jnp.issubdtype(dt, jnp.floating) else jnp.iinfo(dt).min


@staged_action
def Sum(dia, initial: Any = 0, device: bool = False) -> Any:
    """``device=True`` (device-storage DIAs): return the summed pytree
    as replicated DEVICE arrays, no host fetch — feed it straight back
    into a ``Bind`` (zero-sync iterative loops). Single-controller
    only by contract: on a multi-process mesh the request falls back
    to the fetched path (the device result would span non-addressable
    devices and fail confusingly under eager math / np.asarray)."""
    shards = _pull(dia)
    if device and multiplexer.multiprocess(dia.context.mesh_exec):
        device = False
    if device:
        # device-array egress bypasses mex.fetch: run deferred
        # validations before handing columns back to the caller
        dia.context.mesh_exec.drain_checks()
    if isinstance(shards, DeviceShards):
        # Single-controller with device-resident counts: SKIP the
        # empty-guard — forcing a counts sync here would stall
        # iterative loops (SGD's per-round sampled batch), and the
        # masked device reduce returns exact zeros for empty shards
        # anyway. Multi-controller keeps the eager guard: there the
        # counts fetch is a cheap collective the group performs in
        # lock-step, while skipping it costs far more (per-shape
        # reduce compiles + a process_allgather of the result for
        # sums that used to early-return — measured 7x on the
        # 2-process fuzz suite).
        lazy = shards._counts_host is None and \
            not multiplexer.multiprocess(dia.context.mesh_exec)
        if not lazy and shards.total == 0:
            return initial
        reduced = _device_reduce(shards, "sum", keep_device=device)
        if initial is None or (np.isscalar(initial) and initial == 0):
            return reduced
        # fold the initial value like the host path does; accept either
        # a matching pytree or a scalar broadcast over all leaves
        try:
            return jax.tree.map(lambda r, i: r + i, reduced, initial)
        except ValueError:
            return jax.tree.map(lambda r: r + initial, reduced)
    mex = dia.context.mesh_exec
    items = [it for l in shards.lists for it in l]
    if multiplexer.multiprocess(mex):
        local = functools.reduce(lambda a, b: a + b, items) if items \
            else None
        try:
            merged = multiplexer.net_fold(mex, local,
                                          lambda a, b: a + b,
                                          empty=not items)
        except ValueError:
            return initial
        return merged if initial is None else initial + merged
    return functools.reduce(lambda a, b: a + b, items, initial)


@staged_action
def MinMax(dia, is_min: bool) -> Any:
    shards = _pull(dia)
    if isinstance(shards, DeviceShards):
        if shards.total == 0:
            raise ValueError("Min/Max of empty DIA")
        return _device_reduce(shards, "min" if is_min else "max")
    mex = dia.context.mesh_exec
    items = [it for l in shards.lists for it in l]
    if multiplexer.multiprocess(mex):
        local = (min(items) if is_min else max(items)) if items else None
        try:
            return multiplexer.net_fold(
                mex, local, (lambda a, b: min(a, b)) if is_min
                else (lambda a, b: max(a, b)), empty=not items)
        except ValueError:
            raise ValueError("Min/Max of empty DIA")
    if not items:
        raise ValueError("Min/Max of empty DIA")
    return min(items) if is_min else max(items)


def AllReduce(dia, fn: Callable, initial: Any = None) -> Any:
    """Generic associative fold over all items (any storage)."""
    items = AllGather(dia)
    if not items:
        if initial is None:
            raise ValueError("AllReduce of empty DIA without initial")
        return initial
    acc = items[0] if initial is None else fn(initial, items[0])
    for it in items[1:]:
        acc = fn(acc, it)
    return acc
