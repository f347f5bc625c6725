"""PrefixSum / ExPrefixSum.

Reference: thrill/api/prefix_sum.hpp:28 — local sum, net.ExPrefixSum of
partials, re-emit.

What runs on the device: the ADDITIVE scan (``fn is None``), inclusive
or exclusive, with any ``initial``, over every leaf of the item tree: a
masked local ``cumsum`` plus the cross-worker exclusive offset from an
``all_gather`` of the local totals (the FlowControlChannel step become
an XLA collective). It rides the stitched program of the chain it ends
(a ``Sort -> Window -> PrefixSum`` is one dispatch), its operations
carry the named scope ``prefix_sum``, and its rows stay where they are
(``keeps_rows``), so a chain whose rows were a prefix is not compacted
behind it. An unsigned column wraps, as its dtype does.

What does not: a generic ``fn`` (Thrill's ``PrefixSum`` takes any
associative functor, its suffix sorters name by ``max``) folds on the
host path, sequentially. A job that must stay on the device spells its
scan additively: names are 1 + the sum of the boundary flags before a
row (examples/suffix_sorting.py), and a column that must pass through
unsummed is zipped back on afterwards.
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ...data.shards import DeviceShards, HostShards
from ...parallel.mesh import AXIS
from ..dia import DIA
from ..dia_base import DIABase

# HLO metadata only: the scan's operations carry this name in a device
# profile's op_name
SCOPE = "prefix_sum"


class PrefixSumNode(DIABase):
    def __init__(self, ctx, link, fn: Optional[Callable], initial: Any,
                 inclusive: bool) -> None:
        super().__init__(ctx, "PrefixSum" if inclusive else "ExPrefixSum",
                         [link])
        self.fn = fn
        self.initial = initial
        self.inclusive = inclusive

    def _fuse_segment(self):
        """The masked local-cumsum + cross-worker offset trace as a
        fused segment (the all_gather of local totals rides inside the
        stitched program)."""
        from .. import fusion
        inclusive = self.inclusive
        initial = self.initial

        @jax.named_scope(SCOPE)
        def trace(fctx, tree, mask, _bound):
            def one(x):
                m = mask.reshape(mask.shape + (1,) * (x.ndim - 1))
                xm = jnp.where(m, x, 0)
                incl = jnp.cumsum(xm, axis=0, dtype=x.dtype)
                local_total = incl[-1]
                totals = lax.all_gather(local_total, AXIS)   # [W, ...]
                widx = lax.axis_index(AXIS)
                # in the leaf's dtype, like the cumsum: a jnp sum of
                # 32-bit integers would widen the whole column to 64
                prev = jnp.where(
                    (jnp.arange(totals.shape[0]) < widx
                     ).reshape((-1,) + (1,) * (totals.ndim - 1)),
                    totals, 0).sum(axis=0, dtype=x.dtype)
                scan = incl if inclusive else incl - xm
                return scan + prev + jnp.asarray(initial).astype(x.dtype)

            return jax.tree.map(one, tree), mask

        return fusion.Segment(
            label=self.label,
            token=("prefix_sum_fused", inclusive,
                   np.asarray(initial).tobytes()),
            trace=trace, preserves_counts=True, keeps_rows=True,
            dia_id=self.id)

    def compute_plan(self):
        from .. import fusion
        if self.fn is not None:
            return None              # generic fold: host path only
        plan = fusion.pull_plan(self.parents[0])
        if not plan.stitchable:
            return fusion.wrap(self._compute_on(plan.finish()))
        plan.append(self._fuse_segment())
        return plan

    def compute(self):
        plan = self.compute_plan()
        if plan is not None:
            return plan.finish()
        return self._compute_on(self.parents[0].pull())

    def _compute_on(self, shards):
        if isinstance(shards, HostShards) or self.fn is not None:
            if isinstance(shards, DeviceShards):
                shards = shards.to_host_shards("prefixsum-nonnumeric-op")
            return self._compute_host(shards)
        return self._compute_device(shards)

    def _compute_host(self, shards: HostShards):
        # generic (possibly non-associative) fold is sequential across
        # the whole stream: replicate across controllers, compute the
        # identical full result, keep the local lists
        from ...data import multiplexer
        mex = self.context.mesh_exec
        replicated = multiplexer.ensure_replicated(mex, shards,
                                                   "prefixsum-host")
        fn = self.fn or (lambda a, b: a + b)
        out = []
        acc = self.initial
        for items in replicated.lists:
            lst = []
            for it in items:
                if self.inclusive:
                    acc = fn(acc, it)
                    lst.append(acc)
                else:
                    lst.append(acc)
                    acc = fn(acc, it)
            out.append(lst)
        return multiplexer.localize(
            mex, HostShards(shards.num_workers, out))

    def _compute_device(self, shards: DeviceShards):
        mex = shards.mesh_exec
        cap = shards.cap
        leaves, treedef = jax.tree.flatten(shards.tree)
        initial = self.initial
        key = ("prefix_sum", self.inclusive, cap, treedef,
               tuple((l.dtype, l.shape[2:]) for l in leaves))

        def build():
            @jax.named_scope(SCOPE)
            def f(counts_dev, *ls):
                mask = jnp.arange(cap) < counts_dev[0, 0]
                outs = []
                for l in ls:
                    x = l[0]
                    m = mask.reshape(mask.shape + (1,) * (x.ndim - 1))
                    xm = jnp.where(m, x, 0)
                    incl = jnp.cumsum(xm, axis=0, dtype=x.dtype)
                    local_total = incl[-1]
                    totals = lax.all_gather(local_total, AXIS)  # [W, ...]
                    widx = lax.axis_index(AXIS)
                    prev = jnp.where(
                        (jnp.arange(totals.shape[0]) < widx
                         ).reshape((-1,) + (1,) * (totals.ndim - 1)),
                        totals, 0).sum(axis=0, dtype=x.dtype)
                    scan = incl if self.inclusive else incl - xm
                    outs.append((scan + prev + jnp.asarray(initial)
                                 .astype(x.dtype))[None])
                return tuple(outs)

            return mex.smap(f, 1 + len(leaves))

        fn = mex.cached(key, build)
        out = fn(shards.counts_device(), *leaves)
        tree = jax.tree.unflatten(treedef, list(out))
        return DeviceShards(mex, tree, shards.counts.copy())


def PrefixSum(dia: DIA, fn=None, initial: Any = 0, inclusive=True) -> DIA:
    return DIA(PrefixSumNode(dia.context, dia._link(), fn, initial,
                             inclusive))
