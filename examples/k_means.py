"""k-means clustering: classify + ReducePair + Collapse loop.

Reference: /root/reference/examples/k-means/k-means.hpp:176-259 —
points classified to the nearest center, per-center sums reduced
(ReduceByKey on center index), new centers broadcast, loop with
Collapse'd DIAs.

TPU-native: points are a device [n, dim] column; classification is a
batched distance matmul (MXU work!), the per-center reduction is
ReduceToIndex, and centers travel to the next iteration as a small host
array (the reference's AllReduce/broadcast step).
"""

from __future__ import annotations

import _bootstrap  # noqa: F401  (repo root on sys.path for CLI runs)


import numpy as np

from thrill_tpu.api import Context


# Module-level stacked/keyed functions (identity-stable -> executable
# cache hits across iterations AND across k_means calls); the moving
# centroids enter through Bind as a runtime-bound operand, tokened by
# SHAPE — the trace-once analog of the reference's by-reference lambda
# capture (k-means.hpp:176-259), which would otherwise recompile the
# classify program every Lloyd iteration (20-40s each on TPU).

def _label(x, c):                       # x: [n_local, dim] batched
    import jax.numpy as jnp
    d2 = (jnp.sum(x * x, axis=1, keepdims=True)
          - 2.0 * x @ c.T
          + jnp.sum(c * c, axis=1)[None, :])
    return {"i": jnp.argmin(d2, axis=1).astype(jnp.int64), "x": x,
            "cnt": x[:, 0] * 0 + 1.0}


def _cluster_i(t):
    return t["i"]


# declarative reduce spec ("i" carries the key, "x"/"cnt" accumulate):
# unlocks the sort-free dense scatter engine in ReduceToIndex — a
# device dispatch at any backend, so the loop body is fully recordable
# for LoopPlan replay (a generic reduce lambda would demote to the
# host engine on CPU and break the capture)
def _cluster_sum():
    from thrill_tpu.api import FieldReduce
    return FieldReduce({"i": "first", "x": "sum", "cnt": "sum"})


def _center_update(sum_x, cnt, centers):
    import jax.numpy as jnp
    return jnp.where((cnt > 0)[:, None],
                     sum_x / jnp.maximum(cnt, 1.0)[:, None],
                     centers)


def k_means(ctx: Context, points: np.ndarray, k: int, iterations: int = 10,
            seed: int = 0):
    """points: [n, dim] float64. Returns (centers [k, dim], labels DIA)."""
    from thrill_tpu.api import Bind

    n, dim = points.shape
    rng = np.random.default_rng(seed)
    centers = points[rng.choice(n, size=k, replace=False)].copy()

    pts = ctx.Distribute(points.astype(np.float64)).Cache() \
        .Keep(2 * iterations + 1)

    # The Lloyd loop stays entirely in jax's async dispatch stream:
    # AllGatherArrays returns the per-cluster sums as DEVICE arrays,
    # the centroid update runs as a small cached program, and the
    # updated centers re-enter the classify program through Bind
    # (device operands pass straight through). Zero blocking host
    # syncs per iteration; the reference's AllReduce/broadcast step
    # (k-means.hpp:176-259) is host-side and has no such cost.
    #
    # The loop is driven by the iteration layer (api/loop.py): every
    # device step of the body — classify+reduce, columnar egress,
    # centroid update — is a recordable dispatch, so iterations 2..N
    # replay a captured LoopPlan (and, the body being exchange-free at
    # W=1, lower into one whole-loop fori_loop dispatch) instead of
    # rebuilding the DIA graph per iteration.
    from thrill_tpu.api import Iterate
    import jax.numpy as jnp
    red = _cluster_sum()
    update = ctx.mesh_exec.jit_cached(("kmeans_center_update",),
                                      _center_update)

    def body(centers):
        labeled = pts.Map(Bind(_label, centers))
        sums = labeled.ReduceToIndex(
            _cluster_i, red,
            k, neutral={"i": 0, "x": np.zeros(dim), "cnt": 0.0})
        cols = sums.AllGatherArrays()
        return update(cols["x"], cols["cnt"], centers)

    centers = Iterate(ctx, body, jnp.asarray(centers), iterations,
                      name="k_means")
    return np.asarray(centers)


def k_means_dense(points: np.ndarray, centers0: np.ndarray,
                  iterations: int) -> np.ndarray:
    centers = centers0.copy()
    for _ in range(iterations):
        d2 = ((points[:, None, :] - centers[None, :, :]) ** 2).sum(-1)
        lab = d2.argmin(1)
        for j in range(len(centers)):
            sel = points[lab == j]
            if len(sel):
                centers[j] = sel.mean(0)
    return centers


def main():
    import argparse
    parser = argparse.ArgumentParser()
    parser.add_argument("--points", type=int, default=10000)
    parser.add_argument("--dim", type=int, default=8)
    parser.add_argument("--clusters", type=int, default=10)
    parser.add_argument("--iters", type=int, default=10)
    args = parser.parse_args()

    from thrill_tpu.api import Run

    def job(ctx):
        rng = np.random.default_rng(1)
        pts = rng.normal(size=(args.points, args.dim))
        centers = k_means(ctx, pts, args.clusters, args.iters)
        print(centers)

    Run(job)


if __name__ == "__main__":
    main()
