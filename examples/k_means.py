"""k-means clustering: classify + ReduceToIndex + AllGatherArrays loop.

Reference: /root/reference/examples/k-means/k-means.hpp:176-259 —
points classified to the nearest center, per-center sums reduced
(ReduceByKey on center index), new centers broadcast, loop with
Collapse'd DIAs.

TPU-native: points are a device [n, dim] column; classification is
elementwise binary64 arithmetic (squared differences per dimension and
an argmin over k: a TPU has no binary64 unit, XLA runs it as pairs of
binary32 on the vector unit, and none of it reaches the MXU), the
per-center reduction is ReduceToIndex over 0..k-1, the division a Map,
and the centers travel to the next iteration as a small device array
(the reference's AllReduce/broadcast step). ``chipbench/jobs/kmeans.py``
is the same pipeline as the chip benchmark runs it.
"""

from __future__ import annotations

import _bootstrap  # noqa: F401  (repo root on sys.path for CLI runs)


import numpy as np

from thrill_tpu.api import Bind, Context, FieldReduce, Iterate


# Module-level stacked/keyed functions (identity-stable -> executable
# cache hits across iterations AND across k_means calls); the moving
# centroids enter through Bind as a runtime-bound operand, tokened by
# SHAPE — the trace-once analog of the reference's by-reference lambda
# capture (k-means.hpp:176-259), which would otherwise recompile the
# classify program every Lloyd iteration (20-40s each on TPU).

def _label(x, c):                       # x: [n_local, dim] batched
    import jax.numpy as jnp
    d2 = ((x[:, None, :] - c[None, :, :]) ** 2).sum(-1)
    return {"i": jnp.argmin(d2, axis=1).astype(jnp.int64), "x": x,
            "cnt": x[:, 0] * 0 + 1.0}


def _cluster_i(t):
    return t["i"]


# declarative reduce spec ("i" carries the key, "x"/"cnt" accumulate):
# unlocks the sort-free dense scatter engine in ReduceToIndex — a
# device dispatch at any backend, so the loop body is fully recordable
# for LoopPlan replay (a generic reduce lambda would demote to the
# host engine on CPU and break the capture)
_CLUSTER_SUM = FieldReduce({"i": "first", "x": "sum", "cnt": "sum"})


def _with_row(t, row):
    # row j of ReduceToIndex's output is cluster j, also where no point
    # fell and "i" reads the neutral
    return {"j": row, "x": t["x"], "cnt": t["cnt"]}


def _center_update(t, centers):
    import jax.numpy as jnp
    cnt = t["cnt"]
    return jnp.where((cnt > 0)[:, None],
                     t["x"] / jnp.maximum(cnt, 1.0)[:, None],
                     centers[t["j"]])


def _lloyd(centers, pts, k, zeros):
    """One Lloyd iteration; module-level and handed everything it
    reads (``invariants=``), so a later ``k_means`` call over points of
    the same shape rebinds the tape the first one captured."""
    sums = pts.Map(Bind(_label, centers)).ReduceToIndex(
        _cluster_i, _CLUSTER_SUM, k,
        neutral={"i": 0, "x": zeros, "cnt": 0.0})
    return sums.ZipWithIndex(_with_row).Map(
        Bind(_center_update, centers)).AllGatherArrays()


def k_means(ctx: Context, points: np.ndarray, k: int, iterations: int = 10,
            seed: int = 0):
    """points: [n, dim] float64. Returns the centers [k, dim]."""
    import jax.numpy as jnp

    n, dim = points.shape
    rng = np.random.default_rng(seed)
    centers = points[rng.choice(n, size=k, replace=False)].copy()

    pts = ctx.Distribute(points.astype(np.float64)).Cache() \
        .Keep(iterations + 1)

    # The Lloyd loop stays entirely in jax's async dispatch stream:
    # AllGatherArrays returns the new centers as DEVICE arrays and they
    # re-enter the classify program through Bind (device operands pass
    # straight through). Zero blocking host syncs per iteration; the
    # reference's AllReduce/broadcast step (k-means.hpp:176-259) is
    # host-side and has no such cost.
    #
    # The loop is driven by the iteration layer (api/loop.py): every
    # device step of the body is a recordable dispatch, so iterations
    # 2..N replay a captured LoopPlan (and, the body being
    # exchange-free at W=1, lower into one whole-loop fori_loop
    # dispatch) instead of rebuilding the DIA graph per iteration.
    centers = Iterate(ctx, _lloyd, jnp.asarray(centers), iterations,
                      name="k_means",
                      invariants=(pts, k, np.zeros(dim)))
    pts.Dispose()
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
