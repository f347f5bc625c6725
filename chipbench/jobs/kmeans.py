"""Job kind ``kmeans``: Distribute (the points) -> Iterate over the k
centroids (Map: closest centroid -> ReduceToIndex on the cluster id:
sum of the points, count -> Map: divide -> AllGatherArrays) -> the k
binary64 centroids on the host, which is what a k-means user wants.

The job is Thrill's ``examples/k-means/k-means.hpp`` (arXiv:1608.05634
sec. IV): a fixed number of Lloyd iterations, no convergence test,
``double`` coordinates, sums, counts and centroids; its ``ReduceByKey``
on the cluster id is spelt ``ReduceToIndex`` over ``0..k-1``. The input
is ``k-means_run.cpp -g``'s: points drawn uniformly at random, the
initial centroids k of the points.

The generator, the reference and the control share nothing with
``thrill_tpu`` nor with ``examples/k_means.py``; only ``pipeline`` calls
the program, through its public API.
"""

from __future__ import annotations

import numpy as np

COORD_RANGE = 1000.0        # coordinates are uniform on [0, COORD_RANGE)
# Two binary64 summations of about n/k terms in different orders differ
# by about 1e-12 of the range; a centroid held in binary32 between the
# iterations is off by 1e-8 to 1e-7; ONE point that changes cluster
# moves a centroid by about range * k / n (2.4e-6 of it at 2^22 points),
# so a classification in a lower precision, which flips points near a
# bisector, fails by orders of magnitude. A different order of additions
# passes, a lower precision than the configuration states does not.
CENTER_ERR_LIMIT = 1e-9
_NOT_A_NUMBER = float(np.finfo(np.float64).max)
_BLOCK = 1 << 16            # points per block of the reference's distances

# what ``pipeline`` needs besides its input: the harness hands it the
# points and the initial centroids alone, so the number of iterations
# is kept here when ``generate`` runs
_sizes = {}
_functors = {}


def points(traffic: dict) -> int:
    return int(traffic["points"])


def records(traffic: dict) -> int:
    """Point classifications per job: every point once in every
    iteration."""
    return points(traffic) * int(traffic["iterations"])


def generate(seed: int, traffic: dict, config: dict) -> dict:
    """n points uniform on [0, COORD_RANGE)^dim and k of them, drawn
    without replacement, as the initial centroids. A function of the
    seed alone."""
    n, dim, k = points(traffic), int(traffic["dim"]), \
        int(traffic["clusters"])
    rng = np.random.default_rng(seed)
    x = rng.random((n, dim)) * COORD_RANGE
    c0 = x[rng.choice(n, size=k, replace=False)].copy()
    _sizes.update(iterations=int(traffic["iterations"]))
    return {"x": x, "c0": c0}


# ---------------------------------------------------------------- program
# module-level functors: the program caches its compiled programs on the
# function objects, and a loop body that is the same object in every job
# lets Iterate rebind the tape it captured in the first one

def _classify(p, centers):
    """The closest centroid of every point, binary64: p["x"] is the
    worker's [n, dim] block of points, centers [k, dim]."""
    import jax
    import jax.numpy as jnp
    x = p["x"]
    with jax.named_scope("kmeans_classify"):
        d2 = None
        for d in range(x.shape[1]):
            diff = x[:, d, None] - centers[None, :, d]
            d2 = diff * diff if d2 is None else d2 + diff * diff
        label = jnp.argmin(d2, axis=1)
    return {"i": label, "x": x, "cnt": jnp.ones(x.shape[0], x.dtype)}


def _label(t):
    return t["i"]


def _with_row(t, row):
    """Row j of ReduceToIndex's output is cluster j, whatever ``t["i"]``
    reads where no point fell: a Map cannot know its row, ZipWithIndex
    does."""
    return {"j": row, "x": t["x"], "cnt": t["cnt"]}


def _mean_or_keep(t, centers):
    """sum / count; a cluster with no point keeps its centroid."""
    import jax.numpy as jnp
    cnt = t["cnt"]
    mean = t["x"] / jnp.maximum(cnt, 1.0)[:, None]
    return jnp.where((cnt > 0)[:, None], mean, centers[t["j"]])


def _iteration(centers, pts, k, neutral_x):
    """One Lloyd iteration: the carry is the [k, dim] centroids, the
    next carry the means of the points closest to each."""
    from thrill_tpu.api import Bind
    sums = pts.Map(Bind(_classify, centers)).ReduceToIndex(
        _label, _functors["sum"], k,
        neutral={"i": 0, "x": neutral_x, "cnt": 0.0})
    return sums.ZipWithIndex(_with_row).Map(
        Bind(_mean_or_keep, centers)).AllGatherArrays()


def pipeline(ctx, inp: dict) -> dict:
    """One job, from the host's points to the centroids on the host.
    The loop joins the pipeline of its one invariant DIA: a job is one
    pipeline."""
    from thrill_tpu.api import FieldReduce, Iterate
    if not _functors:
        _functors["sum"] = FieldReduce(
            {"i": "first", "x": "sum", "cnt": "sum"})
    iterations = _sizes["iterations"]
    k, dim = inp["c0"].shape
    pts = ctx.Distribute({"x": inp["x"]}).Cache().Keep(iterations + 1)
    centers = Iterate(
        ctx, _iteration, inp["c0"], iterations,
        name="kmeans", invariants=(pts, k, np.zeros(dim)))
    got = np.asarray(centers, dtype=np.float64)
    pts.Dispose()
    return {"c": got}


def fetch(handle: dict) -> dict:
    return handle


def dispose(handle) -> None:
    """The centroids are on the host; the job disposed of what it kept."""


# -------------------------------------------------------------- reference

def _lloyd(x, c0, iterations: int, dtype):
    """Plain Lloyd, the centroids held in ``dtype`` between the
    iterations; distances in blocks of points so that they fit."""
    n, dim = x.shape
    k = len(c0)
    c = c0.astype(dtype)
    for _ in range(iterations):
        cc = c.astype(np.float64)
        label = np.empty(n, np.int64)
        for lo in range(0, n, _BLOCK):
            blk = x[lo:lo + _BLOCK]
            label[lo:lo + _BLOCK] = (
                (blk[:, None, :] - cc[None]) ** 2).sum(-1).argmin(1)
        cnt = np.bincount(label, minlength=k).astype(np.float64)
        sums = np.stack([np.bincount(label, weights=x[:, d], minlength=k)
                         for d in range(dim)], axis=1)
        mean = sums / np.maximum(cnt, 1.0)[:, None]
        c = np.where((cnt > 0)[:, None], mean, cc).astype(dtype)
    return {"c": c.astype(np.float64)}


def reference(inp: dict, traffic: dict) -> dict:
    """numpy, binary64 throughout: ``np.bincount`` per coordinate and
    for the counts, in every iteration."""
    return _lloyd(inp["x"], inp["c0"], int(traffic["iterations"]),
                  np.float64)


def control(inp: dict, traffic: dict) -> dict:
    """The reference with one guarantee broken, the way a later PR would
    be tempted to: the centroids held in binary32 between the
    iterations."""
    return _lloyd(inp["x"], inp["c0"], int(traffic["iterations"]),
                  np.float32)


def compare(got, want: dict) -> dict:
    """Each number is (reading, limit). A result with no ``c``, or with
    centroids of another shape, is ``centers_missing`` = k and nothing
    else; a coordinate that is not a number reads as the largest
    binary64."""
    k = len(want["c"])
    c = got.get("c") if isinstance(got, dict) else None
    if c is None or np.shape(c) != want["c"].shape:
        return {"centers_missing": (k, 0)}
    with np.errstate(all="ignore"):
        err = np.abs(np.asarray(c, np.float64) - want["c"]) / COORD_RANGE
    err = np.where(np.isfinite(err), err, _NOT_A_NUMBER)
    return {"centers_missing": (0, 0),
            "center_err_max": (float(err.max()), CENTER_ERR_LIMIT)}


# ------------------------------------------------------------------ bytes

def min_bytes(traffic: dict, config: dict, want) -> int:
    """What a job must move whatever implements it: per iteration every
    point read once and the k centroids read and written; the upload
    read once."""
    n, k = points(traffic), int(traffic["clusters"])
    point = 8 * int(traffic["dim"])
    return int(traffic["iterations"]) * (point * n + 2 * point * k) \
        + point * n
