"""PageRank: iterative Zip + FlatMap-style contribution + ReduceToIndex.

Reference: /root/reference/examples/page_rank/page_rank.hpp:71-131 —
links grouped by source, ranks joined to outgoing links, contributions
reduced by target index, dampened; iterated with Collapse'd loop DIAs.

TPU-native: the adjacency is a columnar edge list (src, dst) on device;
one iteration = Zip the ranks with the out-degree table (ReduceToIndex,
once, before the loop), gather the scaled rank along every edge by its
source (a dense-index InnerJoin: no sort, no hash exchange, no size
sync) and scatter-add the contributions by target (sort-free
ReduceToIndex). Entirely jitted device programs; on one worker nothing
is exchanged inside an iteration, on several the join all-gathers the
dense table and ReduceToIndex routes each contribution to the worker
that owns its target.

The loop body is a module-level function that takes the edge list, the
degree table and the per-call constants as ``invariants`` of
``Iterate``, so a later call of the same sizes replays the tape the
first one captured. ``chipbench/jobs/pagerank.py`` is the benchmark's
copy of this job (a copy, not an import).
"""

from __future__ import annotations

import _bootstrap  # noqa: F401  (repo root on sys.path for CLI runs)


import numpy as np

from thrill_tpu.api import Bind, Context, FieldReduce, InnerJoin, Iterate, Zip

DAMPENING = 0.85


# Every stacked/keyed function is MODULE-LEVEL (identity-stable): the
# executable caches key on function identity, so in-loop lambdas would
# recompile every iteration — 20-40s per program on TPU. Per-call
# constants (1/num_pages) enter through Bind, which tokens on operand
# SHAPE, so repeated page_rank calls reuse the same executables too.

def _src_one(s):
    return (s, 1)


def _page_first(kv):
    return kv[0]


# declarative degree count: (page, 1) pairs scatter-added per page —
# the sort-free ReduceToIndex engine (no host demotion, no XLA argsort)
_ADD_PAIRS = FieldReduce(("first", "sum"))


def _fill(x, v):
    return x * 0.0 + v[0]


def _edge_src(e):
    return e["s"]


def _scale_rank(r, kv):
    # rank / out-degree, degree clamped so dangling pages divide by 1
    import jax.numpy as jnp
    return r / jnp.maximum(kv[1], 1)


def _join_scaled(e, s):
    return {"d": e["d"], "v": s}


def _contrib_dst(c):
    return c["d"]


# declarative reduce spec: "d" carries the key, "v" accumulates — the
# FieldReduce spelling (like WordCount's) unlocks the sort-free dense
# scatter engine in ReduceToIndex, the O(n) analog of the numpy
# proxy's np.add.at
_SUM_V = FieldReduce({"d": "first", "v": "sum"})


def _dampen(t, base):
    return base[0] + DAMPENING * t["v"]


def _iteration(ranks, edges_dia, deg_dia, num_pages, base):
    """One iteration = three dense-table steps, no sort at any worker
    count and no exchange on one worker (on several, step 2 all-gathers
    the dense table and step 3 routes by target range):

    1. Zip ranks with the degree table and pre-divide — each page's
       outgoing contribution, one elementwise pass over [n] rows (the
       reference divides per EDGE, m/n times more divisions);
    2. a DENSE INDEX join: the right side is the dense per-page
       contribution table (row at global position p has key p by
       construction), so dense_right_index turns the join into a pure
       device gather — no sort, no hash exchange, no size sync (the
       generic sort-merge join pays two XLA argsorts per call);
    3. scatter-add by destination (sort-free FieldReduce engine) and
       dampen — the O(n+m) shape of the numpy proxy's np.add.at."""
    scaled = Zip(ranks, deg_dia, zip_fn=_scale_rank)
    contrib = InnerJoin(edges_dia, scaled, _edge_src, None,
                        _join_scaled, dense_right_index=num_pages)
    sums = contrib.ReduceToIndex(
        _contrib_dst, _SUM_V, num_pages, neutral={"d": 0, "v": 0.0})
    return sums.Map(Bind(_dampen, base))


def page_rank(ctx: Context, edges: np.ndarray, num_pages: int,
              iterations: int = 10):
    """edges: [m, 2] int64 (src, dst). Returns np.ndarray of ranks."""
    src = edges[:, 0].astype(np.int64)
    dst = edges[:, 1].astype(np.int64)

    # out-degree per page (dangling pages keep degree 0)
    deg_dia = ctx.Distribute(src).Map(_src_one).ReduceToIndex(
        _page_first, _ADD_PAIRS, num_pages,
        neutral=(0, 0)).Cache().Keep(iterations + 1)

    edges_dia = ctx.Distribute({"s": src, "d": dst}).Cache() \
        .Keep(iterations + 1)

    inv_n = np.array([1.0 / num_pages])
    base = np.array([(1.0 - DAMPENING) / num_pages])
    ranks = ctx.Generate(num_pages).Map(Bind(_fill, inv_n)).Cache()

    # the Collapse-loop idiom, loop-layer spelling (api/loop.py):
    # iteration 1 runs the body through the pull recursion + fusion
    # planner and CAPTURES the resulting dispatch tape as a LoopPlan;
    # iterations 2..N replay the tape device-resident — zero Python
    # graph construction, zero re-planning, zero host round trips
    # (THRILL_TPU_LOOP_REPLAY=0 restores the plain per-iteration loop).
    # What the body reads unchanged in every iteration goes in as
    # ``invariants``: the body is a module-level function that carries
    # nothing of this call, so the next page_rank() of the same sizes
    # REBINDS the kept tape to its own tables and captures nothing.
    ranks = Iterate(ctx, _iteration, ranks, iterations, name="page_rank",
                    invariants=(edges_dia, deg_dia, num_pages, base))

    return np.asarray(ranks.AllGather(), dtype=np.float64)


def page_rank_dense(ctx: Context, edges: np.ndarray, num_pages: int,
                    iterations: int = 10):
    """Reference implementation in numpy for verification."""
    r = np.full(num_pages, 1.0 / num_pages)
    deg = np.bincount(edges[:, 0], minlength=num_pages)
    for _ in range(iterations):
        contrib = np.zeros(num_pages)
        vals = r[edges[:, 0]] / np.maximum(deg[edges[:, 0]], 1)
        np.add.at(contrib, edges[:, 1], vals)
        r = (1 - DAMPENING) / num_pages + DAMPENING * contrib
    return r


def zipf_graph(num_pages: int, num_edges: int, seed: int = 0) -> np.ndarray:
    """Zipf-distributed targets like the reference's generator."""
    rng = np.random.default_rng(seed)
    src = rng.integers(0, num_pages, num_edges)
    ranks = np.arange(1, num_pages + 1, dtype=np.float64)
    p = (1.0 / ranks)
    p /= p.sum()
    dst = rng.choice(num_pages, size=num_edges, p=p)
    return np.stack([src, dst], axis=1).astype(np.int64)


def main():
    import argparse
    parser = argparse.ArgumentParser()
    parser.add_argument("--pages", type=int, default=1000)
    parser.add_argument("--edges", type=int, default=10000)
    parser.add_argument("--iters", type=int, default=10)
    args = parser.parse_args()

    from thrill_tpu.api import Run

    def job(ctx):
        edges = zipf_graph(args.pages, args.edges)
        r = page_rank(ctx, edges, args.pages, args.iters)
        top = np.argsort(-r)[:10]
        for p in top:
            print(f"page {p}: {r[p]:.6f}")

    Run(job)


if __name__ == "__main__":
    main()
