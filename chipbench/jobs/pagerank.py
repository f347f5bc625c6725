"""Job kind ``pagerank``: Distribute (the edge list) -> out-degrees by
ReduceToIndex -> ranks 1/N -> Iterate(Zip -> dense InnerJoin ->
ReduceToIndex -> dampen) -> the N binary64 ranks on the host, which is
what a PageRank user wants.

The job is Thrill's ``examples/page_rank/page_rank.hpp`` (arXiv:1608.05634
sec. IV) and LDBC Graphalytics' PR: a fixed number of iterations, no
convergence test, ``double`` ranks; no redistribution of dangling pages'
rank (Thrill's reading). The graph is the Graph500 Kronecker generator's
(R-MAT 0.57/0.19/0.19/0.05, vertex labels and edge order permuted,
multi-edges and self-loops left in), read as directed edges.

The generator and the reference are copies (of ``examples/page_rank.py``'s
idea, not its code) and share nothing with ``thrill_tpu``; only
``pipeline`` calls the program, through its public API.
"""

from __future__ import annotations

import numpy as np

RMAT = (0.57, 0.19, 0.19, 0.05)
# two binary64 summations of at most ~30,000 terms in different orders
# differ by under 1e-11 relative; ranks carried in binary32 anywhere in
# the loop differ by about 1e-7: a lower precision than the configuration
# states fails, a different order of additions does not
RANK_REL_ERR_LIMIT = 1e-9
_NOT_A_NUMBER = float(np.finfo(np.float64).max)

# what ``pipeline`` needs besides its input: the harness hands it the
# edge list alone, so the sizes of the traffic mix are kept here when
# ``generate`` runs
_sizes = {}
_functors = {}


def pages(traffic: dict) -> int:
    return 1 << int(traffic["graph500_scale"])


def edges(traffic: dict) -> int:
    return int(traffic["edge_factor"]) << int(traffic["graph500_scale"])


def records(traffic: dict) -> int:
    """Edge traversals per job: every edge once in every iteration."""
    return edges(traffic) * int(traffic["iterations"])


def generate(seed: int, traffic: dict, config: dict) -> dict:
    """Graph500's Kronecker generator (the reference code's
    ``kronecker_generator``): one quadrant of the initiator per bit of
    the vertex id, then the vertex labels and the edge order permuted.
    A function of the seed alone."""
    scale, m, n = int(traffic["graph500_scale"]), edges(traffic), \
        pages(traffic)
    a, b, c, _ = RMAT
    ab = a + b
    c_norm, a_norm = c / (1.0 - ab), a / ab
    rng = np.random.default_rng(seed)
    src = np.zeros(m, np.int64)
    dst = np.zeros(m, np.int64)
    for bit in range(scale):
        s_bit = rng.random(m, dtype=np.float32) > ab
        d_bit = rng.random(m, dtype=np.float32) > np.where(
            s_bit, np.float32(c_norm), np.float32(a_norm))
        src |= s_bit.astype(np.int64) << bit
        dst |= d_bit.astype(np.int64) << bit
    label = rng.permutation(n)
    order = rng.permutation(m)
    _sizes.update(pages=n, iterations=int(traffic["iterations"]),
                  damping=float(traffic["damping"]))
    return {"s": label[src][order], "d": label[dst][order]}


# ---------------------------------------------------------------- program
# module-level functors: the program caches its compiled programs on the
# function objects, and a loop body that is the same object in every job
# lets Iterate replay the tape it captured in the first one

def _src_one(e):
    return (e["s"], 1)


def _page_first(kv):
    return kv[0]


def _fill(kv, v):
    return kv[1] * 0.0 + v[0]


def _scale_rank(r, kv):
    import jax.numpy as jnp
    return r / jnp.maximum(kv[1], 1)


def _edge_src(e):
    return e["s"]


def _join_scaled(e, s):
    return {"d": e["d"], "v": s}


def _contrib_dst(c):
    return c["d"]


def _dampen(t, p):
    return p[0] + p[1] * t["v"]


def _iteration(ranks, links, degrees, n, teleport_damping):
    """rank / max(out-degree, 1) per page, gathered along every edge by
    its source, summed at the edge's target, dampened."""
    from thrill_tpu.api import Bind, InnerJoin, Zip
    scaled = Zip(ranks, degrees, zip_fn=_scale_rank)
    contrib = InnerJoin(links, scaled, _edge_src, None, _join_scaled,
                        dense_right_index=n)
    sums = contrib.ReduceToIndex(_contrib_dst, _functors["sum_v"], n,
                                 neutral={"d": 0, "v": 0.0})
    return sums.Map(Bind(_dampen, teleport_damping))


def pipeline(ctx, inp: dict) -> dict:
    """One job, from the host's edge list to the ranks on the host.
    Every node derives from the one Distribute: a job is one pipeline."""
    from thrill_tpu.api import Bind, FieldReduce, Iterate
    if not _functors:
        _functors["add_pairs"] = FieldReduce(("first", "sum"))
        _functors["sum_v"] = FieldReduce({"d": "first", "v": "sum"})
    n, iterations = _sizes["pages"], _sizes["iterations"]
    damping = _sizes["damping"]
    links = ctx.Distribute(inp).Cache().Keep(iterations + 1)
    degrees = links.Map(_src_one).ReduceToIndex(
        _page_first, _functors["add_pairs"], n,
        neutral=(0, 0)).Cache().Keep(iterations + 1)
    ranks = degrees.Map(Bind(_fill, np.array([1.0 / n])))
    ranks = Iterate(
        ctx, _iteration, ranks, iterations, name="pagerank",
        invariants=(links, degrees, n,
                    np.array([(1.0 - damping) / n, damping])))
    got = np.asarray(ranks.AllGather(), dtype=np.float64)
    links.Dispose()
    degrees.Dispose()
    return {"r": got}


def fetch(handle: dict) -> dict:
    return handle


def dispose(handle) -> None:
    """The ranks are on the host; the job disposed of what it kept."""


# -------------------------------------------------------------- reference

def _ranks(src, dst, n: int, iterations: int, damping: float, dtype):
    deg = np.maximum(np.bincount(src, minlength=n), 1)
    r = np.full(n, 1.0 / n, dtype)
    for _ in range(iterations):
        scaled = (r / deg).astype(dtype)
        sums = np.bincount(dst, weights=scaled[src], minlength=n)
        r = ((1.0 - damping) / n + damping * sums).astype(dtype)
    return {"r": r.astype(np.float64)}


def reference(inp: dict, traffic: dict) -> dict:
    """numpy, binary64 throughout: ``np.bincount`` for the degrees and
    for every iteration's sums."""
    return _ranks(inp["s"], inp["d"], pages(traffic),
                  int(traffic["iterations"]), float(traffic["damping"]),
                  np.float64)


def control(inp: dict, traffic: dict) -> dict:
    """The reference with one guarantee broken, the way a later PR would
    be tempted to: the ranks held in binary32 between the iterations."""
    return _ranks(inp["s"], inp["d"], pages(traffic),
                  int(traffic["iterations"]), float(traffic["damping"]),
                  np.float32)


def compare(got, want: dict) -> dict:
    """Each number is (reading, limit). A result with no ``r``, or with
    ranks for another number of pages, is ``ranks_missing`` and nothing
    else; a rank that is not a number reads as the largest binary64."""
    n = len(want["r"])
    r = got.get("r") if isinstance(got, dict) else None
    if r is None or np.ndim(r) != 1:
        return {"ranks_missing": (n, 0)}
    if len(r) != n:
        return {"ranks_missing": (abs(len(r) - n), 0)}
    with np.errstate(all="ignore"):
        err = np.abs(np.asarray(r, np.float64) - want["r"]) / want["r"]
    err = np.where(np.isfinite(err), err, _NOT_A_NUMBER)
    return {"ranks_missing": (0, 0),
            "rank_rel_err_max": (float(err.max()), RANK_REL_ERR_LIMIT)}


# ------------------------------------------------------------------ bytes

def gather_bytes(m: int) -> int:
    """The dense join of one iteration: every edge (two 8-byte ids) and
    the 8-byte scaled rank of its source read once."""
    return m * (16 + 8)


def table_bytes(n: int) -> int:
    """The rank table of one iteration: read once, written once."""
    return 2 * 8 * n


def min_bytes(traffic: dict, config: dict, want) -> int:
    """What a job must move whatever implements it: per iteration the
    gather and the table; the edge list read once for the degrees and
    the ranks written once."""
    m, n = edges(traffic), pages(traffic)
    return int(traffic["iterations"]) * (gather_bytes(m) + table_bytes(n)) \
        + 16 * m + 8 * n
