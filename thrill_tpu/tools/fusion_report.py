"""Fused-vs-unfused dispatch report over the example pipelines.

Runs WordCount (text -> packed words -> Map -> ReduceByKey) and
PageRank (the iterative join/reduce pipeline) twice each — program
stitching on (default) and THRILL_TPU_FUSE=0 — and prints the device
dispatch counts plus the delta: the launches the fusion planner saves
per run.

Usage::

    python -m thrill_tpu.tools.fusion_report [--pages N] [--edges M]
        [--iters K] [--words N]

(or ``run-scripts/fusion_report.sh``). Exercises the real pipelines,
so it doubles as an end-to-end parity check: both modes' results are
compared exactly before any number is printed.
"""

from __future__ import annotations

import argparse
import os
import sys
import tempfile


def _run_wordcount(ctx, mex, path):
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(
        __file__)), "..", "..", "examples"))
    import word_count as wc
    out = wc.word_count_text_device(ctx, path).AllGatherArrays()
    import jax
    import numpy as np
    cols = jax.tree.map(np.asarray, out)
    order = np.lexsort(tuple(cols["w"].T))
    return {k: v[order] for k, v in sorted(cols.items())}


def _run_pagerank(ctx, mex, edges, pages, iters):
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(
        __file__)), "..", "..", "examples"))
    import page_rank as pr
    return pr.page_rank(ctx, edges, pages, iterations=iters)


def _measure(name, job):
    """Run ``job(fuse)`` for both modes (one warm-up run each so
    compile/caches don't pollute the counts) and return the row."""
    import numpy as np
    counts = {}
    results = {}
    prev = os.environ.get("THRILL_TPU_FUSE")
    try:
        for fuse in ("1", "0"):
            os.environ["THRILL_TPU_FUSE"] = fuse
            job()                                # warm: compile+cache
            d0 = _MEX.stats_dispatches
            results[fuse] = job()
            counts[fuse] = _MEX.stats_dispatches - d0
    finally:
        # restore the caller's setting — the report used to leave
        # THRILL_TPU_FUSE=0 behind, silently unfusing everything run
        # in the same process afterwards
        if prev is None:
            os.environ.pop("THRILL_TPU_FUSE", None)
        else:
            os.environ["THRILL_TPU_FUSE"] = prev
    assert np.allclose(np.asarray(results["1"], dtype=np.float64),
                       np.asarray(results["0"], dtype=np.float64)), \
        f"{name}: fused and unfused results diverge"
    return (name, counts["0"], counts["1"],
            counts["0"] - counts["1"],
            counts["0"] / max(counts["1"], 1))


_MEX = None


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--pages", type=int, default=256)
    ap.add_argument("--edges", type=int, default=2048)
    ap.add_argument("--iters", type=int, default=4)
    ap.add_argument("--words", type=int, default=4096)
    args = ap.parse_args()

    # the jitted engines are what fusion stitches; the CPU-native
    # fallbacks would sidestep the thing being measured
    os.environ.setdefault("THRILL_TPU_HOST_RADIX", "0")

    import numpy as np
    from thrill_tpu.api import Context
    from thrill_tpu.parallel.mesh import MeshExec

    global _MEX
    _MEX = mex = MeshExec()
    ctx = Context(mex)

    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(
        __file__)), "..", "..", "examples"))
    import page_rank as pr

    rng = np.random.default_rng(0)
    vocab = ["w%03d" % i for i in range(97)]
    text = " ".join(rng.choice(vocab, size=args.words))
    edges = pr.zipf_graph(args.pages, args.edges)

    rows = []
    with tempfile.NamedTemporaryFile("w", suffix=".txt",
                                     delete=False) as f:
        f.write(text + "\n")
        path = f.name
    try:
        def wc_leaves():
            cols = _run_wordcount(ctx, mex, path)
            return np.concatenate([np.asarray(v, np.float64).reshape(-1)
                                   for v in cols.values()])

        rows.append(_measure("WordCount", wc_leaves))
        rows.append(_measure(
            "PageRank",
            lambda: _run_pagerank(ctx, mex, edges, args.pages,
                                  args.iters)))
    finally:
        os.unlink(path)

    print(f"{'pipeline':<12} {'unfused':>8} {'fused':>8} "
          f"{'delta':>8} {'ratio':>7}")
    for name, unf, fus, delta, ratio in rows:
        print(f"{name:<12} {unf:>8} {fus:>8} {delta:>8} {ratio:>6.2f}x")
    stats = ctx.overall_stats()
    stages = stats.get("fused_stages") or {}
    if stages:
        print("\nfused stage compositions (this process):")
        for ops, n in sorted(stages.items(), key=lambda kv: -kv[1]):
            print(f"  {n:>5}x  {ops}")
    ctx.close()


if __name__ == "__main__":
    main()
