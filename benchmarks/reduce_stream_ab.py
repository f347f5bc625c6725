"""A/B: bulk vs streamed (MixStream-analog) ReduceByKey post-phase.

The reference defaults ReduceByKey to MixStream delivery with an
overlapped post-phase thread (api/reduce_by_key.hpp:142-168,
core/reduce_table.hpp:40 DefaultReduceConfig). Our analog is
THRILL_TPU_REDUCE_STREAM: per-round exchange programs whose folds
overlap later rounds' collectives via jax async dispatch.

Prints RESULT lines for both modes over a sweep of key cardinalities;
run on the virtual 8-device CPU mesh by default (the only mesh this
image can host) and on a real multi-chip mesh unchanged.
"""

from __future__ import annotations

import os
import sys
import time

os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=8")

import numpy as np  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import thrill_tpu  # noqa: F401,E402
from thrill_tpu.common.platform import require_accelerator  # noqa: E402

require_accelerator()

import jax  # noqa: E402

from thrill_tpu.api import Context  # noqa: E402
from thrill_tpu.parallel.mesh import MeshExec  # noqa: E402


def _key(t):
    return t["k"]


def _red(a, b):
    return {"k": a["k"], "v": a["v"] + b["v"]}


def run_mode(stream: bool, n: int, nkeys: int, iters: int = 5) -> float:
    os.environ["THRILL_TPU_REDUCE_STREAM"] = "1" if stream else "0"
    mex = MeshExec()
    ctx = Context(mex)
    rng = np.random.default_rng(42)
    data = {
        "k": rng.integers(0, nkeys, size=n).astype(np.int64),
        "v": rng.standard_normal(n),
    }
    inp = ctx.Distribute(data)
    jax.block_until_ready(jax.tree.leaves(
        inp.node.materialize(consume=False).tree))

    def once():
        inp.Keep()
        out = inp.ReduceByKey(_key, _red)
        shards = out.node.materialize()
        leaves = jax.tree.leaves(shards.tree)
        jax.block_until_ready(leaves)
        np.asarray(leaves[0])[:1]
        return shards

    once()  # warmup/compile
    t0 = time.perf_counter()
    for _ in range(iters):
        once()
    dt = (time.perf_counter() - t0) / iters
    ctx.close()
    return dt


def main():
    n = int(os.environ.get("AB_N", 1 << 19))
    for nkeys in (64, 4096, 1 << 16, 1 << 19):
        bulk = run_mode(False, n, nkeys)
        strm = run_mode(True, n, nkeys)
        print(f"RESULT bench=reduce_post n={n} keys={nkeys} "
              f"bulk_ms={bulk * 1e3:.1f} stream_ms={strm * 1e3:.1f} "
              f"stream_speedup={bulk / strm:.3f}",
              flush=True)


if __name__ == "__main__":
    main()
