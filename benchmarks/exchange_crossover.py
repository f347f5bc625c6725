"""Measure the dense-vs-1-factor exchange crossover on the ACTUAL mesh.

The dense all_to_all pads every (src, dst) cell to the global maximum —
cheap padding, one launch. The 1-factor schedule pads each round to its
pair maximum — minimal padding, W-1 serialized launches. The crossover
is a latency/bandwidth tradeoff, so the constants must be measured, not
guessed (VERDICT r2, weak #8):

  * round_overhead_s: wall-clock of one near-empty exchange launch
    (program dispatch + collective setup), measured as the slope of
    1-factor total time over its round count at tiny payload.
  * exchange_bw: bytes/s through the padded dense exchange at large
    uniform payload.

  bytes_eq = round_overhead_s * exchange_bw   — the padded-byte volume
  whose transfer costs as much as one extra round launch. The runtime
  model (exchange._prefer_onefactor) picks 1-factor iff the padding it
  saves exceeds bytes_eq per extra launch.

Prints RESULT lines; run on the virtual 8-device CPU mesh (this image)
or any real TPU mesh unchanged.
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

from thrill_tpu.data import exchange  # noqa: E402
from thrill_tpu.data.shards import DeviceShards  # noqa: E402
from thrill_tpu.parallel.mesh import MeshExec  # noqa: E402


def _mk_shards(mex, rows_per_worker: int, row_u64: int) -> DeviceShards:
    W = mex.num_workers
    rng = np.random.default_rng(0)
    tree = {"x": rng.integers(0, 1 << 30,
                              size=(W, rows_per_worker, row_u64)
                              ).astype(np.uint64)}
    counts = np.full(W, rows_per_worker, dtype=np.int64)
    return DeviceShards(mex, jax.tree.map(mex.put, tree), counts)


def _run_exchange(mex, shards, mode: str, iters: int, ident) -> float:
    os.environ["THRILL_TPU_EXCHANGE"] = mode
    # calibration must time the REQUESTED plan: pin the crossover so the
    # cost model under calibration cannot reroute the dense measurement
    os.environ["THRILL_TPU_XCHG_BYTES_EQ"] = str(1 << 62)
    mex.exchange_mode = mode
    W = mex.num_workers

    def dest(tree, mask, widx):
        import jax.numpy as jnp
        # uniform round-robin destinations: every cell equal
        n = tree["x"].shape[0]
        return (jnp.arange(n, dtype=jnp.int32) % W)

    def once():
        out = exchange.exchange(shards, dest, ident + (mode,))
        jax.block_until_ready(jax.tree.leaves(out.tree))
        np.asarray(jax.tree.leaves(out.tree)[0])[:1]

    once()                                  # compile
    t0 = time.perf_counter()
    for _ in range(iters):
        once()
    return (time.perf_counter() - t0) / iters


def main():
    mex = MeshExec()
    W = mex.num_workers
    if W < 2:
        print(f"RESULT bench=exchange_crossover error=single_worker W={W}")
        return

    # 1) round overhead: tiny payload, dense (1 launch) vs 1-factor
    #    (W-1 launches); slope over launch count = per-round overhead
    tiny = _mk_shards(mex, 64, 1)
    t_dense_tiny = _run_exchange(mex, tiny, "dense", 20, ("xco_tiny",))
    t_of_tiny = _run_exchange(mex, tiny, "onefactor", 20, ("xco_tiny",))
    round_overhead = max(t_of_tiny - t_dense_tiny, 1e-9) / max(W - 2, 1)

    # 2) effective exchange bandwidth: large uniform payload, dense
    rows, row_u64 = 1 << 14, 16                 # 2 MiB/worker
    big = _mk_shards(mex, rows, row_u64)
    t_dense_big = _run_exchange(mex, big, "dense", 5, ("xco_big",))
    # fabric bytes only (exclude each worker's 1/W self-share) — the
    # same units the runtime cost model compares
    bytes_moved = (W - 1) * rows * row_u64 * 8
    bw = bytes_moved / t_dense_big

    bytes_eq = round_overhead * bw
    print(f"RESULT bench=exchange_crossover platform={jax.default_backend()} "
          f"W={W} round_overhead_us={round_overhead * 1e6:.1f} "
          f"exchange_bw_mb_s={bw / 1e6:.0f} "
          f"bytes_eq_per_round={int(bytes_eq)}", flush=True)


if __name__ == "__main__":
    main()
