"""Decompose the on-chip TeraSort cost: upload vs sort vs gather.

Prints one RESULT line per component so the perf pass can target the
dominant one instead of guessing. Needs the chip: without one it fails
(JAX_PLATFORMS=cpu runs it on the CPU on purpose). The compile cache is
where JAX_COMPILATION_CACHE_DIR puts it.
"""

from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as np  # noqa: E402


def timeit(fn, iters=3, warmup=1):
    """Time fn with block_until_ready AND a per-iteration readback of a
    few result bytes, so the timed region ends on bytes that reached
    the host. Returns the fetch-inclusive mean; prints nothing
    itself."""
    import jax
    import numpy as _np

    def _force(out):
        out = jax.block_until_ready(out)
        leaf = jax.tree.leaves(out)[0]
        _np.asarray(leaf[:1])        # readback forces real completion
        return out

    for _ in range(warmup):
        _force(fn())
    t0 = time.perf_counter()
    for _ in range(iters):
        _force(fn())
    return (time.perf_counter() - t0) / iters


def main():
    import thrill_tpu  # noqa: F401
    from thrill_tpu.common.platform import require_accelerator

    require_accelerator()

    import jax
    import jax.numpy as jnp

    from thrill_tpu.core import keys as keymod
    from thrill_tpu.core.device_sort import argsort_words

    n = 1 << 20
    rng = np.random.default_rng(0)
    keys_h = rng.integers(0, 256, size=(n, 10)).astype(np.uint8)
    vals_h = rng.integers(0, 256, size=(n, 90)).astype(np.uint8)

    print(f"RESULT platform={jax.default_backend()} n={n}", flush=True)

    # 1. upload cost (host -> device)
    t0 = time.perf_counter()
    keys_d = jax.device_put(keys_h)
    vals_d = jax.device_put(vals_h)
    jax.block_until_ready((keys_d, vals_d))
    up = time.perf_counter() - t0
    print(f"RESULT step=upload_100mb time_ms={up*1000:.0f} "
          f"mb_s={100/up:.0f}", flush=True)

    # 2. encode key words only
    f_enc = jax.jit(lambda k: keymod.encode_key_words(k))
    dt = timeit(lambda: f_enc(keys_d))
    print(f"RESULT step=encode_words time_ms={dt*1000:.1f}", flush=True)

    # 3. argsort words only — A/B every device engine at this size
    #    (auto = chunked above 64K; radix = the Pallas stable-partition
    #    LSD engine, with and without the compiled kernel)
    def sort_only(k):
        words = keymod.encode_key_words(k)
        return argsort_words(list(words))

    prev_impl = os.environ.get("THRILL_TPU_SORT_IMPL")
    prev_pallas = os.environ.get("THRILL_TPU_PALLAS")
    for impl, pallas in (("auto", "0"), ("radix", "0"), ("radix", "1")):
        os.environ["THRILL_TPU_SORT_IMPL"] = impl
        os.environ["THRILL_TPU_PALLAS"] = pallas
        f_sort = jax.jit(sort_only)             # fresh trace per engine
        try:
            dt = timeit(lambda: f_sort(keys_d))
            print(f"RESULT step=argsort_words impl={impl} "
                  f"pallas={pallas} time_ms={dt*1000:.1f}", flush=True)
        except Exception as e:                  # engine fails: keep going
            print(f"RESULT step=argsort_words impl={impl} "
                  f"pallas={pallas} error={type(e).__name__}", flush=True)
    for var, prev in (("THRILL_TPU_SORT_IMPL", prev_impl),
                      ("THRILL_TPU_PALLAS", prev_pallas)):
        if prev is None:
            os.environ.pop(var, None)
        else:
            os.environ[var] = prev

    f_sort = jax.jit(sort_only)
    perm_d = jax.block_until_ready(f_sort(keys_d))

    # 4. payload gather only: [n, 90] u8 take along axis 0
    f_gather = jax.jit(lambda v, p: jnp.take(v, p, axis=0))
    dt_gather = timeit(lambda: f_gather(vals_d, perm_d))
    print(f"RESULT step=gather_90b_u8 time_ms={dt_gather*1000:.1f}",
          flush=True)

    # 4b. payload gather with payload packed as u32 words
    vals_u32 = jax.jit(
        lambda v: jax.lax.bitcast_convert_type(
            jnp.pad(v, ((0, 0), (0, 2))).reshape(n, 23, 4),
            jnp.uint32))(vals_d)
    vals_u32 = jax.block_until_ready(vals_u32)
    dt = timeit(lambda: f_gather(vals_u32, perm_d))
    print(f"RESULT step=gather_23w_u32 time_ms={dt*1000:.1f}", flush=True)

    # 4c. gather keys [n, 10] u8
    dt = timeit(lambda: f_gather(keys_d, perm_d))
    print(f"RESULT step=gather_10b_u8 time_ms={dt*1000:.1f}", flush=True)

    # 4d. HBM-bandwidth utilization (a measured roofline point): the
    # payload gather's traffic model is exact — 90 B random-read +
    # 90 B stream-write per row — so measured GB/s = 180n/t, derived
    # from step 4's timing (no re-run: chip minutes are budgeted).
    # Utilization is quoted against v5e-class peak (~820 GB/s).
    gbs = 180 * n / dt_gather / 1e9
    print(f"RESULT step=hbm_bandwidth_gather gb_s={gbs:.1f} "
          f"util_vs_820={gbs / 820:.3f}", flush=True)

    # 5. fused whole program (encode + sort + both gathers), like the
    #    W=1 Sort program — A/B over the packed-movement flag
    from thrill_tpu.core.rowmove import take_rows

    def fused(k, v):
        words = keymod.encode_key_words(k)
        perm = argsort_words(list(words))
        return take_rows(k, perm), take_rows(v, perm)

    best_fused = None
    for mode in ("1", "0"):
        os.environ["THRILL_TPU_PACK_MOVE"] = mode
        f_all = jax.jit(lambda k, v: fused(k, v))  # fresh trace per mode
        dt = timeit(lambda: f_all(keys_d, vals_d))
        best_fused = dt if best_fused is None else min(best_fused, dt)
        print(f"RESULT step=fused_sort_gather pack={mode} "
              f"time_ms={dt*1000:.1f} mrec_s={n/dt/1e6:.2f}", flush=True)
    os.environ.pop("THRILL_TPU_PACK_MOVE", None)
    # modeled traffic for the fused W=1 program (~480 B argsort state
    # + 20 B key gather + 180 B payload gather ≈ 680 B/row) — softer
    # than the gather-only figure
    gbs_f = 680 * n / best_fused / 1e9
    print(f"RESULT step=hbm_bandwidth_fused_model gb_s={gbs_f:.1f} "
          f"util_vs_820={gbs_f / 820:.3f}", flush=True)

    # 6. per-dispatch overhead (tiny program)
    f_tiny = jax.jit(lambda x: x + 1)
    x1 = jax.device_put(np.zeros(8, np.float32))
    dt = timeit(lambda: f_tiny(x1), iters=20)
    print(f"RESULT step=dispatch_tiny time_ms={dt*1000:.2f}", flush=True)

    # 7. device->host fetch of the [W,W] counts analog (tiny fetch)
    t_small = jax.device_put(np.zeros((1, 1), np.int32))
    dt = timeit(lambda: np.asarray(t_small), iters=20)
    print(f"RESULT step=fetch_tiny time_ms={dt*1000:.2f}", flush=True)


if __name__ == "__main__":
    main()
