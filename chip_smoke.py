#!/usr/bin/env python3
"""The quickest proof that thrill_tpu still starts on the chip.

    python chip_smoke.py              one TPU chip: terasort, wordcount, kernels
    python chip_smoke.py --chips 4    four chips: TeraSort through the exchange
    python chip_smoke.py --rehearse   CPU rehearsal at a tiny size (tests only)

One process, no child, no fallback. Without a TPU it says why and exits
non-zero. Every phase checks its result against a numpy reference and a
phase that raises ends the run with its traceback. The last line of a
passing run on the chip is the contract line

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

``--rehearse`` is the only way the script runs on a CPU: it turns the
native host sort path off so that the device programs are what gets
rehearsed, runs the Pallas kernels in interpret mode, and ends with a
line of its own that is never the contract line.

Everything printed before the last line is a smoke reading (one cold and
one warm call in a machine nobody tuned), not a benchmark number.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

_ROOT = os.path.dirname(os.path.abspath(__file__))
NATIVE_SOURCES = ("blockstore.cpp", "dispatcher.cpp", "hostsort.cpp",
                  "mwmerge.cpp", "records.cpp")
# 2^23 records = 0.84 GB resident in HBM. 2^24 sorts once on a 16 GB
# v5e (the W=1 sort program wants 15.25 of the chip's 15.75 GiB, 11.75
# of them temporaries of the packed row gather) but a second sort in
# the same Context is refused: "RESOURCE_EXHAUSTED: Error loading
# program 'jit_f': Attempting to reserve 11.75G at the bottom of
# memory. That was not possible. There are 10.50G free" (PERF.md, PR 22)
DEFAULT_RECORDS = 1 << 23
WORDCOUNT_WORDS = 1 << 22
WORDCOUNT_VOCAB = 1 << 16
ZIPF_S = 1.1


def _say(msg: str) -> None:
    print(msg, flush=True)


def _order_rows(rows):
    """Lexicographic (memcmp) order of the rows of a [n, k] uint8 array:
    bytes packed big-endian into u64 words, np.lexsort over the words.
    Independent of everything in thrill_tpu."""
    import numpy as np
    n, k = rows.shape
    pad = (-k) % 8
    if pad:
        rows = np.concatenate(
            [rows, np.zeros((n, pad), np.uint8)], axis=1)
    words = np.ascontiguousarray(rows).view(">u8")      # [n, ceil(k/8)]
    # lexsort sorts by the LAST key first
    return np.lexsort(tuple(words[:, j]
                            for j in range(words.shape[1] - 1, -1, -1)))


def _assert_rows_unique_sorted(sorted_rows, what: str) -> None:
    import numpy as np
    if len(sorted_rows) > 1 and not np.all(
            np.any(sorted_rows[1:] != sorted_rows[:-1], axis=1)):
        raise AssertionError(
            f"{what}: duplicate keys in the reference; Sort is free on "
            f"ties, so the byte-for-byte comparison would be unsound")


def _native_libraries() -> None:
    from thrill_tpu.common.native_build import build_and_load
    loaded = {s: build_and_load(s) is not None for s in NATIVE_SOURCES}
    _say("native libraries: " + ", ".join(
        f"{s[:-4]}={'loaded' if ok else 'MISSING'}"
        for s, ok in loaded.items()))
    missing = [s for s, ok in loaded.items() if not ok]
    if missing:
        raise RuntimeError(
            f"native libraries did not build or load: {missing} "
            f"(g++ missing or failing on this machine?)")


def _cache_line() -> str:
    import jax
    where = jax.config.jax_compilation_cache_dir
    if where is None:
        return "compile cache: off"
    by = "from JAX_COMPILATION_CACHE_DIR" \
        if os.environ.get("JAX_COMPILATION_CACHE_DIR") \
        else "set by thrill_tpu"
    return f"compile cache: dir={where!r} ({by})"


def _stats_line(ctx) -> str:
    st = ctx.overall_stats()
    keys = ("device_dispatches", "device_uploads", "device_fetches",
            "exchanges", "bytes_moved", "oom_retries", "segment_splits",
            "host_fallbacks", "admission_spills", "pressure_spilled_bytes",
            "hbm_spills", "hbm_restores",
            # the host phases (seconds on the host's clock, bytes)
            "upload_s", "upload_bytes", "fetch_s", "fetch_bytes",
            "sync_wait_s", "compiles", "compile_s")
    return "stats: " + " ".join(
        f"{k}={st[k]:.3f}" if isinstance(st[k], float) else f"{k}={st[k]}"
        for k in keys)


def _hbm(devices, stat: str) -> str:
    """One ``device.memory_stats()`` entry for every device."""
    vals = [(d.memory_stats() or {}).get(stat) for d in devices]
    return ", ".join("not reported" if v is None else str(v) for v in vals)


def _decision_lines(ctx, kinds) -> list:
    out = []
    for rec in ctx.decisions.snapshot():
        if rec.get("kind") in kinds:
            out.append(f"decision {rec['kind']} site={rec.get('site')} "
                       f"chosen={rec.get('chosen')} "
                       f"reason={rec.get('reason')!r}")
    return out


def _sort_records(ctx, recs):
    """Distribute + Sort + wait. Returns (DIA, device shards, seconds)."""
    import jax
    from terasort import terasort
    t0 = time.perf_counter()
    out = terasort(ctx, recs)
    out.Keep()
    shards = out.node.materialize()
    jax.block_until_ready(shards.tree)
    return out, shards, time.perf_counter() - t0


def phase_terasort(n: int, seed: int, chips: int, devices) -> None:
    import numpy as np
    import jax
    from terasort import KEY_BYTES, VALUE_BYTES, generate_records
    from thrill_tpu.api import Run

    t0 = time.perf_counter()
    recs = generate_records(n, seed)
    order = _order_rows(recs["key"])
    ref_key = recs["key"][order]
    _assert_rows_unique_sorted(ref_key, "terasort")
    ref_val = recs["value"][order]
    del order
    _say(f"terasort: n={n} bytes={n * (KEY_BYTES + VALUE_BYTES)} "
         f"seed={seed}; records + numpy reference in "
         f"{time.perf_counter() - t0:.1f}s")

    def job(ctx):
        mex = ctx.mesh_exec
        if mex.num_workers != chips:
            raise AssertionError(
                f"Run() took {mex.num_workers} devices, wanted {chips}")
        _say(_cache_line())
        out, shards, first = _sort_records(ctx, recs)
        _say(f"terasort first call (upload + compile + run): {first:.2f}s")
        for line in _decision_lines(ctx, ("sort_engine", "xchg_strategy",
                                         "xchg_chunks")):
            _say(line)
        _say("sort engine env pin: THRILL_TPU_SORT_IMPL="
             f"{os.environ.get('THRILL_TPU_SORT_IMPL', 'auto')}")

        devsets = [l.sharding.device_set
                   for l in jax.tree.leaves(shards.tree)]
        _say(f"result shards on {len(devsets[0])} device(s): "
             f"{sorted(str(d) for d in devsets[0])}")
        if any(len(s) != chips for s in devsets):
            raise AssertionError(
                f"result is not spread over {chips} distinct devices")

        got = out.AllGatherArrays()
        got_key, got_val = np.asarray(got["key"]), np.asarray(got["value"])
        if got_key.shape != ref_key.shape or got_val.shape != ref_val.shape:
            raise AssertionError(
                f"shape {got_key.shape}/{got_val.shape} != "
                f"{ref_key.shape}/{ref_val.shape}")
        if not (np.array_equal(got_key, ref_key)
                and np.array_equal(got_val, ref_val)):
            bad = int(np.argmax(np.any(got_key != ref_key, axis=1)
                                | np.any(got_val != ref_val, axis=1)))
            raise AssertionError(
                f"terasort differs from the numpy reference, first at "
                f"row {bad}")
        _say("terasort: keys and values equal the numpy reference, "
             "byte for byte")
        # the second call needs the HBM back: drop every reference to
        # the first result before it starts
        del got, got_key, got_val, shards, devsets
        out.Dispose()
        del out
        _say("HBM bytes in use before the second call: "
             + _hbm(mex.devices, "bytes_in_use"))

        out2, _, second = _sort_records(ctx, recs)
        _say(f"terasort second call (upload + run, warm): {second:.2f}s")
        k2 = np.asarray(out2.AllGatherArrays()["key"])
        if not np.array_equal(k2, ref_key):
            raise AssertionError("second terasort call differs")
        out2.Dispose()

        st = ctx.overall_stats()
        _say(_stats_line(ctx))
        _say("programs built: " + ", ".join(sorted(
            {k[0] for k in mex._cache
             if isinstance(k, tuple) and k and isinstance(k[0], str)})))
        if chips > 1:
            from thrill_tpu.data.exchange import resolve_mode
            _say(f"exchange mode: {resolve_mode(mex)}")
            if st["exchanges"] < 1 or st["bytes_moved"] <= 0:
                raise AssertionError(
                    f"no exchange moved bytes: exchanges="
                    f"{st['exchanges']} bytes_moved={st['bytes_moved']}")
        if st["oom_retries"] or st["segment_splits"] \
                or st["host_fallbacks"] or st["pressure_spilled_bytes"]:
            _say("NOTE: this run only got through by the OOM ladder "
                 "(retries / degraded dispatches / spills above)")
        _say(f"peak HBM bytes per device: "
             f"{_hbm(mex.devices, 'peak_bytes_in_use')} "
             f"(limit {_hbm(mex.devices, 'bytes_limit')})")

    Run(job, devices=devices, seed=seed)


def _vocabulary(rng, size: int):
    """``size`` distinct zero-padded words of 4..16 lowercase bytes."""
    import numpy as np
    from word_count import MAX_WORD
    vocab = rng.integers(ord("a"), ord("z") + 1,
                         size=(size, MAX_WORD)).astype(np.uint8)
    # the first four letters spell the word's index in base 26: distinct
    idx = np.arange(size)
    for j in range(4):
        vocab[:, j] = ord("a") + (idx // 26 ** j) % 26
    lens = rng.integers(4, MAX_WORD + 1, size=size)
    vocab[np.arange(MAX_WORD)[None, :] >= lens[:, None]] = 0
    return vocab


def phase_wordcount(n: int, vocab_size: int, seed: int, devices) -> None:
    import numpy as np
    import jax
    from word_count import word_count_fixed
    from thrill_tpu.api import Run

    rng = np.random.default_rng(seed)
    vocab = _vocabulary(rng, vocab_size)
    p = 1.0 / np.arange(1, vocab_size + 1) ** ZIPF_S
    ids = rng.choice(vocab_size, size=n, p=p / p.sum())
    packed = vocab[ids]
    uniq, counts = np.unique(ids, return_counts=True)
    ref_w = vocab[uniq]
    o = _order_rows(ref_w)
    ref_w, ref_c = ref_w[o], counts[o].astype(np.int64)
    _assert_rows_unique_sorted(ref_w, "wordcount")
    _say(f"wordcount: n={n} words of {packed.shape[1]} bytes, "
         f"Zipf({ZIPF_S}) over {vocab_size} words, "
         f"{len(uniq)} distinct drawn, seed={seed}")

    def once(ctx):
        t0 = time.perf_counter()
        got = word_count_fixed(ctx, packed).AllGatherArrays()
        jax.block_until_ready(got)
        return got, time.perf_counter() - t0

    def job(ctx):
        got, first = once(ctx)
        _say(f"wordcount first call (upload + compile + run): {first:.2f}s")
        w, c = np.asarray(got["w"]), np.asarray(got["c"])
        o = _order_rows(w)
        if not (np.array_equal(w[o], ref_w) and np.array_equal(c[o], ref_c)):
            raise AssertionError(
                f"wordcount table differs from np.unique: "
                f"{len(w)} rows against {len(ref_w)}")
        _say(f"wordcount: all {len(ref_w)} (word, count) rows equal "
             f"np.unique")
        _, second = once(ctx)
        _say(f"wordcount second call (upload + run, warm): {second:.2f}s")
        _say(_stats_line(ctx))

    Run(job, devices=devices, seed=seed)


def phase_kernels(n: int, interpret: bool, seed: int) -> None:
    """Each Pallas kernel against its jnp fallback, at the largest size
    its own gate admits on the chip (compiled), tiny in a rehearsal
    (interpreted)."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    from thrill_tpu.core import pallas_kernels as pk
    from thrill_tpu.core import pallas_sort as ps

    if not pk.rows_ok(n):
        raise AssertionError(f"n={n} is past the kernels' row gate")
    nbins, nseg, nreg = 256, pk.SEGSUM_MAX_SEGS, pk.PRESFILL_MAX_REGS
    if not (pk.segment_sum_ok(nseg, n) and pk.presence_fill_ok(nreg, n)):
        raise AssertionError("gate-edge sizes refused by their own gates")
    rng = np.random.default_rng(seed)
    dest = jnp.asarray(rng.integers(0, nbins, n).astype(np.int32))
    seg = jnp.asarray(rng.integers(0, nseg, n).astype(np.int32))
    # small integers: every f32 partial sum is exact, so the kernel and
    # the fallback agree bit for bit whatever their summation order
    vals = jnp.asarray(rng.integers(0, 4, n).astype(np.float32))
    # leave some registers empty so presence is not all-ones
    regs = jnp.asarray(rng.integers(0, nreg // 2, n).astype(np.int32))
    valid = jnp.asarray(rng.integers(0, 2, n).astype(bool))

    cases = [
        ("partition_histogram_pallas", (dest,),
         lambda d: pk.partition_histogram_pallas(d, nbins,
                                                 interpret=interpret),
         lambda d: pk.partition_histogram(d, nbins)),
        ("segment_sum_pallas", (seg, vals),
         lambda s, v: pk.segment_sum_pallas(s, v, nseg,
                                            interpret=interpret),
         lambda s, v: pk.segment_sum(s, v, nseg)),
        ("presence_fill_pallas", (regs, valid),
         lambda h, v: pk.presence_fill_pallas(h, v, nreg,
                                              interpret=interpret),
         lambda h, v: pk.presence_fill(h, v, nreg)),
        ("stable_partition_offsets_pallas", (dest,),
         lambda d: ps.stable_partition_offsets_pallas(
             d, nbins, interpret=interpret),
         lambda d: ps._offsets_scan(d, nbins)),
    ]
    if os.environ.get("THRILL_TPU_PALLAS") == "1":
        raise AssertionError(
            "THRILL_TPU_PALLAS=1 is set: the dispatchers would take the "
            "kernel branch and there would be no fallback to compare")
    for name, args, kernel, fallback in cases:
        t0 = time.perf_counter()
        kfn = jax.jit(kernel)
        if not interpret and "tpu_custom_call" not in \
                kfn.lower(*args).as_text():
            raise AssertionError(f"{name}: no tpu_custom_call lowered")
        got = np.asarray(kfn(*args))
        want = np.asarray(jax.jit(fallback)(*args))
        if got.shape != want.shape or not np.array_equal(got, want):
            raise AssertionError(f"{name} differs from its jnp fallback")
        _say(f"kernel {name}: n={n} "
             f"{'interpreted' if interpret else 'compiled'}, equal to its "
             f"fallback ({time.perf_counter() - t0:.2f}s)")

    if interpret:
        _say("kernel dispatcher branch: not checked in a rehearsal "
             "(pallas_enabled() needs a TPU backend)")
        return
    os.environ["THRILL_TPU_PALLAS"] = "1"
    try:
        text = jax.jit(
            lambda d: pk.partition_histogram(d, nbins)).lower(dest).as_text()
    finally:
        del os.environ["THRILL_TPU_PALLAS"]
    if "tpu_custom_call" not in text:
        raise AssertionError(
            "partition_histogram under THRILL_TPU_PALLAS=1 did not take "
            "the kernel branch")
    _say("kernel dispatcher: partition_histogram under THRILL_TPU_PALLAS=1 "
         "lowers to tpu_custom_call")


def _phase(name: str, fn, *args) -> None:
    _say(f"== phase {name}")
    t0 = time.perf_counter()
    fn(*args)
    _say(f"== phase {name} passed in {time.perf_counter() - t0:.1f}s")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--records", type=int, default=DEFAULT_RECORDS,
                    help="TeraSort records of 100 bytes (default 2^23)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only TeraSort across four chips and its "
                         "reference")
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal at a tiny size; never prints the "
                         "contract line")
    args = ap.parse_args(argv)
    t_start = time.perf_counter()

    for p in (_ROOT, os.path.join(_ROOT, "examples")):
        if p not in sys.path:
            sys.path.insert(0, p)
    if args.rehearse:
        # rehearse what the chip runs: the jitted device programs with
        # the accelerator's key and row layouts, not the native host path
        os.environ["THRILL_TPU_HOST_RADIX"] = "0"
        os.environ["THRILL_TPU_SORT_U32"] = "1"
        os.environ["THRILL_TPU_PACK_MOVE"] = "1"

    import jax
    import thrill_tpu  # noqa: F401  (the way a user does; turns x64 on)
    devices = jax.devices()
    dev = devices[0]
    if args.rehearse:
        if dev.platform != "cpu":
            _say(f"--rehearse is for the CPU; JAX found {dev.platform}")
            return 2
    elif dev.platform != "tpu":
        _say(f"chip_smoke: JAX found no TPU (platform={dev.platform!r}, "
             f"{len(devices)} device(s)); this check runs on the chip "
             f"only. Use --rehearse for a CPU rehearsal.")
        return 2
    if len(devices) < args.chips:
        _say(f"chip_smoke: --chips {args.chips} but JAX found "
             f"{len(devices)} device(s)")
        return 2
    # Run() takes every local device, as a user's would; only a host
    # with more devices than asked for is cut down
    run_devices = None if len(devices) == args.chips \
        else devices[:args.chips]
    _say(f"device: platform={dev.platform} kind={dev.device_kind} "
         f"count={len(devices)} jax={jax.__version__}")

    _phase("native", _native_libraries)
    _phase("terasort", phase_terasort, args.records, args.seed, args.chips,
           run_devices)
    if args.chips == 1:
        wc_n = 1 << 12 if args.rehearse else WORDCOUNT_WORDS
        wc_v = 1 << 8 if args.rehearse else WORDCOUNT_VOCAB
        _phase("wordcount", phase_wordcount, wc_n, wc_v, args.seed,
               run_devices)
        from thrill_tpu.core.pallas_kernels import MAX_ROWS
        _phase("kernels", phase_kernels,
               (1 << 11) + 3 if args.rehearse else MAX_ROWS - 1,
               args.rehearse, args.seed)
    _say(f"all phases passed in {time.perf_counter() - t_start:.1f}s")

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices)}
    if args.rehearse:
        print(json.dumps({"ok": False, "rehearsal": "passed",
                          "device": device}), flush=True)
        return 0
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
