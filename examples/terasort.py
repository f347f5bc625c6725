"""TeraSort: distributed sort of 100-byte records with 10-byte keys.

Reference: /root/reference/examples/terasort/terasort.cpp:30-43 —
Record { uint8_t key[10]; uint8_t value[90]; }, api::Sort by memcmp on
the key. TPU-native: keys and values live as device byte columns; the
sample sort classifies by two packed uint64 key words.
"""

from __future__ import annotations

import _bootstrap  # noqa: F401  (repo root on sys.path for CLI runs)


import numpy as np

from thrill_tpu.api import Context

KEY_BYTES = 10
VALUE_BYTES = 90


def generate_records(n: int, seed: int = 0):
    """Random TeraGen-style records as a columnar dict."""
    rng = np.random.default_rng(seed)
    return {
        "key": rng.integers(0, 256, size=(n, KEY_BYTES)).astype(np.uint8),
        "value": rng.integers(0, 256, size=(n, VALUE_BYTES)).astype(np.uint8),
    }


def record_key(r):
    """Module-level key extractor: compiled programs are cached on the
    function object, so a second terasort() reuses the first's."""
    return r["key"]


def terasort(ctx: Context, records) -> "DIA":
    d = ctx.Distribute(records)
    return d.Sort(key_fn=record_key)


def verify_sorted(out_records) -> bool:
    keys = np.asarray(out_records["key"])
    if len(keys) <= 1:
        return True
    prev, nxt = keys[:-1], keys[1:]
    # lexicographic compare rows
    for i in range(KEY_BYTES):
        lt = prev[:, i] < nxt[:, i]
        gt = prev[:, i] > nxt[:, i]
        if np.any(gt & ~lt):
            # only bad if all previous bytes equal
            eq = np.ones(len(prev), dtype=bool)
            for j in range(i):
                eq &= prev[:, j] == nxt[:, j]
            if np.any(gt & eq):
                return False
    return True


def main():
    import argparse
    import time
    parser = argparse.ArgumentParser(description="thrill_tpu TeraSort")
    parser.add_argument("--records", type=int, default=1_000_000)
    args = parser.parse_args()

    from thrill_tpu.api import Run

    def job(ctx):
        recs = generate_records(args.records)
        t0 = time.perf_counter()
        out = terasort(ctx, recs)
        out.Execute()
        dt = time.perf_counter() - t0
        gb = args.records * 100 / 1e9
        print(f"sorted {args.records} records ({gb:.2f} GB) in {dt:.3f}s "
              f"= {gb / dt:.3f} GB/s")

    Run(job)


if __name__ == "__main__":
    main()
