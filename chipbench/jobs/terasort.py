"""Job kind ``terasort``: Distribute -> Sort by the key -> sorted shards
ready on the devices (the upstream example's generate -> Sort -> Size
mode leaves the result distributed).

The generator and the reference are copies of ``chip_smoke.py``'s (PR 22)
and share no code with ``thrill_tpu``; only ``pipeline``, ``fetch`` and
``dispose`` call the program, through its public API.
"""

from __future__ import annotations

import numpy as np


def records(traffic: dict) -> int:
    return int(traffic["records_per_job"])


def generate(seed: int, traffic: dict, config: dict) -> dict:
    """``records_per_job`` records of uniform random bytes, a function of
    the seed alone."""
    shapes = config["shapes"]
    n = records(traffic)
    rng = np.random.default_rng(seed)
    return {
        "key": rng.integers(0, 256, size=(n, shapes["key_bytes"]),
                            dtype=np.uint8),
        "value": rng.integers(0, 256, size=(n, shapes["value_bytes"]),
                              dtype=np.uint8),
    }


def record_key(r):
    """Module-level: the program caches its compiled programs on the
    key function's identity."""
    return r["key"]


def pipeline(ctx, inp: dict):
    """One job, from the host's records to the sorted shards ready on
    the devices. Returns the DIA that holds them."""
    import jax
    out = ctx.Distribute(inp).Sort(key_fn=record_key)
    out.Keep()
    jax.block_until_ready(out.node.materialize().tree)
    return out


def fetch(handle) -> dict:
    """The whole result on the host (0.84 GB at 2^23 records): outside
    every timed job."""
    got = handle.AllGatherArrays()
    return {"key": np.asarray(got["key"]), "value": np.asarray(got["value"])}


def dispose(handle) -> None:
    handle.Dispose()


def order_rows(rows: np.ndarray) -> np.ndarray:
    """Lexicographic (memcmp) order of the rows of a [n, k] uint8 array:
    bytes packed big-endian into u64 words, np.lexsort over the words."""
    n, k = rows.shape
    pad = (-k) % 8
    if pad:
        rows = np.concatenate([rows, np.zeros((n, pad), np.uint8)], axis=1)
    words = np.ascontiguousarray(rows).view(">u8")
    # lexsort sorts by the LAST key first
    return np.lexsort(tuple(words[:, j]
                            for j in range(words.shape[1] - 1, -1, -1)))


def reference(inp: dict, traffic: dict) -> dict:
    """The input's records in key order. Refuses duplicate keys: Sort is
    free on ties, so a byte-for-byte comparison would be unsound."""
    order = order_rows(inp["key"])
    key = inp["key"][order]
    if len(key) > 1 and not np.all(np.any(key[1:] != key[:-1], axis=1)):
        raise ValueError("terasort: duplicate keys in the input; Sort is "
                         "free on ties, so the comparison would be unsound")
    return {"key": key, "value": inp["value"][order]}


def control(inp: dict, traffic: dict) -> dict:
    """The reference with one guarantee broken, the way a later PR would
    be tempted to: records ordered by a PREFIX of the key only (fewer key
    words to sort), ties left in input order. The prefix is the longest
    that still leaves at least 64 expected ties at this n."""
    n = len(inp["key"])
    prefix = max(1, (2 * int(np.log2(max(n, 2))) - 7) // 8)
    order = order_rows(inp["key"][:, :prefix])
    return {"key": inp["key"][order], "value": inp["value"][order]}


def compare(got: dict, want: dict) -> dict:
    """Exact: every record in its place with its value. Each number is
    (reading, limit)."""
    n_got, n_want = len(got["key"]), len(want["key"])
    m = min(n_got, n_want)
    if np.array_equal(got["key"][:m], want["key"][:m]) \
            and np.array_equal(got["value"][:m], want["value"][:m]):
        differing = 0
    else:
        differing = int(np.count_nonzero(
            np.any(got["key"][:m] != want["key"][:m], axis=1)
            | np.any(got["value"][:m] != want["value"][:m], axis=1)))
    return {"rows_missing": (abs(n_got - n_want), 0),
            "rows_differing": (differing, 0)}


def min_bytes(traffic: dict, config: dict, want: dict | None) -> int:
    """What a job must move whatever implements it: every record read
    once and written once."""
    shapes = config["shapes"]
    return 2 * records(traffic) * (shapes["key_bytes"]
                                   + shapes["value_bytes"])
