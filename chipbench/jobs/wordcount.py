"""Job kind ``wordcount``: Distribute -> ReduceByKey (sum of 1 per word)
-> the (word, count) table fetched to the host, which is what a
WordCount user wants.

The generator is a copy of ``chip_smoke.py``'s (PR 22); the reference
works from the packed input alone. Neither shares code with
``thrill_tpu``; only ``pipeline`` calls the program, through its public
API.
"""

from __future__ import annotations

import numpy as np

_COUNT_WORDS = None


def records(traffic: dict) -> int:
    return int(traffic["words_per_job"])


def vocabulary(rng, size: int, word_bytes: int) -> np.ndarray:
    """``size`` distinct zero-padded words of 4..word_bytes lowercase
    bytes."""
    vocab = rng.integers(ord("a"), ord("z") + 1,
                         size=(size, word_bytes)).astype(np.uint8)
    # the first four letters spell the word's index in base 26: distinct
    idx = np.arange(size)
    for j in range(4):
        vocab[:, j] = ord("a") + (idx // 26 ** j) % 26
    lens = rng.integers(4, word_bytes + 1, size=size)
    vocab[np.arange(word_bytes)[None, :] >= lens[:, None]] = 0
    return vocab


def generate(seed: int, traffic: dict, config: dict) -> dict:
    """``words_per_job`` packed words drawn Zipf(zipf_s) from a seeded
    vocabulary, a function of the seed alone."""
    n, size = records(traffic), int(traffic["vocabulary"])
    rng = np.random.default_rng(seed)
    vocab = vocabulary(rng, size, config["shapes"]["word_bytes"])
    p = 1.0 / np.arange(1, size + 1) ** float(traffic["zipf_s"])
    ids = rng.choice(size, size=n, p=p / p.sum())
    return {"w": vocab[ids], "c": np.ones(n, dtype=np.int64)}


def _word_key(t):
    return t["w"]


def pipeline(ctx, inp: dict) -> dict:
    """One job, from the host's words to the table on the host."""
    global _COUNT_WORDS
    if _COUNT_WORDS is None:
        # module-level functor: the program caches its compiled programs
        # on the function objects
        from thrill_tpu.api import FieldReduce
        _COUNT_WORDS = FieldReduce({"w": "first", "c": "sum"})
    got = ctx.Distribute(inp).ReduceByKey(_word_key, _COUNT_WORDS) \
        .AllGatherArrays()
    return {"w": np.asarray(got["w"]), "c": np.asarray(got["c"])}


def fetch(handle: dict) -> dict:
    return handle


def dispose(handle) -> None:
    """The table is on the host; the program keeps nothing of the job."""


def _words_u64(w: np.ndarray) -> np.ndarray:
    """[n, 16] bytes as [n, 2] big-endian u64: memcmp order is the
    lexicographic order of the two words."""
    return np.ascontiguousarray(w).view(">u8")


def _count(w: np.ndarray) -> dict:
    words = _words_u64(w)
    order = np.lexsort((words[:, 1], words[:, 0]))
    s = words[order]
    first = np.ones(len(s), dtype=bool)
    first[1:] = np.any(s[1:] != s[:-1], axis=1)
    starts = np.flatnonzero(first)
    counts = np.diff(np.append(starts, len(s))).astype(np.int64)
    return {"w": w[order[starts]], "c": counts}


def reference(inp: dict, traffic: dict) -> dict:
    """Every distinct word once, in memcmp order, with its exact count:
    sort the packed words, count the runs."""
    return _count(inp["w"])


def control(inp: dict, traffic: dict) -> dict:
    """The reference with one guarantee broken, the way a later PR would
    be tempted to: each half of the input pre-aggregated, the tables
    concatenated and the final merge left out, so a word of both halves
    appears twice with partial counts."""
    half = len(inp["w"]) // 2
    a, b = _count(inp["w"][:half]), _count(inp["w"][half:])
    return {"w": np.concatenate([a["w"], b["w"]]),
            "c": np.concatenate([a["c"], b["c"]])}


def compare(got: dict, want: dict) -> dict:
    """Exact, as multisets of (word, count) rows: the table's order is
    the program's own. Each number is (reading, limit)."""
    def rows(t):
        return np.concatenate(
            [_words_u64(t["w"]).astype(np.uint64),
             np.asarray(t["c"]).astype(np.uint64)[:, None]], axis=1)
    both = np.concatenate([rows(got), rows(want)])
    _, per_row = np.unique(both, axis=0, return_counts=True)
    # a row of the reference is distinct, so a row in both tables is seen
    # exactly twice
    return {"rows_missing": (abs(len(got["w"]) - len(want["w"])), 0),
            "rows_differing": (int(np.count_nonzero(per_row != 2)), 0)}


def min_bytes(traffic: dict, config: dict, want: dict | None):
    """What a job must move whatever implements it: every (word, count)
    row read once and every row of the table written once. Needs the
    reference's table for its row count."""
    if want is None:
        return None
    row = config["shapes"]["word_bytes"] + config["shapes"]["count_bytes"]
    return records(traffic) * row + len(want["w"]) * row
