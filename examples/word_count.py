"""WordCount: the canonical FlatMap + ReduceByKey pipeline.

Reference: /root/reference/examples/word_count/word_count.hpp:35-57
(FlatMap split + ReduceByKey sum). Two variants:

* ``word_count``     — faithful text pipeline (host storage for strings)
* ``word_count_fixed`` — TPU-native: words packed into fixed-width byte
  vectors on device, the whole aggregation running as jitted programs.
"""

from __future__ import annotations

import _bootstrap  # noqa: F401  (repo root on sys.path for CLI runs)


import numpy as np

from thrill_tpu.api import Context, FieldReduce


def word_count(ctx: Context, path_or_lines):
    """Returns a DIA of (word, count) pairs from text."""
    if isinstance(path_or_lines, str):
        lines = ctx.ReadLines(path_or_lines)
    else:
        lines = ctx.Distribute(list(path_or_lines), storage="host")
    return (lines
            .FlatMap(lambda line: line.split())
            .Map(lambda w: (w, 1))
            .ReduceByKey(lambda kv: kv[0],
                         lambda a, b: (a[0], a[1] + b[1])))


MAX_WORD = 16   # device variant: words truncated/padded to 16 bytes


def pack_words(words) -> np.ndarray:
    """Pack a list of strings into [n, MAX_WORD] uint8 (zero padded).

    Row i always corresponds to words[i] — empty strings keep their
    (all-zero) row; the byte packing itself is one vectorized gather."""
    enc = [w.encode("utf-8")[:MAX_WORD] for w in words]
    lens = np.fromiter((len(b) for b in enc), np.int64, count=len(enc))
    buf = np.frombuffer(b"".join(enc), dtype=np.uint8)
    if buf.size == 0:
        return np.zeros((len(words), MAX_WORD), dtype=np.uint8)
    offs = np.concatenate(([0], np.cumsum(lens)))[:-1]
    idx = offs[:, None] + np.arange(MAX_WORD)[None, :]
    valid = np.arange(MAX_WORD)[None, :] < lens[:, None]
    return np.where(valid, buf[np.where(valid, idx, 0)],
                    0).astype(np.uint8)


def word_count_text_device(ctx: Context, path: str,
                           max_word: int = MAX_WORD):
    """Device WordCount straight from a text file: vectorized
    tokenization into packed byte rows (ctx.ReadWordsPacked), then the
    whole aggregation as jitted device programs. Returns a DIA of
    {"w": [max_word] u8, "c": count} rows (use
    thrill_tpu.core.text.unpack_words to recover strings)."""
    import jax.numpy as jnp

    words = ctx.ReadWordsPacked(path, max_word=max_word)
    # ones_like(..[..., 0]) yields [n] on the batched device tree and a
    # scalar on a single host item — valid under both Map contracts
    pairs = words.Map(lambda t: {
        "w": t["w"],
        "c": jnp.ones_like(t["w"][..., 0], dtype=jnp.int64)})
    # declarative functor: the host local phase fuses the whole
    # aggregation into one native hash-probe pass (the analog of the
    # reference's std::plus being template-inlined into its table)
    return pairs.ReduceByKey(lambda t: t["w"],
                             FieldReduce({"w": "first", "c": "sum"}))


def _word_key(t):
    return t["w"]


# module-level functors: compiled programs are cached on the function
# objects, so a second word_count_fixed() reuses the first's
_COUNT_WORDS = FieldReduce({"w": "first", "c": "sum"})


def word_count_fixed(ctx: Context, packed: np.ndarray):
    """Device WordCount over pre-packed fixed-width words.

    The reduce runs fully on device: key = the byte vector itself
    (encoded to uint64 words), value = count.
    """
    d = ctx.Distribute({"w": packed,
                        "c": np.ones(len(packed), dtype=np.int64)})
    return d.ReduceByKey(_word_key, _COUNT_WORDS)


def main():
    import argparse
    parser = argparse.ArgumentParser(description="thrill_tpu WordCount")
    parser.add_argument("input", help="text file/glob")
    parser.add_argument("--top", type=int, default=10)
    args = parser.parse_args()

    from thrill_tpu.api import Run

    def job(ctx):
        counts = word_count(ctx, args.input).AllGather()
        counts.sort(key=lambda kv: -kv[1])
        for w, c in counts[:args.top]:
            print(f"{c:8d}  {w}")

    Run(job)


if __name__ == "__main__":
    main()
