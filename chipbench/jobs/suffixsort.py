"""Job kind ``suffixsort``: Distribute (the text, once) -> names by a
padded Window(4), Sort, Window(2), ExPrefixSum -> doubling rounds (Zip,
Map bound to h, Sort, Window(2) bound to h, Sort, Window(2),
ExPrefixSum, and a ``Max`` read back that decides whether another
follows) -> the suffix array on the host, which is what someone who
builds a text index wants.

The job is Thrill's ``examples/suffix_sorting/prefix_doubling.cpp``
(Bingmann, Gog, Kurpicz, arXiv:1610.03007): plain prefix doubling "using
sorting and windows". ONE implementation of the pipeline exists, the
program's own example ``examples/suffix_sorting.py suffix_array``,
which ``pipeline`` calls; the generator, the reference, the checker and
the control below share nothing with it nor with ``thrill_tpu``.
A job's doubling rounds are its ``fetches_per_job`` - 1 in any result
line; the handle ``pipeline`` returns carries them too, and ``compare``
holds them to what the text needs (``rounds_differing``).

The text is Zipf words with ONE planted repeat (``planted_repeat_bytes``
of the traffic file): plain Zipf-word text has its longest repeated
substring on both sides of 64 bytes from seed to seed, where a fifth
round begins, so seeds ran jobs of four rounds or of five, 20 % apart.
A repeat of 160 bytes is the longest of the text on every seed and lies
in 128..255: six rounds from ``h`` = 4 (the traffic file's ``rounds``,
printed beside what the text needed as ``rounds_needed``).
"""

from __future__ import annotations

import importlib.util
import os
import sys

import numpy as np

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_example = []
# The span readers need a traced window's records whole, and this job
# writes about 30 a round: a warm-up job and three traced ones of eight
# pulls each come to some 1,000, where the program's flight recorder
# keeps 512 unless told otherwise. The harness loads a job kind before
# it makes its ``Run()``, which is where the Tracer reads this.
os.environ.setdefault("THRILL_TPU_TRACE_RING", "4096")


def records(traffic: dict) -> int:
    """One record is one suffix placed."""
    return int(traffic["chars"])


# -------------------------------------------------------------- generator

def vocabulary(rng, size: int, lo: int, hi: int) -> np.ndarray:
    """``size`` distinct zero-padded words of lo..hi lowercase bytes (a
    copy of ``wordcount``'s: job kinds import nothing of each other)."""
    vocab = rng.integers(ord("a"), ord("z") + 1,
                         size=(size, hi)).astype(np.uint8)
    # the first four letters spell the word's index in base 26: distinct
    idx = np.arange(size)
    for j in range(4):
        vocab[:, j] = ord("a") + (idx // 26 ** j) % 26
    lens = rng.integers(lo, hi + 1, size=size)
    vocab[np.arange(hi)[None, :] >= lens[:, None]] = 0
    return vocab


def zipf_text(rng, n: int, traffic: dict) -> np.ndarray:
    """``n`` bytes: words drawn Zipf(zipf) from a seeded vocabulary,
    joined by single spaces, cut to length."""
    size = int(traffic["words"])
    lo, hi = (int(x) for x in str(traffic["word_letters"]).split("-"))
    vocab = vocabulary(rng, size, lo, hi)
    p = 1.0 / np.arange(1, size + 1) ** float(traffic["zipf"])
    # a word and its space are at least lo + 1 bytes
    words = vocab[rng.choice(size, size=n // (lo + 1) + 1, p=p / p.sum())]
    lens = (words != 0).sum(axis=1)
    start = np.concatenate([[0], np.cumsum(lens + 1)[:-1]])
    text = np.full(int(start[-1] + lens[-1] + 1), ord(" "), np.uint8)
    for j in range(hi):
        has = lens > j
        text[start[has] + j] = words[has, j]
    return np.ascontiguousarray(text[:n])


def plant_repeat(rng, text: np.ndarray, length: int) -> tuple:
    """Copy ONE span of ``length`` bytes from a seeded position in the
    first half of the text over a seeded position in the second half, in
    place: the two are apart by more than ``length`` (they never
    overlap nor touch). Returns (source, destination)."""
    n = len(text)
    if n < 2 * length + 2:
        raise ValueError(f"a text of {n} bytes cannot hold a planted "
                         f"repeat of {length}: it takes 2 x {length} + 2")
    src = int(rng.integers(0, n // 2 - length + 1))
    dst = int(rng.integers(n // 2 + 1, n - length + 1))
    text[dst:dst + length] = text[src:src + length]
    return src, dst


def generate(seed: int, traffic: dict, config: dict) -> dict:
    """``chars`` bytes of Zipf-word text with, where the traffic file
    gives ``planted_repeat_bytes``, one span of that length repeated. A
    function of the seed alone; the plant's positions are drawn after
    the text, whose bytes elsewhere are what they are without it."""
    rng = np.random.default_rng(seed)
    text = zipf_text(rng, records(traffic), traffic)
    planted = int(traffic.get("planted_repeat_bytes", 0))
    if planted:
        plant_repeat(rng, text, planted)
    return {"text": text}


# ---------------------------------------------------------------- program

def _suffix_array():
    """The program's own example, loaded by its path."""
    if not _example:
        folder = os.path.join(_ROOT, "examples")
        if folder not in sys.path:
            sys.path.insert(0, folder)
        spec = importlib.util.spec_from_file_location(
            "chipbench_example_suffix_sorting",
            os.path.join(folder, "suffix_sorting.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _example.append(mod.suffix_array)
    return _example[0]


def pipeline(ctx, inp: dict) -> dict:
    """One job, from the host's text to the suffix array on the host,
    with the doubling rounds it took beside it. ``stats=`` is this
    PR's: a program without the pipeline in DIA operators refuses the
    keyword at once."""
    stats = {}
    sa = _suffix_array()(ctx, inp["text"], stats=stats)
    return {"sa": sa, "rounds": stats["rounds"]}


def fetch(handle: dict) -> dict:
    return handle


def dispose(handle) -> None:
    """The suffix array is on the host; the job disposed of what it
    kept."""


# -------------------------------------------------------------- reference

def _doubling(text: np.ndarray):
    """Plain prefix doubling from h = 1: ``np.lexsort`` on (rank2,
    rank1), names by the boundaries' running sum, a scatter of the new
    ranks. Returns the suffix array, by position the names one round
    earlier (the ranks the last round started from), and ``P``: the
    first power of two (from 2) at which the names of the prefixes of
    that length are all distinct."""
    n = len(text)
    rank = text.astype(np.int64) + 1        # 0 is "past the end"
    h = 1
    while True:
        rank2 = np.zeros(n, np.int64)
        if h < n:
            rank2[:n - h] = rank[h:]
        order = np.lexsort((rank2, rank))
        r1, r2 = rank[order], rank2[order]
        names = np.cumsum(np.concatenate(
            [[True], (r1[1:] != r1[:-1]) | (r2[1:] != r2[:-1])]))
        if names[-1] == n:
            return order.astype(np.uint32), rank, 2 * h
        rank[order] = names
        h *= 2


def rounds_from(power: int, traffic: dict = None) -> int:
    """Doubling rounds from names of ``initial_h`` characters (4 where
    the traffic file gives none) until prefixes of ``power`` characters
    are named: log2(P) - 2 from 4."""
    initial_h = int((traffic or {}).get("initial_h", 4))
    return max(0, (power // initial_h).bit_length() - 1)


def reference(inp: dict, traffic: dict) -> dict:
    """The suffix array, the text (for the checker), the rounds the
    text needs of a job that starts at ``initial_h``, and the rounds
    the traffic file states (None where it states none)."""
    sa, _, power = _doubling(inp["text"])
    return {"sa": sa, "text": inp["text"],
            "rounds_needed": rounds_from(power, traffic),
            "rounds": traffic.get("rounds")}


def control(inp: dict, traffic: dict) -> dict:
    """The reference with one guarantee broken, the way a later PR
    would be tempted to: stopped one round early. What that round had
    not told apart (suffixes of equal name) is left in index order."""
    _, before, power = _doubling(inp["text"])
    return {"sa": np.argsort(before, kind="stable").astype(np.uint32),
            "rounds": rounds_from(power // 2, traffic)}


def order_violations(text: np.ndarray, sa: np.ndarray) -> int:
    """The classic linear check of a permutation ``sa``, which depends
    on no construction: with ``isa`` its inverse, neighbours ``a``,
    ``b`` must satisfy ``text[a] < text[b]``, or equality and
    ``isa[a + 1] < isa[b + 1]`` (the end of the text is smallest)."""
    n = len(text)
    isa = np.empty(n + 1, np.int64)
    isa[n] = -1
    isa[sa] = np.arange(n)
    a, b = sa[:-1].astype(np.int64), sa[1:].astype(np.int64)
    ok = (text[a] < text[b]) | ((text[a] == text[b])
                                & (isa[a + 1] < isa[b + 1]))
    return int(np.sum(~ok))


def compare(got, want: dict) -> dict:
    """Each number is (reading, limit). The result being THE suffix
    array and the rounds exactly those the text needs, the four
    ``sa_*`` / ``rounds_differing`` limits are 0. A result of another
    length or dtype kind reads n everywhere; one that is no permutation
    cannot be checked for order and reads n there; one without its
    rounds reads n there. ``rounds_needed`` is the TEXT's reading, held
    to the traffic file's ``rounds`` where it states them: a seed whose
    text needed more shows here."""
    n = len(want["sa"])
    sa = got.get("sa") if isinstance(got, dict) else None
    sa = None if sa is None else np.asarray(sa)
    rounds = got.get("rounds") if isinstance(got, dict) else None
    sound = sa is not None and sa.shape == (n,) and sa.dtype.kind in "ui"
    if sound:
        seen = np.bincount(sa[sa < n].astype(np.int64), minlength=n)
        broken = int(np.sum(seen != 1))
        out = {"sa_not_permutation": (broken, 0),
               "sa_rows_differing": (int(np.sum(sa != want["sa"])), 0),
               "sa_order_violations": (
                   n if broken else order_violations(want["text"], sa), 0)}
    else:
        out = {"sa_not_permutation": (n, 0), "sa_rows_differing": (n, 0),
               "sa_order_violations": (n, 0)}
    out["rounds_differing"] = (
        abs(int(rounds) - want["rounds_needed"])
        if sound and rounds is not None else n, 0)
    if want.get("rounds") is not None:
        out["rounds_needed"] = (want["rounds_needed"], int(want["rounds"]))
    return out


# ------------------------------------------------------------------ bytes

def min_bytes(traffic: dict, config: dict, want) -> int:
    """What a job must move whatever implements it: the text read once,
    the suffix array of 4-byte indices written once."""
    n = records(traffic)
    return n + int(traffic["index_bytes"]) * n
