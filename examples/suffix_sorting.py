"""Suffix array construction by prefix doubling — the Sort-heaviest user.

Reference: Thrill's examples/suffix_sorting/prefix_doubling.cpp and
T. Bingmann, S. Gog, F. Kurpicz, "Scalable Construction of Text Indexes
with Thrill" (arXiv:1610.03007), its prefix doubling "using sorting and
windows" after Flick and Aluru (also DC3/DC7 in dc3.cpp/dc7.cpp). The
pipeline, in DIA operators only (``suffix_array``):

1. names: every position ``i`` with the first 4 characters of its
   suffix packed big-endian into one 32-bit integer (a padded
   ``Window(4)`` over the indexed text), ``Sort`` by it, a padded
   ``Window(2)`` that flags where a row's key differs from its
   successor's, an ``ExPrefixSum`` that turns the flags into names:
   ``(index, rank)``; ``h`` = 4.
2. a round: ``Sort`` by ``(index mod h, index)`` (brings ``rank[i]`` and
   ``rank[i + h]`` side by side), a padded ``Window(2)`` that emits
   ``(index, rank1, rank2)`` with ``rank2 = 0`` past the end, ``Sort`` by
   ``(rank1, rank2)``, flags, names; ONE scalar read back (``Max`` of
   the names: are they all distinct?) decides whether ``h`` doubles and
   another round runs.
3. the suffix array is the index column in the order of the last sort.

The text is distributed ONCE and every round's DIAs descend from it: a
job is one pipeline. ``h`` enters each round's programs as an operand
(``Bind``) and every stacked function is module-level, so all rounds
share two compiled programs and no job compiles after the first.
"""

from __future__ import annotations

import _bootstrap  # noqa: F401  (repo root on sys.path for CLI runs)

import functools

import jax
import numpy as np

from thrill_tpu.api import Bind, Context, Zip

KMER = 4        # characters packed into the first key: the index type's bytes


# ------------------------------------------------------- stacked functions
# Module-level, hence identity-stable: the program caches its compiled
# programs on the function objects, and a fresh lambda per round or per
# job would compile anew each time (20-66 s each on a TPU). Each works
# on batched columns (jax or numpy arrays with a leading item axis) and
# on single host items alike, so the tiny inputs that fall back to the
# host path run the same arithmetic.

def _u32(x):
    return x.astype(np.uint32) if hasattr(x, "astype") else np.uint32(x)


def _u64(x):
    return x.astype(np.uint64) if hasattr(x, "astype") else np.uint64(x)


def _char_row(c, g):
    """ZipWithIndex: a character with its position in the text."""
    return {"i": _u32(g), "c": c}


def _kmer(w):
    """Padded Window(4) over (index, character): the first 4 characters
    of the suffix packed big-endian, and how many of them the text
    holds. A pad row has index 0, a row behind another never, so the
    index column tells the end of the text from a character 0: the
    key ``(k-mer, length)`` orders "end of text" below every
    character."""
    i, c = w["i"], _u32(w["c"])
    kmer = (c[:, 0] << 24) | (c[:, 1] << 16) | (c[:, 2] << 8) | c[:, 3]
    length = 1 + _u32(i[:, 1] != 0) + _u32(i[:, 2] != 0) \
        + _u32(i[:, 3] != 0)
    return {"i": i[:, 0], "r1": kmer, "r2": length}


def _by_pair(t):
    """Sort key (rank1, rank2) as ONE 64-bit word: the device sort
    compares it as exactly its two 32-bit halves."""
    return (_u64(t["r1"]) << np.uint64(32)) | _u64(t["r2"])


def _group_end(w):
    """Padded Window(2) over rows sorted by (rank1, rank2): 1 where a
    row's pair differs from its successor's (the last row's always
    does: a pad row is (0, 0) and a real rank1 is never 0)."""
    r1, r2 = w["r1"], w["r2"]
    return _u32((r1[:, 0] != r1[:, 1]) | (r2[:, 0] != r2[:, 1]))


def _index_of(t):
    return t["i"]


def _index_rank(i, r):
    return {"i": i, "r": r}


def _with_class(t, h):
    """Map bound to ``h`` (a power of two): the residue class of the
    index. Within a class the order of ``index div h`` is the order of
    the index, so the round's first sort key is (index mod h, index)."""
    return {"i": t["i"], "r": t["r"], "m": t["i"] & (h - np.uint32(1))}


def _by_class(t):
    return (_u64(t["m"]) << np.uint64(32)) | _u64(t["i"])


def _pair(w, h):
    """Padded Window(2) bound to ``h`` over rows sorted by class:
    (index, rank1, rank2) with rank2 the successor row's rank where
    that row is position index + h, else 0 (past the end; a pad row
    has index 0, which no index + h is)."""
    i, r = w["i"], w["r"]
    return {"i": i[:, 0], "r1": r[:, 0],
            "r2": r[:, 1] * _u32(i[:, 1] == i[:, 0] + h)}


def _on_host(device_fn, index, window, *operands):
    """A window function's host form: the k items stacked into one
    batched window of one row, through the same arithmetic."""
    stacked = jax.tree.map(
        lambda *xs: np.stack([np.asarray(x) for x in xs])[None], *window)
    return jax.tree.map(lambda a: np.asarray(a)[0],
                        device_fn(stacked, *operands))


_kmer_host = functools.partial(_on_host, _kmer)
_group_end_host = functools.partial(_on_host, _group_end)


def _pair_host(step):
    return lambda index, window: _on_host(_pair, index, window, step)


def _named(pairs):
    """Rows (index, rank1, rank2) -> the index column and the names,
    both in (rank1, rank2) order: a name is 1 + the number of groups
    that end before the row. The sorted rows feed two consumers (Keep):
    the naming, which the round's read pulls, and the index column,
    which the next round's Zip or the final gather pulls."""
    rows = pairs.Sort(_by_pair).Keep()
    names = rows.Window(2, _group_end_host, device_fn=_group_end,
                        pad=True).ExPrefixSum(initial=1)
    return rows.Map(_index_of), names


def suffix_array(ctx: Context, text: np.ndarray,
                 stats: dict = None) -> np.ndarray:
    """text: [n] uint8. Returns the suffix array [n] uint32; ``stats``,
    where given, receives ``rounds`` (doubling rounds run) and ``h``."""
    n = len(text)
    if n == 0:
        return np.array([], dtype=np.uint32)
    chars = ctx.Distribute(np.ascontiguousarray(text, dtype=np.uint8))
    kmers = chars.ZipWithIndex(_char_row).Window(
        KMER, _kmer_host, device_fn=_kmer, pad=True)
    index, names = _named(kmers)
    h, rounds = KMER, 0
    # the ACTION that ends a round: all names distinct = the largest is n
    while int(names.Keep().Max()) < n:
        step = np.uint32(h)
        pairs = Zip(index, names, zip_fn=_index_rank) \
            .Map(Bind(_with_class, step)).Sort(_by_class) \
            .Window(2, _pair_host(step), device_fn=Bind(_pair, step),
                    pad=True)
        index, names = _named(pairs)
        h *= 2
        rounds += 1
    names.Dispose()
    if stats is not None:
        stats.update(rounds=rounds, h=h)
    return np.asarray(index.AllGatherArrays(), dtype=np.uint32)


def suffix_array_quadrupling(ctx: Context, text: np.ndarray) -> np.ndarray:
    """Prefix quadrupling: rank refinement advancing h by 4x per round
    with (rank[i], rank[i+h], rank[i+2h], rank[i+3h]) quadruple keys —
    half the distributed sorts of doubling at wider keys (reference:
    examples/suffix_sorting/prefix_quadrupling.cpp)."""
    n = len(text)
    if n == 0:
        return np.array([], dtype=np.int64)

    rank = text.astype(np.int64) + 1
    idx = np.arange(n, dtype=np.int64)
    h = 1
    while True:
        def shifted(k):
            out = np.zeros(n, dtype=np.int64)
            if k < n:
                out[:n - k] = rank[k:]
            return out

        r2, r3, r4 = shifted(h), shifted(2 * h), shifted(3 * h)
        d = ctx.Distribute({"i": idx, "a": rank, "b": r2, "c": r3,
                            "d": r4})
        got = d.Sort(
            key_fn=lambda t: (t["a"], t["b"], t["c"], t["d"])).AllGather()
        si = np.array([int(t["i"]) for t in got])
        cols = [np.array([int(t[k]) for t in got])
                for k in ("a", "b", "c", "d")]
        boundary = np.ones(n, dtype=np.int64)
        neq = np.zeros(n - 1, dtype=bool)
        for c in cols:
            neq |= c[1:] != c[:-1]
        boundary[1:] = neq.astype(np.int64)
        new_rank_sorted = np.cumsum(boundary)
        rank = np.zeros(n, dtype=np.int64)
        rank[si] = new_rank_sorted
        if new_rank_sorted[-1] == n:
            return si
        h *= 4
        if h >= 4 * n:
            return si


def dc3_suffix_array(ctx: Context, text: np.ndarray) -> np.ndarray:
    """DC3 (difference cover mod 3, a.k.a. skew) suffix array.

    Reference: /root/reference/examples/suffix_sorting/dc3.cpp — the
    heaviest recursive Sort stress test of the reference suite. The
    heavy phases ride the device: the (t_i, t_{i+1}, t_{i+2}) triple
    sort of the mod-1/mod-2 sample and the (t_i, rank_{i+1}) sort of
    the mod-0 class are DIA Sorts at every recursion level; lexicographic
    naming and the class-aware 3-way merge are linear host passes.
    """
    T = np.asarray(text, dtype=np.int64) + 1     # 0 reserved as sentinel
    return _dc3(ctx, T)


def _dc3(ctx: Context, T: np.ndarray) -> np.ndarray:
    n = len(T)
    if n <= 3:
        return np.array(sorted(range(n),
                               key=lambda i: tuple(T[i:]) + (0,)),
                        dtype=np.int64)

    # canonical Kärkkäinen–Sanders counts: when n % 3 == 1 the sample
    # gains the dummy position n (triple (0,0,0)), so the mod-1 section
    # of the recursion string ends with a unique smallest terminator
    n0 = (n + 2) // 3
    n1 = (n + 1) // 3
    ext = n0 - n1                    # 1 iff n % 3 == 1
    m = n + ext
    Tp = np.concatenate([T, np.zeros(3 + ext, dtype=np.int64)])
    s12 = np.array([i for i in range(m) if i % 3 != 0], dtype=np.int64)

    # device sort of the sample triples (the hot phase)
    d = ctx.Distribute({"i": s12, "a": Tp[s12], "b": Tp[s12 + 1],
                        "c": Tp[s12 + 2]})
    got = d.Sort(key_fn=lambda t: (t["a"], t["b"], t["c"])).AllGather()
    order = np.array([int(t["i"]) for t in got], dtype=np.int64)
    trip = np.array([[int(t["a"]), int(t["b"]), int(t["c"])]
                     for t in got], dtype=np.int64)

    # lexicographic names: 1 + count of strict triple boundaries
    boundary = np.ones(len(order), dtype=np.int64)
    if len(order) > 1:
        boundary[1:] = np.any(trip[1:] != trip[:-1], axis=1)
    names_sorted = np.cumsum(boundary)
    num_names = int(names_sorted[-1])
    name_of = np.zeros(m + 3, dtype=np.int64)
    name_of[order] = names_sorted

    if num_names < len(s12):
        # names collide: recurse on the sample string (mod-1 positions
        # then mod-2 positions, the canonical DC3 arrangement)
        ones = np.array([i for i in range(m) if i % 3 == 1])
        twos = np.array([i for i in range(m) if i % 3 == 2])
        R = np.concatenate([name_of[ones], name_of[twos]])
        SA_R = _dc3(ctx, R)
        k1 = len(ones)
        SA12 = np.where(SA_R < k1, 1 + 3 * SA_R, 2 + 3 * (SA_R - k1))
    else:
        SA12 = order

    # rank of each sample suffix in SA12 (1-based; 0 = beyond end)
    rank12 = np.zeros(m + 3, dtype=np.int64)
    rank12[SA12] = np.arange(1, len(SA12) + 1)
    # the dummy (position n, empty suffix) leaves the output
    SA12 = SA12[SA12 < n]

    # device sort of the mod-0 class by (t_i, rank_{i+1})
    s0 = np.array([i for i in range(n) if i % 3 == 0], dtype=np.int64)
    d0 = ctx.Distribute({"i": s0, "a": Tp[s0], "r": rank12[s0 + 1]})
    got0 = d0.Sort(key_fn=lambda t: (t["a"], t["r"])).AllGather()
    SA0 = np.array([int(t["i"]) for t in got0], dtype=np.int64)

    # class-aware linear merge (reference: dc3.cpp merge comparators)
    def leq12(i, j):
        """suffix i (mod 1 or 2) <= suffix j (mod 0)?"""
        if i % 3 == 1:
            return (Tp[i], rank12[i + 1]) <= (Tp[j], rank12[j + 1])
        return (Tp[i], Tp[i + 1], rank12[i + 2]) <= \
            (Tp[j], Tp[j + 1], rank12[j + 2])

    out = np.empty(n, dtype=np.int64)
    a = b = k = 0
    while a < len(SA12) and b < len(SA0):
        if leq12(int(SA12[a]), int(SA0[b])):
            out[k] = SA12[a]
            a += 1
        else:
            out[k] = SA0[b]
            b += 1
        k += 1
    while a < len(SA12):
        out[k] = SA12[a]
        a += 1
        k += 1
    while b < len(SA0):
        out[k] = SA0[b]
        b += 1
        k += 1
    return out


def suffix_array_dense(text: np.ndarray) -> np.ndarray:
    s = bytes(text)
    return np.array(sorted(range(len(s)), key=lambda i: s[i:]),
                    dtype=np.int64)


# DC7 difference cover: {0, 1, 3} mod 7 (differences cover Z_7), so 3/7
# of positions are sampled and any two residues share an aligning shift
DC7_D = (0, 1, 3)
# SHIFT[a][b] = min t >= 0 with (a+t) % 7 in D and (b+t) % 7 in D
DC7_SHIFT = [[min(t for t in range(7)
                  if (a + t) % 7 in DC7_D and (b + t) % 7 in DC7_D)
              for b in range(7)] for a in range(7)]


def dc7_suffix_array(ctx: Context, text: np.ndarray) -> np.ndarray:
    """DC7 (difference cover mod 7) suffix array.

    Reference: /root/reference/examples/suffix_sorting/dc7.cpp — like
    DC3 but samples 3/7 of positions with the perfect difference cover
    {0,1,3} mod 7, so each recursion level shrinks by 3/7 instead of
    2/3 and sorts wider (7-char) tuples: fewer, fatter device Sorts,
    the shape the MXU-era sort engine prefers. The sample 7-tuple sort
    and the batched non-sample class sort ride the device DIA Sort;
    naming and the comparator merge are linear host passes.
    """
    return _dc7(ctx, np.asarray(text, dtype=np.int64))


def _dc7(ctx: Context, S: np.ndarray) -> np.ndarray:
    """Suffix array of an arbitrary non-negative int string S."""
    n = len(S)
    if n <= 16:
        return np.array(sorted(range(n),
                               key=lambda i: tuple(S[i:]) + (-1,)),
                        dtype=np.int64)

    # internal shift so 0 is reserved for padding/terminators: zeros
    # then appear only in the tail, making every zero-containing
    # 7-tuple position-unique (shorter-suffix-sorts-first semantics)
    T = S + 1
    Tp = np.concatenate([T, np.zeros(14, dtype=np.int64)])

    res = np.arange(n) % 7
    s_cls = [np.flatnonzero(res == c).astype(np.int64) for c in range(7)]
    s_all = np.concatenate([s_cls[c] for c in DC7_D])

    # ---- device sort of the sample 7-tuples (naming phase) ----------
    cols = {f"c{k}": Tp[s_all + k] for k in range(7)}
    d = ctx.Distribute({"i": s_all, **cols})
    got = d.Sort(key_fn=lambda t: tuple(t[f"c{k}"] for k in range(7))) \
        .AllGather()
    order = np.array([int(t["i"]) for t in got], dtype=np.int64)
    tup = np.array([[int(t[f"c{k}"]) for k in range(7)] for t in got],
                   dtype=np.int64)

    boundary = np.ones(len(order), dtype=np.int64)
    if len(order) > 1:
        boundary[1:] = np.any(tup[1:] != tup[:-1], axis=1)
    names_sorted = np.cumsum(boundary)
    num_names = int(names_sorted[-1])
    name_of = np.zeros(n + 14, dtype=np.int64)
    name_of[order] = names_sorted

    if num_names < len(s_all):
        # recursion string: class sections joined by 0 terminators (a
        # unique-smallest section end keeps cross-section comparisons
        # from ever being decided by wrapped-around names; the
        # recursion re-shifts internally, so 0 stays reserved)
        sections = [name_of[s_cls[c]] for c in DC7_D]
        R = np.concatenate([sections[0], [0], sections[1], [0],
                            sections[2]])
        pos_map = np.concatenate([s_cls[DC7_D[0]], [-1],
                                  s_cls[DC7_D[1]], [-1],
                                  s_cls[DC7_D[2]]])
        SA_R = _dc7(ctx, R)
        SA12 = pos_map[SA_R]
        SA12 = SA12[SA12 >= 0]
    else:
        SA12 = order

    rank7 = np.zeros(n + 14, dtype=np.int64)
    rank7[SA12] = np.arange(1, len(SA12) + 1)

    # ---- one batched device sort of the non-sample classes ----------
    # class c orders by (T[i..i+tc-1], rank7[i+tc]); keys are laid out
    # (class, ch0.., rank, 0-pad) so one Sort covers all four classes
    ns_cls = [c for c in range(7) if c not in DC7_D]
    ns_pos = np.concatenate([s_cls[c] for c in ns_cls])
    if len(ns_pos):
        tcs = np.array([DC7_SHIFT[c][c] for c in range(7)], dtype=np.int64)
        tmax = int(tcs[ns_cls].max())              # = 3 for {0,1,3}
        keys = np.zeros((len(ns_pos), tmax + 2), dtype=np.int64)
        keys[:, 0] = ns_pos % 7
        for c in ns_cls:                           # 4 vectorized fills
            mask = ns_pos % 7 == c
            pos = ns_pos[mask]
            tc = int(tcs[c])
            keys[np.flatnonzero(mask)[:, None], 1 + np.arange(tc)] = \
                Tp[pos[:, None] + np.arange(tc)]
            keys[mask, 1 + tc] = rank7[pos + tc]
        dn = ctx.Distribute({"i": ns_pos,
                             **{f"k{j}": keys[:, j]
                                for j in range(tmax + 2)}})
        gotn = dn.Sort(key_fn=lambda t: tuple(t[f"k{j}"]
                                              for j in range(tmax + 2))) \
            .AllGather()
        by_cls = {c: [] for c in ns_cls}
        for t in gotn:
            by_cls[int(t["k0"])].append(int(t["i"]))
        seqs = [SA12.tolist()] + [by_cls[c] for c in ns_cls]
    else:
        seqs = [SA12.tolist()]

    # ---- comparator merge of the 5 sorted sequences -----------------
    import heapq
    from functools import cmp_to_key

    def cmp(i: int, j: int) -> int:
        t = DC7_SHIFT[i % 7][j % 7]
        for k in range(t):
            if Tp[i + k] != Tp[j + k]:
                return -1 if Tp[i + k] < Tp[j + k] else 1
        ri, rj = rank7[i + t], rank7[j + t]
        return -1 if ri < rj else (1 if ri > rj else 0)

    out = np.fromiter(
        heapq.merge(*seqs, key=cmp_to_key(cmp)), dtype=np.int64, count=n)
    return out


def wavelet_tree(ctx: Context, text: np.ndarray, bits: int = 8):
    """Wavelet matrix (level-ordered wavelet tree) of a byte sequence.

    Reference: /root/reference/examples/suffix_sorting wavelet_tree —
    construction is one stable bit-partition per level, which maps to
    one device SortStable by the current bit (the reference builds the
    node-ordered tree with its sample sort; the level-ordered matrix
    variant is the natural fit for whole-array device partitions and
    supports the same rank/select/access queries). Returns one packed
    bitvector per level, MSB first, each in that level's element order.
    """
    levels = []
    cur = np.asarray(text, dtype=np.uint8)
    for b in reversed(range(bits)):
        bit = (cur >> b) & 1
        levels.append(np.packbits(bit))
        if b == 0:
            break
        # stable partition by the current bit = stable sort on it, run
        # on the device path through the DIA Sort
        d = ctx.Distribute({"v": cur.astype(np.int64),
                            "b": bit.astype(np.int64)})
        got = d.SortStable(key_fn=lambda t: t["b"]).AllGather()
        cur = np.array([int(t["v"]) for t in got], dtype=np.uint8)
    return levels


def wavelet_access(levels, n: int, i: int, bits: int = 8) -> int:
    """Reconstruct the symbol at original position i from the matrix
    (rank-based descent; validates the construction)."""
    sym = 0
    pos = i
    for lvl in range(bits):
        bv = np.unpackbits(levels[lvl])[:n]
        b = int(bv[pos])
        sym = (sym << 1) | b
        if lvl == bits - 1:
            break
        if b == 0:
            pos = int(np.sum(bv[:pos] == 0))
        else:
            pos = int(np.sum(bv == 0)) + int(np.sum(bv[:pos] == 1))
    return sym


def bwt(ctx: Context, text: np.ndarray) -> np.ndarray:
    """Burrows-Wheeler transform via the suffix array
    (reference: examples/suffix_sorting/wavelet_tree / bwt usage)."""
    sa = suffix_array(ctx, text).astype(np.int64)
    return text[(sa - 1) % len(text)]


def rl_bwt(ctx: Context, text: np.ndarray):
    """Run-length-compressed BWT: (run chars, run lengths).

    Reference: examples/suffix_sorting/rl_bwt.cpp — BWT through the
    suffix array, then run-length encoding of the output (the
    reference encodes via a FlatWindow scan; the host pass here is the
    same boundary-flag + segment-length computation).
    """
    b = bwt(ctx, text)
    if len(b) == 0:
        return np.array([], dtype=text.dtype), np.array([], np.int64)
    starts = np.concatenate([[0], np.flatnonzero(b[1:] != b[:-1]) + 1])
    lengths = np.diff(np.concatenate([starts, [len(b)]]))
    return b[starts], lengths.astype(np.int64)


def check_sa(text: np.ndarray, sa: np.ndarray) -> bool:
    """Linear-time suffix array verification.

    Reference: examples/suffix_sorting/check_sa.hpp — permutation check
    plus the rank trick: sa is correct iff for consecutive entries
    (text[sa[r-1]], rank[sa[r-1]+1]) <= (text[sa[r]], rank[sa[r]+1])
    with the empty suffix ranked smallest.
    """
    n = len(text)
    sa = np.asarray(sa)
    if len(sa) != n:
        return False
    if n == 0:
        return True
    if not np.array_equal(np.sort(sa), np.arange(n)):
        return False
    rank = np.zeros(n + 1, dtype=np.int64)
    rank[sa] = np.arange(1, n + 1)                 # rank[n] = 0 (empty)
    a, b = sa[:-1], sa[1:]
    ca, cb = text[a], text[b]
    ra, rb = rank[a + 1], rank[b + 1]
    return bool(np.all((ca < cb) | ((ca == cb) & (ra < rb))))


def lcp_from_sa(text: np.ndarray, sa: np.ndarray) -> np.ndarray:
    """LCP array (lcp[r] = lcp(suffix sa[r-1], suffix sa[r]), lcp[0]=0)
    by Kasai's algorithm.

    Reference: examples/suffix_sorting/construct_lcp.hpp — the
    reference derives LCP during construction; the Kasai pass here
    yields the identical array from any valid SA in O(n) host time.
    """
    n = len(text)
    lcp = np.zeros(n, dtype=np.int64)
    if n == 0:
        return lcp
    rank = np.zeros(n, dtype=np.int64)
    rank[sa] = np.arange(n)
    h = 0
    for i in range(n):
        r = rank[i]
        if r > 0:
            j = int(sa[r - 1])
            while i + h < n and j + h < n and text[i + h] == text[j + h]:
                h += 1
            lcp[r] = h
            if h > 0:
                h -= 1
        else:
            h = 0
    return lcp


def main():
    import argparse
    parser = argparse.ArgumentParser()
    parser.add_argument("--size", type=int, default=10000)
    args = parser.parse_args()

    from thrill_tpu.api import Run

    def job(ctx):
        rng = np.random.default_rng(0)
        text = rng.integers(97, 101, args.size).astype(np.uint8)
        sa = suffix_array(ctx, text)
        print("suffix array head:", sa[:10])

    Run(job)


if __name__ == "__main__":
    main()
