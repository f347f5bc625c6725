"""Device sort engine: XLA sort, chunked sort+merge, or bitonic network.

The DIA operators sort through two entry points over the same engines:
``argsort_words`` (stable argsort by a list of uint64 key words) for a
caller that needs only the permutation, and ``sort_words`` (the sorted
words beside it) for one that reads its keys back, so that no caller
gathers a word it has just sorted. Three interchangeable
implementations:

* ``xla``     — ``lax.sort`` multi-operand (fastest where the XLA sort
                lowering is healthy; always used on CPU).
* ``chunked`` — batched ``lax.sort`` over ``CHUNK_ROWS`` tiles (each
                tile stays below the TPU sort-lowering compile cliff),
                then the bitonic merge stages over the sorted tiles.
                Every stage is a compare-exchange with a partner
                fetched by contiguous rolls — NO random gathers — and
                all O(log C · log n) stages share one rolled loop, so
                compile time does not grow with n.
* ``bitonic`` — the full bitonic network through the same loop
                (O(log² n) stages; no base-case sort to compile).

Selection, in :func:`choose_engine` and nowhere else:
THRILL_TPU_SORT_IMPL = auto (default) | xla | chunked | bitonic |
radix (core/pallas_sort.py). ``auto`` uses xla on CPU backends and for
small n, chunked (or, with the Pallas tier on, the cheaper of chunked
and radix) on accelerators above the threshold: the TPU compiler's time
for a multi-operand ``lax.sort`` grows steeply with rows (round 1:
stalls beyond ~64K rows; JAX 0.9.0 for v5e: minutes from 2^16 rows up,
see ``CHUNK_ROWS`` and CHANGES.md PR 22).
"""

from __future__ import annotations

import math
import os
from typing import List, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from . import rowmove

# HLO metadata only (jax.named_scope adds, moves and fuses nothing): a
# device profile tells the sort engine's operations from the row
# movement's and the exchange's by this name in their op_name
SCOPE = "sort_engine"

# above this row count, accelerator backends switch engines in auto
XLA_SORT_MAX_N = 1 << 16
# rows per tile of the chunked engine's base-case sort. The TPU
# compiler's time for a 7-operand lax.sort grows steeply with rows
# (3 s at 2^12, 14 s at 2^13, 50 s at 2^14, 218 s at 2^16: AOT compiles
# for v5e, JAX 0.9.0, CHANGES.md PR 22), and the merge stages above the
# tiles are one rolled loop whose compile time does not depend on them
CHUNK_ROWS = 1 << 12

ENGINES = ("xla", "bitonic", "chunked", "radix")


def choose_engine(n: int, words, *, radix_ok=None,
                  record: bool = True) -> str:
    """THE device sort engine choice: every site that sorts or merges
    ``n`` rows by ``words`` asks here, nowhere else.

    ``THRILL_TPU_SORT_IMPL`` pins one of :data:`ENGINES`. Otherwise:

    * xla     — one ``lax.sort``, where its lowering is healthy: on the
                CPU, or at ``n <= XLA_SORT_MAX_N`` (the TPU compile
                cliff);
    * chunked — batched tile sorts + bitonic merge stages:
                n·(log²(tile)/2 + log C·log n) compare-exchanges;
    * radix   — LSD 8-bit passes over the key words (pallas_sort):
                ~3n per pass (histogram + offsets + scatter),
                ``total_bits/8`` passes; a candidate only where the
                Pallas stable-partition kernel engages (``radix_ok``;
                None asks the dispatching mesh).

    Past the cliff the cheaper of chunked and radix wins, both priced
    in modelled element operations. An unpinned choice is written to
    the dispatching mesh's decision ledger as a ``sort_engine`` record
    (``record=False``: a caller that sorts nothing itself, the merge of
    presorted runs)."""
    mode = os.environ.get("THRILL_TPU_SORT_IMPL", "auto")
    if mode in ENGINES:
        return mode
    from ..parallel import mesh as _mesh
    mex = _mesh.current_mex()
    total_bits = sum(32 if w.dtype == jnp.uint32 else 64 for w in words)
    lg = max(1.0, math.log2(max(n, 2)))
    if jax.default_backend() == "cpu" or n <= XLA_SORT_MAX_N:
        costs = {"xla": n * lg}
        reason = "xla sort lowering healthy at this size"
    else:
        lgc = math.log2(CHUNK_ROWS)
        c_tiles = max(1.0, n / CHUNK_ROWS)
        costs = {"chunked": n * (lgc * lgc / 2.0
                                 + math.log2(c_tiles) * lg)}
        if radix_ok is None:
            from .pallas_kernels import MAX_ROWS, pallas_enabled
            radix_ok = pallas_enabled(mex) and n < MAX_ROWS
        if radix_ok:
            costs["radix"] = 3.0 * n * max(1, (total_bits + 7) // 8)
            reason = "past the xla compile cliff; radix eligible"
        else:
            reason = ("past the xla compile cliff; radix ineligible "
                      "(Pallas off or too many rows)")
    engine = min(costs, key=costs.get)
    led = getattr(mex, "decisions", None) if record else None
    if led is not None and led.enabled:
        led.record(
            "sort_engine", site=f"sort:n{n}:w{len(words)}",
            chosen=engine, predicted=costs[engine],
            rejected=[(e, c) for e, c in sorted(costs.items())
                      if e != engine],
            reason=reason, n=n, total_bits=total_bits, unit="ops")
    return engine


def _use_u32() -> bool:
    """Split uint64 key words into native uint32 (hi, lo) pairs?

    TPU VPU lanes are 32-bit; XLA emulates every 64-bit integer compare
    and select as u32 pairs with carry fixups. Splitting explicitly
    yields the same lexicographic order ((hi, lo) big-endian) out of
    native ops and lets the carried iota be a single u32 word. Default
    on for accelerator backends, off on CPU (native 64-bit ALU); env
    THRILL_TPU_SORT_U32 = 0|1 overrides.
    """
    mode = os.environ.get("THRILL_TPU_SORT_U32")
    if mode is not None:
        return mode not in ("0", "false", "")
    return jax.default_backend() != "cpu"


def _split_words_u32(words: List[jnp.ndarray]) -> List[jnp.ndarray]:
    """uint64 word list -> equivalent uint32 (hi, lo) word list.

    Words already narrower than 33 bits keep one (lo) word."""
    out: List[jnp.ndarray] = []
    for w in words:
        if w.dtype != jnp.uint64:
            out.append(w.astype(jnp.uint32))
            continue
        out.append((w >> jnp.uint64(32)).astype(jnp.uint32))
        out.append(w.astype(jnp.uint32))
    return out


def prepare_sort_words(words: List[jnp.ndarray], n: int):
    """Shared key prep for every sort entry point: apply the u32 word
    split when enabled and pick the index/iota dtype for ``n`` rows.
    Returns (words, index_dtype). Callers that build their own sort
    or merge network (Sort's fused run-merge) MUST go through this so
    their key layout never diverges from ``argsort_words``."""
    if _use_u32():
        words = _split_words_u32(words)
        idt = jnp.uint32 if n <= (1 << 31) else jnp.uint64
    else:
        idt = jnp.uint64
    return words, idt


def _radix_argsort(words: List[jnp.ndarray]) -> jnp.ndarray:
    """LSD radix over 8-bit digits (O(n * passes), no comparison
    network, no XLA-sort compile cliff): Pallas stable-partition kernel
    on TPU, lax.scan fallback elsewhere. u32 split is irrelevant —
    digits are extracted by shifts either way."""
    from .pallas_sort import radix_argsort_device
    bits = [32 if w.dtype == jnp.uint32 else 64 for w in words]
    return radix_argsort_device(
        [w.astype(jnp.uint64) for w in words],
        word_bits=bits).astype(jnp.int32)


@jax.named_scope(SCOPE)
def argsort_words(words: List[jnp.ndarray]) -> jnp.ndarray:
    """Stable argsort by uint64 key words (lexicographic). [n] int32."""
    n = words[0].shape[0]
    impl = choose_engine(n, words)
    if impl == "radix":
        return _radix_argsort(words)
    words, idt = prepare_sort_words(words, n)
    if impl == "xla":
        iota = jnp.arange(n, dtype=idt)
        res = lax.sort(tuple(words) + (iota,), dimension=0,
                       num_keys=len(words), is_stable=True)
        return res[-1].astype(jnp.int32)
    if impl == "chunked":
        return _chunked_argsort(words, index_dtype=idt)
    return _bitonic_argsort(words, index_dtype=idt)


@jax.named_scope(SCOPE)
def sort_words(words: List[jnp.ndarray]
               ) -> Tuple[List[jnp.ndarray], jnp.ndarray]:
    """Stable sort by key words (lexicographic, as ``argsort_words``):
    ``(sorted words, perm)``, the words in the dtypes passed and
    ``perm`` the [n] int32 permutation ``argsort_words`` returns.

    The engines end with every operand in sorted order, so the words
    are taken from there, not gathered by ``perm``; a uint64 word split
    into u32 halves comes back as ``(hi << 32) | lo``, elementwise, which
    fuses into its consumer. ``radix`` yields only a permutation and
    gathers the words here, so a caller has one path whatever engine
    :func:`choose_engine` picks. The words taken from a sort are counted
    as ``overall_stats()["sort_keys_reused"]``, one its caller leaves
    unread as well (``sort_keys``' validity word on shards not full)."""
    n = words[0].shape[0]
    impl = choose_engine(n, words)
    if impl == "radix":
        perm = _radix_argsort(words)
        with jax.named_scope(rowmove.SCOPE):
            return [jnp.take(w, perm) for w in words], perm
    split, idt = prepare_sort_words(words, n)
    if impl == "xla":
        arrs = lax.sort(tuple(split) + (jnp.arange(n, dtype=idt),),
                        dimension=0, num_keys=len(split), is_stable=True)
    elif n == 1:                 # nothing to sort, nothing to pad to
        arrs = split + [jnp.zeros(1, idt)]
    elif impl == "chunked":
        arrs = [a.reshape(-1)[:n]
                for a in _chunked_sorted(split, CHUNK_ROWS, idt)]
    else:
        arrs = [a[:n] for a in _bitonic_sorted(split, idt)]
    from ..parallel.mesh import note
    note("sort_keys_reused", len(words))
    return _unsplit_words(words, arrs[:-1]), arrs[-1].astype(jnp.int32)


def _unsplit_words(words: List[jnp.ndarray],
                   sorted_split: List[jnp.ndarray]) -> List[jnp.ndarray]:
    """The caller's ``words`` from their sorted :func:`prepare_sort_words`
    form: a uint64 word that came back as u32 (hi, lo) halves is
    ``(hi << 32) | lo``; any other word is its own sorted operand."""
    it = iter(sorted_split)
    out = []
    for w in words:
        s = next(it)
        if w.dtype == jnp.uint64 and s.dtype == jnp.uint32:
            s = ((s.astype(jnp.uint64) << jnp.uint64(32))
                 | next(it).astype(jnp.uint64))
        out.append(s.astype(w.dtype))
    return out


def _lex_gt(a_words, b_words):
    """Elementwise lexicographic a > b over parallel word lists."""
    gt = jnp.zeros(a_words[0].shape, bool)
    eq = jnp.ones(a_words[0].shape, bool)
    for a, b in zip(a_words, b_words):
        gt = gt | (eq & (a > b))
        eq = eq & (a == b)
    return gt


def _bitonic_stages(arrs, s0: int, k: int):
    """Stages ``s0 .. k-1`` of the bitonic sorting network over flat
    ``[2^k]`` arrays (lexicographic by the whole array tuple), as ONE
    ``lax.fori_loop`` over a static (stage, distance) table.

    Precondition: every aligned block of ``2^s0`` rows is sorted, even
    blocks ascending and odd blocks descending (``s0 = 0`` asks
    nothing). Afterwards the whole array is sorted ascending.

    Position ``i`` meets its partner ``i ^ d``; the partner's value is
    fetched as a select between the array rolled up and rolled down by
    ``d`` — contiguous copies at a runtime offset, no gather. One loop
    body serves every stage, so compile time does not grow with n;
    one static reshape per distance costs ~5 s of TPU compile time per
    stage, 15 min at 2^22 rows (v5e, JAX 0.9.0; CHANGES.md PR 22).
    """
    n = arrs[0].shape[0]
    stages = [(s, ss) for s in range(s0, k) for ss in range(s, -1, -1)]
    if not stages:
        return list(arrs)
    stage_of = jnp.array([s for s, _ in stages], jnp.int32)
    dist_of = jnp.array([1 << ss for _, ss in stages], jnp.int32)
    i = jnp.arange(n, dtype=jnp.int32)

    def body(t, arrs):
        d = dist_of[t]
        lower = (i & d) == 0                  # i < i ^ d
        partner = tuple(jnp.where(lower, jnp.roll(a, -d), jnp.roll(a, d))
                        for a in arrs)
        up = ((i >> (stage_of[t] + 1)) & 1) == 0
        gt = _lex_gt(arrs, partner)
        # a full tie swaps two equal tuples: harmless
        take_partner = jnp.where(up == lower, gt, ~gt)
        return tuple(jnp.where(take_partner, b, a)
                     for a, b in zip(arrs, partner))

    return list(lax.fori_loop(0, len(stages), body, tuple(arrs)))


def _flip_odd_rows(a):
    """[C, L] rows sorted ascending -> odd rows descending: the
    alternating directions :func:`_bitonic_stages` starts from."""
    odd = (jnp.arange(a.shape[0]) & 1)[:, None] == 1
    return jnp.where(odd, a[:, ::-1], a)


def _chunked_argsort(words: List[jnp.ndarray],
                     chunk: int = CHUNK_ROWS,
                     index_dtype=jnp.uint64) -> jnp.ndarray:
    """Sorted tiles + bitonic merge stages; [n] int32 permutation.

    Stability comes from carrying the original index as the final key
    word (total order), not from the network itself. Pads (max words,
    index >= n) are the largest tuples, so perm[:n] is exactly the
    sorted real items.
    """
    n_real = words[0].shape[0]
    if n_real == 1:
        return jnp.zeros(1, jnp.int32)
    arrs = _chunked_sorted(words, chunk, index_dtype)
    return arrs[-1].reshape(-1)[:n_real].astype(jnp.int32)


def _chunked_sorted(words: List[jnp.ndarray], chunk: int,
                    index_dtype) -> List[jnp.ndarray]:
    """The chunked engine's operands, the words padded to a power of
    two and the index behind them, after the sort: ``[C, L]`` arrays
    whose rows read in order are the sorted tuples, pads last."""
    n_real = words[0].shape[0]
    n = 1 << (n_real - 1).bit_length()
    c = min(chunk, n)
    pad = n - n_real
    iota = jnp.arange(n, dtype=index_dtype)
    arrs = [jnp.concatenate([w, jnp.full(pad, jnp.iinfo(w.dtype).max,
                                         w.dtype)])
            if pad else w for w in words] + [iota]

    arrs = [a.reshape(n // c, c) for a in arrs]
    # base case: batched sort of every tile (compiles like one tile)
    arrs = list(lax.sort(tuple(arrs), dimension=1, num_keys=len(arrs),
                         is_stable=False))
    return merge_sorted_runs(arrs)


@jax.named_scope(SCOPE)
def merge_sorted_runs(arrs: List[jnp.ndarray]) -> List[jnp.ndarray]:
    """Bitonic merge of C sorted runs; [C, L] arrays -> [1, C*L].

    Each input row must be sorted ascending by the word tuple (ties
    allowed); C and L must be powers of two. log C merge levels of
    compare-exchange stages, all in one rolled loop — no gathers. This
    is the back half of the chunked sort, exposed for callers whose
    runs are already sorted (Sort phase 3 merges the W received
    rank-ordered runs this way instead of re-sorting from scratch)."""
    C, L = arrs[0].shape
    if C == 1:
        return list(arrs)
    flat = [_flip_odd_rows(a).reshape(-1) for a in arrs]
    merged = _bitonic_stages(flat, L.bit_length() - 1,
                             (C * L).bit_length() - 1)
    return [a.reshape(1, C * L) for a in merged]


def _bitonic_argsort(words: List[jnp.ndarray],
                     index_dtype=jnp.uint64) -> jnp.ndarray:
    n_real = words[0].shape[0]
    if n_real == 1:
        return jnp.zeros(1, jnp.int32)
    arrs = _bitonic_sorted(words, index_dtype)
    return arrs[-1].astype(jnp.int32)[:n_real]


def _bitonic_sorted(words: List[jnp.ndarray],
                    index_dtype) -> List[jnp.ndarray]:
    """The bitonic engine's operands after the sort, flat, pads last."""
    n_real = words[0].shape[0]
    # pad to a power of two with max-words; pads carry the largest iota
    # so they sort strictly last and perm[:n_real] is exactly the sorted
    # real items (handles non-pow2 caps, e.g. after local concat)
    n = 1 << (n_real - 1).bit_length()
    pad = n - n_real
    k = n.bit_length() - 1
    # original index as the final key word: total order -> stability
    iota = jnp.arange(n, dtype=index_dtype)
    arrs = tuple(jnp.concatenate([w, jnp.full(pad, jnp.iinfo(w.dtype).max,
                                              w.dtype)])
                 if pad else w for w in words) + (iota,)

    return _bitonic_stages(arrs, 0, k)
