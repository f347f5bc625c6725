"""Device radix sort: Pallas stable-partition kernel + LSD driver.

The chunked/bitonic engines (core/device_sort.py) exist because XLA's
sort lowering hit a compile cliff above ~64K rows in round 1. Both
are comparison networks — O(n log^2 n) compare-exchanges. A radix sort is O(n * passes): each pass is a
STABLE PARTITION by an 8-bit digit, and stable partition is exactly
the primitive a sequential-grid Pallas kernel expresses naturally:

  offsets[i] = base[d_i] + #{j < i : d_j == d_i}

* ``base``    — exclusive scan of the global digit histogram
  (partition_histogram, already MXU-counted).
* the running per-digit counters live in VMEM scratch across the
  sequential row-tile grid (TPU grids execute in order), and the
  within-tile exclusive prefix-by-digit is a strict-lower-triangular
  matmul of the one-hot matrix — the MXU does the counting, there is
  no per-item loop anywhere.

``stable_partition_offsets`` dispatches to the Pallas kernel on TPU
(THRILL_TPU_PALLAS=1) with a lax.scan fallback of identical semantics
on every platform; CPU tests run the kernel in interpret mode to pin
equivalence. ``radix_argsort_device`` drives LSD passes over uint
words (most-significant word last), honoring per-word used-bit hints
so zero-padded byte keys skip dead passes at TRACE time (the host
engine skips them at runtime; static shapes demand a static pass
list here).

Precision note: tile partials ride the MXU in f32, exact up to 2^24 —
the Pallas path therefore applies to n < 16M rows per shard (well
above any per-shard capacity this framework produces; the fallback has
no such limit).
"""

from __future__ import annotations

import functools
import os
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp

from .pallas_kernels import (BLOCK, COLS, LANES, SUBLANES, _first_block,
                             _round_up, _row_block, pallas_enabled,
                             partition_histogram)

_F32_EXACT = 1 << 24


def _part_kernel(base_ref, dest_ref, out_ref, run_ref, *,
                 num_bins_padded: int):
    # Layout contract (see pallas_kernels module docstring): elements on
    # the LANE axis in (SUBLANES, COLS) tiles, bins on the SUBLANE axis
    # as (B, 1) columns — no transposes anywhere. The tile's sublane
    # rows are processed in order (row-major element order) so the
    # running per-digit counters stay sequentially consistent.
    from jax.experimental import pallas as pl

    pi = pl.program_id(0)

    @pl.when(pi == 0)
    def _init():
        run_ref[:] = base_ref[:].astype(jnp.float32)

    bins = jax.lax.broadcasted_iota(
        jnp.int32, (num_bins_padded, COLS), 0)         # [B, COLS]
    # upper-triangular matmul = exclusive within-row prefix along lanes:
    # prefix[b, j] = #{k < j : d_k == b}
    rows = jax.lax.broadcasted_iota(jnp.int32, (COLS, COLS), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (COLS, COLS), 1)
    tri_u = (rows < cols).astype(jnp.float32)
    for r in range(SUBLANES):                          # static unroll
        d_r = dest_ref[r:r + 1, :]                     # [1, COLS]
        onehot = (bins == d_r).astype(jnp.float32)     # [B, COLS]
        prefix = jnp.dot(onehot, tri_u,
                         preferred_element_type=jnp.float32)
        within = jnp.sum(prefix * onehot, axis=0,
                         keepdims=True)                # [1, COLS]
        start = jnp.sum(onehot * run_ref[:], axis=0,
                        keepdims=True)                 # gather by digit
        out_ref[r:r + 1, :] = (start + within).astype(jnp.int32)
        run_ref[:] += jnp.sum(onehot, axis=1, keepdims=True)


def stable_partition_offsets_pallas(dest: jnp.ndarray, num_bins: int,
                                    interpret: bool = False
                                    ) -> jnp.ndarray:
    """Pallas path of :func:`stable_partition_offsets`."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n = dest.shape[0]
    n_pad = _round_up(max(n, 1), BLOCK)
    # out-of-range and padding rows partition into sentinel bin
    # num_bins (kept stable after every real row) so they never
    # collide with real offsets
    bpad = _round_up(num_bins + 1, LANES)
    dest = jnp.where((dest >= 0) & (dest < num_bins),
                     dest.astype(jnp.int32), num_bins)
    d = jnp.full(n_pad, num_bins, jnp.int32).at[:n].set(dest)
    hist = partition_histogram(d, num_bins)            # real bins only
    base = jnp.concatenate([
        jnp.zeros(1, jnp.int32),
        jnp.cumsum(hist.astype(jnp.int32))])           # [num_bins + 1]
    base = jnp.pad(base, (0, bpad - num_bins - 1))
    d2 = d.reshape(n_pad // COLS, COLS)

    kernel = functools.partial(_part_kernel, num_bins_padded=bpad)
    out = pl.pallas_call(
        kernel,
        grid=(n_pad // BLOCK,),
        in_specs=[pl.BlockSpec((bpad, 1), _first_block),
                  pl.BlockSpec((SUBLANES, COLS), _row_block)],
        out_specs=pl.BlockSpec((SUBLANES, COLS), _row_block),
        out_shape=jax.ShapeDtypeStruct((n_pad // COLS, COLS),
                                       jnp.int32),
        scratch_shapes=[pltpu.VMEM((bpad, 1), jnp.float32)],
        interpret=interpret,
    )(base.reshape(bpad, 1), d2)
    return out.reshape(-1)[:n]


def _offsets_scan(dest: jnp.ndarray, num_bins: int) -> jnp.ndarray:
    """lax.scan fallback: same carried-counter math, any platform."""
    n = dest.shape[0]
    n_pad = _round_up(max(n, 1), BLOCK)
    B = num_bins + 1                                   # + pad sentinel
    dest = jnp.where((dest >= 0) & (dest < num_bins),
                     dest.astype(jnp.int32), num_bins)
    d = jnp.full(n_pad, num_bins, jnp.int32).at[:n].set(dest)
    hist = jnp.bincount(d[:n], length=B)
    base = jnp.concatenate([jnp.zeros(1, jnp.int32),
                            jnp.cumsum(hist[:num_bins])
                            .astype(jnp.int32)])       # [B]
    d2 = d.reshape(n_pad // BLOCK, BLOCK)

    def step(carry, dt):
        onehot = (dt[:, None] == jnp.arange(B)[None, :]).astype(
            jnp.int32)                                 # [BLOCK, B]
        prefix = jnp.cumsum(onehot, axis=0) - onehot   # exclusive
        within = jnp.sum(prefix * onehot, axis=1)
        start = jnp.take(carry, dt)
        return (carry + jnp.sum(onehot, axis=0).astype(jnp.int32),
                start + within.astype(jnp.int32))

    _, offs = jax.lax.scan(step, base, d2)
    return offs.reshape(-1)[:n]


def stable_partition_offsets(dest: jnp.ndarray,
                             num_bins: int) -> jnp.ndarray:
    """offsets[i] = stable-partition target of row i under dest[i].
    Values outside [0, num_bins) are SANITIZED into the trailing pad
    bin (both engines) and land after every real row, still stably —
    the result is always a permutation of [0, n)."""
    if pallas_enabled() and dest.shape[0] < _F32_EXACT:
        return stable_partition_offsets_pallas(dest, num_bins)
    return _offsets_scan(dest, num_bins)


def radix_argsort_device(words: Sequence[jnp.ndarray],
                         word_bits: Optional[Sequence[int]] = None,
                         digit_bits: int = 8) -> jnp.ndarray:
    """LSD radix argsort by lexicographic uint words (words[0] most
    significant) — O(n * passes), no comparison network, no XLA sort.

    ``word_bits[k]`` bounds the USED high bits of words[k] counting
    from bit 0 (e.g. a 2-byte zero-padded field packed high uses 64 —
    pass the real span; dead all-zero passes are skipped statically).
    """
    n = words[0].shape[0]
    nbins = 1 << digit_bits
    perm = jnp.arange(n, dtype=jnp.int32)

    def run_pass(digit, p):
        offs = stable_partition_offsets(digit, nbins)
        return jnp.zeros_like(p).at[offs].set(p)

    for k in range(len(words) - 1, -1, -1):
        w = words[k]
        bits = 64 if word_bits is None else int(word_bits[k])
        w = w.astype(jnp.uint64)
        for shift in range(0, bits, digit_bits):
            digit = ((jnp.take(w, perm) >> jnp.uint64(shift))
                     & jnp.uint64(nbins - 1)).astype(jnp.int32)
            # runtime dead-pass skip (the host engine's histogram skip,
            # expressed as lax.cond): a uniform digit — zero-padded key
            # bytes, narrow fields — costs one O(n) check instead of a
            # full partition + scatter
            uniform = jnp.all(digit == digit[0])
            perm = jax.lax.cond(uniform, lambda d, p: p, run_pass,
                                digit, perm)
    return perm
