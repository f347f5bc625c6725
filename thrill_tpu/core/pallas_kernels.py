"""Pallas TPU kernels for data-plane hot loops.

The exchange planner's per-destination histogram and the reduce phases'
segment sums are the innermost device loops of every shuffle (reference
analog: the per-partition counters of ReducePrePhase,
core/reduce_pre_phase.hpp:94). These kernels keep the accumulator in
VMEM across a sequential grid over row blocks, and express the one-hot
accumulation as lane-parallel VPU compares and reductions (the
stable-partition kernel in pallas_sort.py additionally rides the MXU
for its within-row triangular prefix).

Layout (settled by an on-chip round-5 lowering session — the original
(1, BLOCK) row blocks violated Mosaic's (8, 128) trailing-dims rule,
and the ``d.reshape(BLOCK, 1)`` one-hot pivot is a lane->sublane
transpose Mosaic won't lower):

* data tiles are ``(SUBLANES, COLS)`` = (8, 64) — 512 elements per
  sequential grid step, elements ALWAYS on the lane axis;
* bin/segment counters are ``(bins, 1)`` columns — bins on the
  SUBLANE axis — so one-hot compares are pure broadcasts
  ``iota(bins, COLS) == d_row(1, COLS)`` with no transposes anywhere.

Usage is gated: ``partition_histogram`` dispatches to the Pallas kernel
when THRILL_TPU_PALLAS=1 and the platform is a TPU, else to the jnp
fallback (identical semantics; CPU tests run the kernel in interpret
mode to pin equivalence).
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np

BLOCK = 512          # elements per sequential grid step
LANES = 128
SUBLANES = 8         # Mosaic block rule: trailing dims divisible by
                     # (8, 128) or equal to the array's dims
COLS = BLOCK // SUBLANES   # 64 lanes per tile row


# f32 one-hot partials stay exact below this row count (same bound as
# pallas_sort._F32_EXACT); every dispatcher refuses larger inputs
MAX_ROWS = 1 << 24
# one-hot register fill / segment sum are O(bins*n) lane-compares: a
# clear win only while the bin column stays small (the preshuffle
# _REG_MIN clamp's home turf); above these XLA's native scatter wins.
# They are also what fits VMEM: the (bins, 1) counter column pads 128x
# under the (8, 128) tiling, and at 1 << 13 registers the v5e compiler
# refuses presence_fill (28 MiB scoped against a 16 MiB limit;
# tests/core/test_tpu_aot_compile.py holds both gates to the compiler)
PRESFILL_MAX_REGS = 1 << 12
SEGSUM_MAX_SEGS = 1 << 12
# The XLA histogram counts up to this many bins by comparison: one fused
# reduce of ``dest == b`` over every bin, which reads ``dest`` and writes
# nothing of its length. Its O(n * bins) compares cost 0.6 ms at 5 bins
# and 18.5 ms at 4,096 where the scatter-add of int64 ones costs 252 and
# 290 ms (2^22 rows on a v5e, PERF.md); above the largest bin count
# measured the scatter stays
HIST_COMPARE_MAX_BINS = 1 << 12

_MISSING = object()


def pallas_enabled(mex=None) -> bool:
    """True when the Pallas kernel tier should drive eligible hot loops
    (THRILL_TPU_PALLAS=1 on a real TPU backend).

    The knob is resolved ONCE at MeshExec construction (mirroring the
    THRILL_TPU_EXCHANGE contract): inside a dispatch or trace the
    owning mesh's cached value wins — flipping the env var after the
    mesh exists deliberately does nothing. Outside any dispatch (bare
    kernel calls, unit tests) fall back to the live env read.
    """
    if mex is None:
        from ..parallel.mesh import current_mex
        mex = current_mex()
    env = getattr(mex, "_env_pallas", _MISSING) if mex is not None \
        else _MISSING
    if env is _MISSING:
        env = os.environ.get("THRILL_TPU_PALLAS", "0")
    return env == "1" and jax.default_backend() == "tpu"


def rows_ok(n: int) -> bool:
    """Row-count refusal gate shared by every kernel dispatcher."""
    return n < MAX_ROWS


def presence_fill_ok(num_regs: int, n: int) -> bool:
    return num_regs <= PRESFILL_MAX_REGS and rows_ok(n)


def segment_sum_ok(num_segments: int, n: int) -> bool:
    return num_segments <= SEGSUM_MAX_SEGS and rows_ok(n)


def _round_up(n: int, g: int) -> int:
    return ((n + g - 1) // g) * g


# BlockSpec index maps. The package turns x64 on, under which a Python
# ``0`` traces to i64 beside the i32 grid index, and Mosaic refuses the
# mixed (i32, i64) return — so the constant is spelled as an i32.
def _row_block(i):
    return i, jnp.int32(0)


def _first_block(i):
    return jnp.int32(0), jnp.int32(0)


def _hist_kernel(dest_ref, out_ref, *, num_bins_padded: int):
    from jax.experimental import pallas as pl

    pi = pl.program_id(0)

    @pl.when(pi == 0)
    def _init():
        out_ref[:] = jnp.zeros_like(out_ref)

    bins = jax.lax.broadcasted_iota(
        jnp.int32, (num_bins_padded, COLS), 0)        # [B, COLS]
    acc = jnp.zeros((num_bins_padded, 1), jnp.float32)
    for r in range(SUBLANES):                          # static unroll
        d_r = dest_ref[r:r + 1, :]                     # [1, COLS] int32
        onehot = (bins == d_r).astype(jnp.float32)     # [B, COLS]
        # per-row count = lane reduce; partials <= BLOCK (exact in f32),
        # the cross-block accumulator is int32 so totals never lose
        # precision past 2^24
        acc += jnp.sum(onehot, axis=1, keepdims=True)
    out_ref[:] += acc.astype(jnp.int32)


def partition_histogram_pallas(dest: jnp.ndarray, num_bins: int,
                               interpret: bool = False) -> jnp.ndarray:
    """Count occurrences of each bin value in ``dest`` (int32 [n]).

    Values outside [0, num_bins) are ignored (padding sentinel W).
    """
    from jax.experimental import pallas as pl

    n = dest.shape[0]
    n_pad = _round_up(max(n, 1), BLOCK)
    bpad = _round_up(max(num_bins, 1), LANES)
    d = jnp.full(n_pad, -1, jnp.int32).at[:n].set(dest.astype(jnp.int32))
    d2 = d.reshape(n_pad // COLS, COLS)

    kernel = functools.partial(_hist_kernel, num_bins_padded=bpad)
    out = pl.pallas_call(
        kernel,
        grid=(n_pad // BLOCK,),
        in_specs=[pl.BlockSpec((SUBLANES, COLS), _row_block)],
        out_specs=pl.BlockSpec((bpad, 1), _first_block),
        out_shape=jax.ShapeDtypeStruct((bpad, 1), jnp.int32),
        interpret=interpret,
    )(d2)
    return out[:num_bins, 0]


def histogram_path(n: int, num_bins: int) -> str:
    """Which mechanism :func:`partition_histogram` counts ``n`` ids into
    ``num_bins`` bins with: ``"pallas"``, ``"compare"`` or
    ``"scatter"``. Decided by the static shapes (and the Pallas knob)."""
    if pallas_enabled() and rows_ok(n):
        return "pallas"
    return "compare" if num_bins <= HIST_COMPARE_MAX_BINS else "scatter"


def partition_histogram(dest: jnp.ndarray, num_bins: int) -> jnp.ndarray:
    """Count each bin value of ``dest`` into ``int32[num_bins]``: Pallas
    on TPU when enabled, else a compare-and-sum per bin where the bins
    are few, else a scatter-add (:func:`histogram_path`).

    Every path ignores values outside [0, num_bins) — negative or
    too-large ids are padding sentinels, never counted — and none needs
    ``dest`` sorted.
    """
    path = histogram_path(dest.shape[0], num_bins)
    if path == "pallas":
        return partition_histogram_pallas(dest, num_bins)
    if path == "compare":
        bins = jnp.arange(num_bins, dtype=dest.dtype)
        return jnp.sum(dest[None, :] == bins[:, None], axis=1,
                       dtype=jnp.int32)
    sanitized = jnp.where((dest >= 0) & (dest < num_bins), dest, num_bins)
    return jnp.bincount(sanitized,
                        length=num_bins + 1)[:num_bins].astype(jnp.int32)


def _segsum_kernel(seg_ref, val_ref, out_ref, *, num_segs_padded: int):
    from jax.experimental import pallas as pl

    pi = pl.program_id(0)

    @pl.when(pi == 0)
    def _init():
        out_ref[:] = jnp.zeros_like(out_ref)

    segs = jax.lax.broadcasted_iota(
        jnp.int32, (num_segs_padded, COLS), 0)        # [S, COLS]
    acc = jnp.zeros((num_segs_padded, 1), jnp.float32)
    for r in range(SUBLANES):                          # static unroll
        s_r = seg_ref[r:r + 1, :]                      # [1, COLS]
        v_r = val_ref[r:r + 1, :]                      # [1, COLS] f32
        onehot = (segs == s_r).astype(jnp.float32)     # [S, COLS]
        acc += jnp.sum(onehot * v_r, axis=1, keepdims=True)
    out_ref[:] += acc


def segment_sum(seg_ids: jnp.ndarray, values: jnp.ndarray,
                num_segments: int) -> jnp.ndarray:
    """Dispatch: Pallas on TPU when enabled, else jax segment_sum."""
    if pallas_enabled() and segment_sum_ok(num_segments, values.shape[0]):
        return segment_sum_pallas(seg_ids, values, num_segments)
    import jax.ops
    safe = jnp.where((seg_ids >= 0) & (seg_ids < num_segments),
                     seg_ids, num_segments)
    return jax.ops.segment_sum(values.astype(jnp.float32), safe,
                               num_segments=num_segments + 1)[:num_segments]


def segment_sum_pallas(seg_ids: jnp.ndarray, values: jnp.ndarray,
                       num_segments: int,
                       interpret: bool = False) -> jnp.ndarray:
    """Sum float32 ``values`` into ``num_segments`` buckets by seg id.

    This is the specialized fast path for additive float reductions
    (dense ReduceToIndex-style sums); the generic reduce pipeline keeps
    the segmented associative scan, which supports arbitrary reduce
    functions.
    """
    from jax.experimental import pallas as pl

    n = values.shape[0]
    n_pad = _round_up(max(n, 1), BLOCK)
    spad = _round_up(max(num_segments, 1), LANES)
    s = jnp.full(n_pad, -1, jnp.int32).at[:n].set(seg_ids.astype(jnp.int32))
    v = jnp.zeros(n_pad, jnp.float32).at[:n].set(values.astype(jnp.float32))

    kernel = functools.partial(_segsum_kernel, num_segs_padded=spad)
    out = pl.pallas_call(
        kernel,
        grid=(n_pad // BLOCK,),
        in_specs=[pl.BlockSpec((SUBLANES, COLS), _row_block),
                  pl.BlockSpec((SUBLANES, COLS), _row_block)],
        out_specs=pl.BlockSpec((spad, 1), _first_block),
        out_shape=jax.ShapeDtypeStruct((spad, 1), jnp.float32),
        interpret=interpret,
    )(s.reshape(-1, COLS), v.reshape(-1, COLS))
    return out[:num_segments, 0]


def _presfill_kernel(h_ref, v_ref, out_ref, *, num_regs_padded: int):
    from jax.experimental import pallas as pl

    pi = pl.program_id(0)

    @pl.when(pi == 0)
    def _init():
        out_ref[:] = jnp.zeros_like(out_ref)

    regs = jax.lax.broadcasted_iota(
        jnp.int32, (num_regs_padded, COLS), 0)         # [M, COLS]
    acc = jnp.zeros((num_regs_padded, 1), jnp.float32)
    for r in range(SUBLANES):                          # static unroll
        h_r = h_ref[r:r + 1, :]                        # [1, COLS] int32
        v_r = v_ref[r:r + 1, :]                        # [1, COLS] f32
        onehot = (regs == h_r).astype(jnp.float32)     # [M, COLS]
        acc = jnp.maximum(
            acc, jnp.max(onehot * v_r, axis=1, keepdims=True))
    out_ref[:] = jnp.maximum(out_ref[:], acc.astype(jnp.int32))


def presence_fill_pallas(h: jnp.ndarray, valid: jnp.ndarray,
                         num_regs: int,
                         interpret: bool = False) -> jnp.ndarray:
    """u8 presence registers: out[m] = 1 iff some i has ``h[i] == m``
    and ``valid[i]`` truthy. Values of ``h`` outside [0, num_regs) are
    ignored (padding sentinel -1)."""
    from jax.experimental import pallas as pl

    n = h.shape[0]
    n_pad = _round_up(max(n, 1), BLOCK)
    mpad = _round_up(max(num_regs, 1), LANES)
    hp = jnp.full(n_pad, -1, jnp.int32).at[:n].set(h.astype(jnp.int32))
    vp = jnp.zeros(n_pad, jnp.float32).at[:n].set(
        valid.astype(jnp.float32))

    kernel = functools.partial(_presfill_kernel, num_regs_padded=mpad)
    out = pl.pallas_call(
        kernel,
        grid=(n_pad // BLOCK,),
        in_specs=[pl.BlockSpec((SUBLANES, COLS), _row_block),
                  pl.BlockSpec((SUBLANES, COLS), _row_block)],
        out_specs=pl.BlockSpec((mpad, 1), _first_block),
        out_shape=jax.ShapeDtypeStruct((mpad, 1), jnp.int32),
        interpret=interpret,
    )(hp.reshape(-1, COLS), vp.reshape(-1, COLS))
    return (out[:num_regs, 0] > 0).astype(jnp.uint8)


def presence_fill(h: jnp.ndarray, valid: jnp.ndarray,
                  num_regs: int) -> jnp.ndarray:
    """Dispatch: Pallas on TPU when enabled and the register column is
    small enough that one-hot compares beat XLA's scatter, else the
    scatter-max fallback (bit-identical — presence is 0/1, no float
    reassociation). This is the device analog of the reference's
    Golomb-coded fingerprint columns (duplicate detection,
    arXiv:1608.05634): the pre-shuffle presence registers that
    location-detect and dup-detect fill before any data ships.
    """
    if pallas_enabled() and presence_fill_ok(num_regs, h.shape[0]):
        return presence_fill_pallas(h, valid, num_regs)
    safe = jnp.where((h >= 0) & (h < num_regs), h, num_regs)
    return jnp.zeros(num_regs + 1, jnp.uint8).at[safe].max(
        valid.astype(jnp.uint8))[:num_regs]
