"""Packed row movement: u32 word views for sub-word payload columns.

TPU VPU lanes are 32-bit; a gather/scatter of a [n, 90] uint8 payload
column moves 90 sub-word elements per row where 23 u32 words would do.
Every bulk row movement (sort payload gathers, exchange scatters +
all_to_all) can therefore run on a bitcast u32 view: pad the trailing
axis to a 4-byte multiple, bitcast to uint32, move, bitcast back,
slice. Pack and unpack live INSIDE the same jitted program as the
movement, so the layout is never observable outside and endianness is
self-consistent by construction.

Gate: THRILL_TPU_PACK_MOVE = auto (default: on for accelerator
backends, off on CPU) | 1 | 0. The helpers are no-ops for leaves where
packing cannot help (4-byte+ dtypes, tiny rows, 1-D sub-word columns).

Reference analog: the block layer moves opaque byte ranges, not typed
items (thrill/data/block.hpp:52) — this is the columnar, static-shape
translation of that idea.
"""

from __future__ import annotations

import os
from typing import List

import jax
import jax.numpy as jnp
from jax import lax


# the name the gathers carry in a device profile (jax.named_scope: HLO
# metadata, no operation added), beside core/device_sort.py's
SCOPE = "row_move"


def enabled() -> bool:
    mode = os.environ.get("THRILL_TPU_PACK_MOVE", "auto")
    if mode in ("0", "false"):
        return False
    if mode == "auto":
        return jax.default_backend() != "cpu"
    return True


def _packable(x) -> bool:
    dt = jnp.dtype(x.dtype)
    isz = dt.itemsize
    # bitcast_convert_type rejects bool (and complex never benefits)
    if dt == jnp.bool_ or dt.kind == "c" or isz >= 4 or x.ndim < 2:
        return False
    row_elems = 1
    for d in x.shape[1:]:
        row_elems *= d
    return row_elems * isz >= 8      # tiny rows: packing buys nothing


def pack_rows(x):
    """[n, ...] sub-word leaf -> ([n, w] uint32 view, meta). Leaves that
    cannot profit pass through with meta=None."""
    if not _packable(x):
        return x, None
    n = x.shape[0]
    isz = jnp.dtype(x.dtype).itemsize
    flat = x.reshape(n, -1)
    k = flat.shape[1]
    per = 4 // isz                   # elements per u32 word
    pad = (-k) % per
    if pad:
        flat = jnp.pad(flat, ((0, 0), (0, pad)))
    words = lax.bitcast_convert_type(
        flat.reshape(n, (k + pad) // per, per), jnp.uint32)
    return words, (x.dtype, x.shape[1:], k, per)


def unpack_rows(words, meta):
    """Inverse of pack_rows on the moved words."""
    if meta is None:
        return words
    dtype, trail_shape, k, per = meta
    n = words.shape[0]
    flat = lax.bitcast_convert_type(words, dtype)   # [n, w, per]
    flat = flat.reshape(n, -1)[:, :k]
    return flat.reshape((n,) + tuple(trail_shape))


def pack_leaves(leaves: List):
    """Pack every leaf; returns (packed_leaves, metas)."""
    packed, metas = [], []
    for l in leaves:
        p, m = pack_rows(l)
        packed.append(p)
        metas.append(m)
    return packed, metas


def unpack_leaves(packed: List, metas: List):
    return [unpack_rows(p, m) for p, m in zip(packed, metas)]


@jax.named_scope(SCOPE)
def take_rows(x, perm):
    """jnp.take(x, perm, axis=0) through the packed view when enabled
    and profitable — the drop-in gather for payload columns."""
    if not enabled():
        return jnp.take(x, perm, axis=0)
    words, meta = pack_rows(x)
    return unpack_rows(jnp.take(words, perm, axis=0), meta)


# ----------------------------------------------------------------------
# widened + batched gathers: ONE u32 word matrix for a whole leaf set
# ----------------------------------------------------------------------
# A permutation gather of a typical sorted payload moves each leaf in
# its own gather — sub-word leaves as packed words, but every >=4-byte
# scalar column ([n] int64 keys, [n] float64 ranks) as SCALAR rows: one
# element per gathered row, 1.6% of the HBM roofline measured (13 GB/s,
# BENCH r5). ``pack_rows_wide`` widens packing to those leaves too
# (any non-bool/complex dtype bitcasts to u32 words, 1-D columns
# included), and ``take_rows_multi`` batches every widenable leaf into
# ONE [n, total_words] matrix so a single gather moves all their words
# per lane instead of k scalar gathers.


def pack_rows_wide(x):
    """[n, ...] leaf of ANY non-bool/complex dtype -> ([n, w] uint32
    words, meta). Unlike :func:`pack_rows` this also packs 1-D columns
    and >=4-byte dtypes (each element bitcast to itemsize/4 words), so
    a whole payload tree can ride one word matrix. Returns (x, None)
    for leaves that cannot be packed.

    The narrow branch mirrors :func:`pack_rows` (different word layout:
    flattened 2-D here vs [n, w, per] there, matching each consumer's
    concat/ship shape) — a pad/bitcast change to one must be mirrored
    in the other."""
    dt = jnp.dtype(x.dtype)
    isz = dt.itemsize
    if dt == jnp.bool_ or dt.kind == "c":
        return x, None
    n = x.shape[0]
    flat = x.reshape(n, -1)
    k = flat.shape[1]
    if isz >= 4:
        words = lax.bitcast_convert_type(flat, jnp.uint32)
        if isz > 4:                    # [n, k, isz//4] -> [n, k*isz//4]
            words = words.reshape(n, -1)
        return words, ("wide", x.dtype, x.shape[1:], k, isz)
    per = 4 // isz                     # elements per u32 word
    pad = (-k) % per
    if pad:
        flat = jnp.pad(flat, ((0, 0), (0, pad)))
    words = lax.bitcast_convert_type(
        flat.reshape(n, (k + pad) // per, per), jnp.uint32
    ).reshape(n, -1)
    return words, ("narrow", x.dtype, x.shape[1:], k, per)


def unpack_rows_wide(words, meta):
    """Inverse of :func:`pack_rows_wide` on the moved words."""
    if meta is None:
        return words
    kind, dtype, trail_shape, k, arg = meta
    n = words.shape[0]
    if kind == "wide":
        isz = arg
        if isz > 4:                    # [n, k*m] -> [n, k, m] -> [n, k]
            flat = lax.bitcast_convert_type(
                words.reshape(n, k, isz // 4), dtype)
        else:
            flat = lax.bitcast_convert_type(words, dtype)
        return flat.reshape((n,) + tuple(trail_shape))
    # narrow: [n, w] u32 -> [n, w, per] elems, trim the pad
    flat = lax.bitcast_convert_type(words, dtype)
    flat = flat.reshape(n, -1)[:, :k]
    return flat.reshape((n,) + tuple(trail_shape))


@jax.named_scope(SCOPE)
def take_rows_multi(leaves, perm):
    """Gather MANY leaves by one shared row permutation through a
    single concatenated u32 word matrix.

    All widenable leaves bitcast+concatenate into one [n, W_total]
    uint32 matrix, ONE ``jnp.take`` moves it, and the slices bitcast
    back — the gather engine sees wide rows instead of k scalar/narrow
    gathers (the 13 GB/s -> multi-word-per-lane fix). Leaves that
    cannot pack (bool, complex) gather individually; with packing
    disabled this degrades to plain per-leaf takes."""
    leaves = list(leaves)
    if not enabled() or len(leaves) == 0:
        return [jnp.take(l, perm, axis=0) for l in leaves]
    packed = [pack_rows_wide(l) for l in leaves]
    batch = [(i, w, m) for i, (w, m) in enumerate(packed)
             if m is not None]
    out: list = [None] * len(leaves)
    for i, (w, m) in enumerate(packed):
        if m is None:
            out[i] = jnp.take(leaves[i], perm, axis=0)
    if batch:
        if len(batch) == 1:
            i, w, m = batch[0]
            out[i] = unpack_rows_wide(jnp.take(w, perm, axis=0), m)
        else:
            widths = [w.shape[1] for _, w, _ in batch]
            mat = jnp.concatenate([w for _, w, _ in batch], axis=1)
            moved = jnp.take(mat, perm, axis=0)
            off = 0
            for (i, _w, m), width in zip(batch, widths):
                out[i] = unpack_rows_wide(moved[:, off:off + width], m)
                off += width
    return out
