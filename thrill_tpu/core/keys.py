"""Key encoding: map item keys to lexicographic uint64 word vectors.

XLA's sort (and our Pallas kernels) compare fixed numbers of scalar
words, not arbitrary C++ comparators. Any key pytree whose leaves are
ints, floats, bools or fixed-width byte vectors is encoded into k uint64
"key words" whose lexicographic order equals the natural order of the
key (tuple order = left-to-right significance, matching the reference's
operator< on std::tuple / struct keys used by api/sort.hpp).

Encodings (all order-preserving):
* unsigned ints  -> zero-extended
* signed ints    -> bias by 2^63 (flip sign bit)
* floats         -> IEEE trick: if sign bit set, flip all bits, else flip
                    sign bit (total order incl. -0 < +0; NaN sorts last)
* uint8[L] bytes -> big-endian packed into ceil(L/8) words (shorter-is-
                    smaller padding with zeros — matches memcmp on
                    zero-padded fixed-width fields, e.g. TeraSort keys)
"""

from __future__ import annotations

from typing import Any, List

import jax
import jax.numpy as jnp
import numpy as np


def num_key_words(example_key_tree: Any) -> int:
    """Number of uint64 words the encoder will produce per item."""
    total = 0
    for leaf in jax.tree.leaves(example_key_tree):
        leaf = np.asarray(leaf) if not hasattr(leaf, "dtype") else leaf
        if leaf.dtype == np.uint8 and leaf.ndim >= 1:
            total += -(-leaf.shape[-1] // 8)
        else:
            total += 1
    return total


def encode_key_words(key_tree: Any) -> List[jnp.ndarray]:
    """Encode a batched key pytree (leaves [n] or [n, L]) to uint64 [n] words."""
    words: List[jnp.ndarray] = []
    for leaf in jax.tree.leaves(key_tree):
        dt = leaf.dtype
        if dt == jnp.uint8 and leaf.ndim >= 2:
            words.extend(_pack_bytes(leaf))
        elif jnp.issubdtype(dt, jnp.unsignedinteger):
            words.append(leaf.astype(jnp.uint64))
        elif jnp.issubdtype(dt, jnp.signedinteger) or dt == jnp.bool_:
            w = leaf.astype(jnp.int64).astype(jnp.uint64)
            words.append(w ^ jnp.uint64(1 << 63))
        elif jnp.issubdtype(dt, jnp.floating):
            bits = jax.lax.bitcast_convert_type(
                leaf.astype(jnp.float64), jnp.uint64)
            sign = bits >> jnp.uint64(63)
            flipped = jnp.where(sign == 1, ~bits, bits | jnp.uint64(1 << 63))
            words.append(flipped)
        else:
            raise TypeError(f"unsupported key leaf dtype {dt}")
    if not words:
        raise ValueError("key function produced an empty pytree")
    return words


def encode_key_words_np(key_tree: Any) -> List[np.ndarray]:
    """Host mirror of :func:`encode_key_words` over numpy leaves —
    identical word values, no XLA dispatch (used by the CPU backend's
    native radix sort path, where eager jnp op overhead would dominate
    the sort itself)."""
    words: List[np.ndarray] = []
    for leaf in jax.tree.leaves(key_tree):
        leaf = np.asarray(leaf)
        dt = leaf.dtype
        if dt == np.uint8 and leaf.ndim == 2:
            n, L = leaf.shape
            nwords = -(-L // 8)
            padded = np.zeros((n, nwords * 8), dtype=np.uint8)
            padded[:, :L] = leaf
            packed = padded.view(np.dtype(">u8")).astype(np.uint64)
            words.extend(packed[:, i] for i in range(nwords))
        elif dt == np.uint8 and leaf.ndim > 2:
            # >2-D byte keys produce non-flat words in the traced
            # encoder; no host mirror — let callers fall back to it
            raise TypeError("encode_key_words_np: >2-D uint8 key leaf")
        elif np.issubdtype(dt, np.unsignedinteger):
            words.append(leaf.astype(np.uint64))
        elif np.issubdtype(dt, np.signedinteger) or dt == np.bool_:
            words.append(leaf.astype(np.int64).astype(np.uint64)
                         ^ np.uint64(1 << 63))
        elif np.issubdtype(dt, np.floating):
            bits = leaf.astype(np.float64).view(np.uint64)
            sign = bits >> np.uint64(63)
            words.append(np.where(sign == 1, ~bits,
                                  bits | np.uint64(1 << 63)))
        else:
            raise TypeError(f"unsupported key leaf dtype {dt}")
    if not words:
        raise ValueError("key function produced an empty pytree")
    return words


def _pack_bytes(leaf: jnp.ndarray) -> List[jnp.ndarray]:
    """[n, L] uint8 -> ceil(L/8) big-endian uint64 [n] words."""
    n, L = leaf.shape[0], leaf.shape[-1]
    nwords = -(-L // 8)
    padded = jnp.pad(leaf, [(0, 0)] * (leaf.ndim - 1) + [(0, nwords * 8 - L)])
    grouped = padded.reshape(*leaf.shape[:-1], nwords, 8).astype(jnp.uint64)
    shifts = jnp.uint64(8) * jnp.arange(7, -1, -1, dtype=jnp.uint64)
    packed = jnp.sum(grouped << shifts, axis=-1, dtype=jnp.uint64)
    # -> [n, nwords]; split into word list
    return [packed[..., i] for i in range(nwords)]

