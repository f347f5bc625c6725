"""HyperLogLog distinct counting.

Reference: thrill/api/hyperloglog.hpp:27 + core/hyperloglog.{hpp,cpp}
(register arrays, sparse/dense encodings, AllReduce merge). Device
path: hash to uint64, scatter-max into 2^p registers per worker, pmax
across the mesh, classic HLL estimate with linear-counting small-range
correction on the host.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ...common import hashing
from ...core import keys as keymod
from ...data.shards import DeviceShards, HostShards
from ...parallel.mesh import AXIS
from ..dia import DIA
from ..dia_base import staged_action


def _alpha(m: int) -> float:
    if m == 16:
        return 0.673
    if m == 32:
        return 0.697
    if m == 64:
        return 0.709
    return 0.7213 / (1 + 1.079 / m)


def _estimate(registers: np.ndarray, p: int) -> float:
    m = 1 << p
    inv = np.sum(np.exp2(-registers.astype(np.float64)))
    raw = _alpha(m) * m * m / inv
    if raw <= 2.5 * m:
        zeros = int(np.sum(registers == 0))
        if zeros:
            return m * np.log(m / zeros)
    two32 = float(1 << 32)
    if raw > two32 / 30.0:
        return -two32 * np.log(1.0 - raw / two32)
    return raw


@staged_action
def HyperLogLog(dia: DIA, precision: int = 14) -> float:
    p = int(precision)
    m = 1 << p
    shards = dia._link().pull()
    if isinstance(shards, HostShards):
        regs = np.zeros(m, dtype=np.int32)
        for items in shards.lists:
            for it in items:
                h = hashing.stable_host_hash(_hashable(it))
                idx = h >> (64 - p)
                rest = (h << p) & 0xFFFFFFFFFFFFFFFF
                # standard register range is [1, 64-p+1]: an all-zero
                # suffix yields rho = 64-p+1 (ADVICE r1)
                rho = 64 - p + 1 if rest == 0 else _clz64(rest) + 1
                regs[idx] = max(regs[idx], min(rho, 64 - p + 1))
        from ...data import multiplexer
        mex = dia.context.mesh_exec
        if multiplexer.multiprocess(mex):
            # the register sketch merges by elementwise max — ship the
            # m-register array, not the items (reference:
            # core/hyperloglog.hpp merge)
            regs = multiplexer.net_fold(mex, regs, np.maximum)
        return _estimate(regs, p)

    mex = shards.mesh_exec
    cap = shards.cap
    leaves, treedef = jax.tree.flatten(shards.tree)
    key = ("hll", p, cap, treedef,
           tuple((l.dtype, l.shape[2:]) for l in leaves))

    def build():
        def f(counts_dev, *ls):
            valid = jnp.arange(cap) < counts_dev[0, 0]
            tree = jax.tree.unflatten(treedef, [l[0] for l in ls])
            words = keymod.encode_key_words(tree)
            h = hashing.hash_key_words(words)
            idx = (h >> jnp.uint64(64 - p)).astype(jnp.int32)
            rest = h << jnp.uint64(p)
            # register range [1, 64-p+1]; rest==0 -> 64-p+1 (ADVICE r1)
            rho = jnp.where(rest == 0, 64 - p + 1, _clz_device(rest) + 1)
            rho = jnp.minimum(rho, 64 - p + 1).astype(jnp.int32)
            rho = jnp.where(valid, rho, 0)
            regs = jnp.zeros(m, jnp.int32).at[idx].max(rho)
            return lax.pmax(regs, AXIS)

        from jax.sharding import PartitionSpec as P
        return mex.smap(f, 1 + len(leaves), out_specs=P())

    fn = mex.cached(key, build)
    regs = mex.fetch(fn(shards.counts_device(), *leaves))
    return _estimate(regs, p)


def _clz_device(x: jnp.ndarray) -> jnp.ndarray:
    """Count leading zeros of nonzero uint64 (branch-free doubling)."""
    n = jnp.zeros(x.shape, jnp.int32)
    for shift in (32, 16, 8, 4, 2, 1):
        hi = x >> jnp.uint64(64 - shift)
        move = hi == 0
        n = n + jnp.where(move, shift, 0)
        x = jnp.where(move, x << jnp.uint64(shift), x)
    return n


def _clz64(v: int) -> int:
    n = 0
    for shift in (32, 16, 8, 4, 2, 1):
        if (v >> (64 - shift)) == 0:
            n += shift
            v = (v << shift) & 0xFFFFFFFFFFFFFFFF
    return n


def _hashable(it):
    if isinstance(it, np.ndarray):
        return tuple(it.tolist())
    if isinstance(it, np.generic):
        return it.item()
    return it
