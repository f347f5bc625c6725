"""All-to-all item exchange: the TPU-native shuffle data plane.

The reference moves items between workers through serialized Block
streams multiplexed over TCP/MPI connections (reference:
thrill/data/multiplexer.hpp:67, cat_stream.hpp:155, mix_stream.hpp:126,
stream.hpp:77-210 ``Scatter``). The TPU-native equivalent is a
bulk-synchronous exchange of columnar shards over the ICI mesh:

  Phase A (jit): compute each item's destination worker, stably sort
      items by destination, count per-destination sends
      -> the analog of the reference's per-destination BlockWriters.
  Host step: agree on padded block capacity from the [W, W] send-count
      matrix (tiny transfer; shapes must be static for XLA). Capacities
      round up to powers of two so recompilation is rare.
  Phase B (jit): cut the dest-sorted rows into [W, M] padded
      per-destination blocks (slices: the send side scatters nothing),
      ``lax.all_to_all`` over the mesh, compact received blocks into a
      fresh [out_cap] shard -> the analog of Multiplexer block transit +
      receive-side BlockQueues.

On real TPU pods `lax.ragged_all_to_all` can skip the padding (config
``exchange='ragged'``); XLA:CPU lacks that op, so the dense padded path
is the portable default.

Destination builders cover every DOp shuffle pattern:
  hash partition (ReduceByKey/GroupBy/Join), range partition by splitter
  search (Sort/Merge), index ranges (ReduceToIndex/Zip/Concat/Rebalance)
  and explicit per-item targets.

Overlapped data plane (the MixStream-analog dispatch discipline):

* Phase B is CHUNKED — the per-destination slot space [0, M_pad) splits
  into K row ranges (``common/partition.py`` bounds) and each range is
  its own jitted dispatch scattering into a shared output accumulator.
  Every output row is written by exactly one chunk at the exact position
  the bulk program would use, so results are bit-identical for any K;
  jax's async dispatch keeps chunk i's ``all_to_all`` + compaction in
  flight while chunk i+1's blocks are cut, and the consumer's next program
  can be enqueued before the last chunks land. ``THRILL_TPU_XCHG_CHUNKS``
  forces K; the auto policy chunks only volumes worth pipelining.
* The mid-shuffle host sync on the [W, W] send matrix is ELIDED in
  steady state: per-(plan-key, site) padded capacities learned by
  ``_sticky_caps`` double as a capacity-plan cache, phase B dispatches
  optimistically on the cached plan straight off the DEVICE send matrix
  (counts come back as a device output), and a device-computed overflow
  flag rides a deferred check (the hinted-join pattern): on a miss the
  exchange transparently re-runs from the retained phase-A output under
  the synced plan. ``THRILL_TPU_XCHG_CAP_CACHE=0`` disables the
  optimistic path; ``THRILL_TPU_OVERLAP=0`` restores the bulk-
  synchronous exchange (single dispatch + host sync) bit-identically.
"""

from __future__ import annotations

import os
import time
import weakref
from typing import Any, Callable, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..common import decisions as _decisions
from ..common import faults
from ..common import trace as _trace
from ..common.config import (cap_cache_enabled, overlap_enabled,
                             round_up_pow2, xchg_narrow_enabled)
from ..common.partition import dense_range_bounds
from ..common.retry import default_policy
from ..parallel.mesh import AXIS, MeshExec, note
from .shards import DeviceShards

# the name the exchange's device work carries in a device profile (the
# per-destination send blocks, under SCOPE/send_slice, and the
# collective; jax.named_scope: HLO metadata, no operation added), beside
# core/device_sort.py's and core/rowmove.py's
SCOPE = "exchange"

# per-chunk injection at the chunked phase-B dispatch loop: fires
# BEFORE the chunk program launches (nothing dispatched yet), so a
# transient retry is safe — mirrors the fused per-op site discipline
_F_CHUNK = faults.declare("data.exchange.chunk")
# row-narrowing injection: fires before a learned narrow spec is
# applied to a phase-B dispatch; an armed fire DEGRADES that exchange
# to full-width rows (narrowing is a pure byte optimization — shipping
# wide is always correct), never a wrong result
_F_PACK = faults.declare("data.exchange.pack")


# ----------------------------------------------------------------------
# plan-state persistence (service/plan_store.py)
# ----------------------------------------------------------------------
# The learned per-site plan state — sticky capacities, plan kinds,
# narrow ranges — is keyed by in-memory identity tuples (call-site
# ident + cap + treedef + dtypes). For persistence the tuples digest to
# stable strings: every component reprs deterministically for a fixed
# program (ints, strings, dtypes, shape tuples, PyTreeDefs), so a warm
# restart of the SAME pipeline recomputes the same digests, and a
# changed pipeline simply misses and re-learns. Values are correctness-
# neutral (a lying capacity/range is healed by the in-trace overflow
# flag), which is what makes importing them safe at all.


def _canon(x) -> str:
    """Address-free canonical repr for digesting. Call-site idents
    embed user FUNCTIONS (key extractors, reduce lambdas) whose repr
    carries a memory address; canonicalize them to module.qualname
    plus a bytecode hash — stable across processes for the same
    source, distinct for distinct lambdas sharing a qualname. Other
    objects whose default repr is address-bearing degrade to their
    class identity: a collision can only MERGE plan state of
    same-class sites, which is correctness-neutral (capacities
    ratchet, ranges/kinds are healed by the in-trace guards)."""
    if isinstance(x, tuple):
        return "(" + ",".join(_canon(e) for e in x) + ")"
    if callable(x) and not isinstance(x, type):
        qn = getattr(x, "__qualname__", None)
        if qn:
            code = getattr(x, "__code__", None)
            if code is not None:
                import hashlib
                # bytecode + constants: `lambda x: x % 7` and
                # `lambda x: x % 11` share co_code (the constant lives
                # in co_consts, referenced by index) — hashing both
                # keeps "edit the constant -> warm restart misses and
                # re-learns". Nested code objects in co_consts hash by
                # their own bytecode (their repr carries an address).
                consts = tuple(
                    c.co_code.hex() if hasattr(c, "co_code")
                    else repr(c) for c in code.co_consts)
                # closure cells too: factory-made lambdas
                # (make(7) vs make(1000)) share code AND consts — the
                # captured value is what distinguishes them
                try:
                    cells = tuple(_canon(c.cell_contents)
                                  for c in (x.__closure__ or ()))
                except Exception:
                    cells = ("<?>",)
                h = hashlib.sha1(repr((consts, cells)).encode()
                                 + b"|" + code.co_code).hexdigest()[:8]
                return f"<fn {getattr(x, '__module__', '?')}.{qn}:{h}>"
            return f"<fn {getattr(x, '__module__', '?')}.{qn}>"
    r = repr(x)
    if " at 0x" in r:
        return f"<{type(x).__module__}.{type(x).__qualname__}>"
    return r


def _ident_digest(ident: Tuple) -> str:
    import hashlib
    return hashlib.sha1(_canon(ident).encode()).hexdigest()


def plan_seed(mex: MeshExec, kind: str, ident: Tuple):
    """Consume the imported plan-store seed for ``ident`` (None when
    no store was attached or the key is unknown). Consumed ONCE: the
    live per-mesh dicts take over from the first lookup, so the seed
    table never shadows fresher in-process learning. Shared with
    core/preshuffle.py for its verdict/fraction kinds."""
    seeds = getattr(mex, "_plan_seed", None)
    if not seeds:
        return None
    m = seeds.get(kind)
    if not m:
        return None
    dg = _ident_digest(ident)
    v = m.pop(dg, None)
    if v is not None:
        mex.stats_plan_store_hits = getattr(
            mex, "stats_plan_store_hits", 0) + 1
        # decision ledger: a warm-start seed was consumed INSTEAD of a
        # data-driven plan build — explain() shows where the plan
        # store actually paid off (common/decisions.py)
        led = _decisions.ledger_of(mex)
        if led is not None:
            led.record("store_seed", site="xchg:" + dg[:10],
                       chosen=kind, reason="warm-start seed consumed")
    return v


def count_plan_build(mex: MeshExec) -> None:
    """One data-driven host plan construction (synced exchange plan /
    pre-shuffle verdict evaluation) — the events a warm plan-store
    restart runs ZERO of."""
    mex.stats_plan_builds = getattr(mex, "stats_plan_builds", 0) + 1


def merge_unconsumed_seeds(mex, out: dict) -> dict:
    """Ride imported-but-unconsumed seeds along an export, so learned
    state for pipelines NOT re-run this session survives the save
    (forgetting this silently drops their plans). Shared by every
    plan-state exporter (here and core/preshuffle.py)."""
    seeds = getattr(mex, "_plan_seed", None) or {}
    for kind in out:
        for dg, v in (seeds.get(kind) or {}).items():
            out[kind].setdefault(dg, v)
    return out


def install_plan_seeds(mex, state: dict, kinds, *,
                       symmetric: bool = False) -> int:
    """Merge digest maps for ``kinds`` into the shared lazy seed table
    (``mex._plan_seed``); returns how many entries arrived. Shared by
    every plan-state importer.

    ``symmetric`` is the caller's attestation that every rank of a
    multi-controller mesh installs these EXACT entries (the rank-0
    broadcast path, api/context.py). A non-attested install — e.g. a
    per-rank store read — flips ``mex._plan_seed_symmetric`` off, and
    with it the optimistic exchange gate (``_optimistic_ok``): seeds
    of unknown provenance could differ across ranks, and per-process
    optimism over divergent plans desyncs the collective schedule.
    IN-PROCESS learned state needs no attestation: it derives from the
    replicated send matrix under the lockstep submission contract, so
    it is symmetric by construction (the flag's default)."""
    seeds = getattr(mex, "_plan_seed", None)
    if seeds is None:
        seeds = mex._plan_seed = {}
    n = 0
    for kind in kinds:
        m = state.get(kind)
        if isinstance(m, dict) and m:
            seeds.setdefault(kind, {}).update(m)
            n += len(m)
    if n and not symmetric:
        mex._plan_seed_symmetric = False
    return n


#: MeshExec attributes owned by this module whose VALUES are shaped by
#: the worker count W (per-worker capacity vectors, W-specific plan
#: kinds and narrow ranges, unconsumed store seeds keyed under the
#: current W). An elastic resize (parallel/mesh.py MeshExec.resize)
#: archives them per W instead of letting a W' pipeline consume a
#: W-shaped capacity — a lying cap is healed by the overflow flag, but
#: a WRONG-LENGTH cap vector would be garbage, not a lie.
W_STATE_ATTRS = ("_sticky_caps", "_sticky_ranges", "_xchg_plan",
                 "_xchg_plan_uses", "_plan_seed")


def export_plan_state(mex: MeshExec) -> dict:
    """This mesh's exchange plan state as JSON-serializable digest
    maps (the plan store's on-disk form)."""
    return merge_unconsumed_seeds(mex, {
        "caps": {_ident_digest(k): [int(x) for x in v]
                 for k, v in getattr(mex, "_sticky_caps", {}).items()},
        "plan": {_ident_digest(k): str(v)
                 for k, v in getattr(mex, "_xchg_plan", {}).items()},
        "ranges": {_ident_digest(k):
                   [list(map(int, r)) if r is not None else None
                    for r in v]
                   for k, v in getattr(mex, "_sticky_ranges",
                                       {}).items()},
    })


def import_plan_state(mex: MeshExec, state: dict, *,
                      symmetric: bool = False) -> int:
    """Install exchange plan-state seeds (digest maps, as produced by
    :func:`export_plan_state`); returns how many entries arrived."""
    return install_plan_seeds(mex, state, ("caps", "plan", "ranges"),
                              symmetric=symmetric)


def _seeded_caps(mex: MeshExec, ident: Tuple) -> Optional[Tuple[int, ...]]:
    v = plan_seed(mex, "caps", ident)
    if not v:
        return None
    try:
        return tuple(int(x) for x in v)
    except (TypeError, ValueError):
        return None


def _sticky_range_get(mex: MeshExec, cap_ident: Tuple):
    """The remembered per-leaf range union for a site, seeding the
    live store from an attached plan store on first miss."""
    store = getattr(mex, "_sticky_ranges", None)
    if store is None:
        store = mex._sticky_ranges = {}
    prev = store.get(cap_ident)
    if prev is None:
        v = plan_seed(mex, "ranges", cap_ident)
        if v is not None:
            try:
                prev = tuple(tuple(int(x) for x in r)
                             if r is not None else None for r in v)
            except (TypeError, ValueError):
                prev = None
            if prev is not None:
                store[cap_ident] = prev
    return prev


# ----------------------------------------------------------------------
# phase-B row narrowing (dtype/range analysis)
# ----------------------------------------------------------------------
# Integer leaves whose observed [min, max] fits a narrower dtype cross
# the fabric as that dtype: phase A all-reduces per-leaf ranges on
# device (no extra sync — the synced plan step reads them alongside the
# send matrix, and the optimistic path trusts the spec LEARNED from
# past synced runs, guarded by an in-trace range check riding the
# existing deferred overflow flag). Narrow specs, like capacities, only
# ever WIDEN for a site, so steady-state executables are reused.


def _narrowable_leaves(leaves) -> Tuple[int, ...]:
    """Leaf indices eligible for range analysis: integer dtypes wider
    than one byte (floats never narrow — NaN/rounding would break bit
    parity; sub-byte ints have nothing to gain)."""
    return tuple(i for i, l in enumerate(leaves)
                 if np.dtype(l.dtype).kind in "iu"
                 and np.dtype(l.dtype).itemsize >= 2)


def _spec_from_ranges(mex: MeshExec, cap_ident: Tuple, leaves,
                      nidx: Tuple[int, ...],
                      ranges: Optional[np.ndarray]):
    """Sticky (widen-only) narrow spec for this site: merge the fetched
    per-leaf ranges into the remembered union and derive the narrow
    dtype per leaf. Returns a tuple of dtype-str-or-None per LEAF (not
    per narrowable leaf), or None when nothing narrows."""
    if ranges is None or not nidx:
        return None
    prev = _sticky_range_get(mex, cap_ident)
    store = mex._sticky_ranges
    merged = []
    for j, li in enumerate(nidx):
        lo, hi = int(ranges[j, 0]), int(ranges[j, 1])
        dt = np.dtype(leaves[li].dtype)
        if dt.kind == "u" and (lo < 0 or hi < 0):
            # u64 value past int64.max wrapped negative in the range
            # output: unrepresentable — poison the leaf's range so it
            # never narrows
            lo, hi = np.iinfo(np.int64).min, np.iinfo(np.int64).max
        if lo > hi:                       # empty shard: no information
            if prev is not None and prev[j] is not None:
                lo, hi = prev[j]
            else:
                merged.append(None)
                continue
        elif prev is not None and prev[j] is not None:
            lo, hi = min(lo, prev[j][0]), max(hi, prev[j][1])
        merged.append((lo, hi))
    store[cap_ident] = tuple(merged)
    from ..net.wire import narrow_dtype
    spec: list = [None] * len(leaves)
    any_narrow = False
    for j, li in enumerate(nidx):
        if merged[j] is None:
            continue
        nd = narrow_dtype(merged[j][0], merged[j][1],
                          np.dtype(leaves[li].dtype).itemsize)
        if nd is not None:
            spec[li] = nd.str
            any_narrow = True
    return tuple(spec) if any_narrow else None


def _sticky_spec(mex: MeshExec, cap_ident: Tuple, leaves):
    """Narrow spec for an OPTIMISTIC dispatch: derived purely from the
    site's remembered range union (no fetch). The in-trace guard in
    chunk 0 catches data that outgrew the learned ranges and routes
    the exchange to the synced heal, which re-learns them."""
    prev = _sticky_range_get(mex, cap_ident)
    if prev is None:
        return None
    nidx = _narrowable_leaves(leaves)
    from ..net.wire import narrow_dtype
    spec: list = [None] * len(leaves)
    any_narrow = False
    for j, li in enumerate(nidx):
        if j >= len(prev) or prev[j] is None:
            continue
        nd = narrow_dtype(prev[j][0], prev[j][1],
                          np.dtype(leaves[li].dtype).itemsize)
        if nd is not None:
            spec[li] = nd.str
            any_narrow = True
    return tuple(spec) if any_narrow else None


def _pack_degraded(spec):
    """data.exchange.pack injection gate: an armed fire drops the
    narrow spec for THIS exchange (full-width rows — always correct),
    mirroring the degrade-never-wrong discipline of mem.estimate."""
    if spec is None or not faults.REGISTRY.active():
        return spec
    try:
        faults.check(_F_PACK)
    except faults.InjectedFault:
        faults.note("recovery", what="xchg.pack_degrade")
        return None
    return spec


def _narrow_item_bytes(leaves, spec) -> int:
    """Per-item fabric bytes under a narrow spec (None = full width)."""
    total = 0
    for i, l in enumerate(leaves):
        isz = (np.dtype(spec[i]).itemsize
               if spec is not None and spec[i] is not None
               else np.dtype(l.dtype).itemsize)
        total += isz * int(np.prod(l.shape[2:], dtype=np.int64))
    return total


def leaf_ranges_traced(xs, mask):
    """Traced helper (inside shard_map): all-reduced [len(xs), 2] int64
    ``[min, max]`` of each leaf's valid items — the range analysis the
    phase-B narrowing feeds on. Shared by phase A and by the presorted
    classify programs (Sort/Merge phase 2), so every phase-B flavor
    learns from the same math. u64 values past int64.max are clamped
    BEFORE the int64 cast: they saturate at int64.max, which correctly
    reads as "cannot narrow" without poisoning the leaf's sticky range
    when a shard merely happened to be empty."""
    i64max = np.iinfo(np.int64).max
    rows = []
    for x in xs:
        info = jnp.iinfo(x.dtype)
        smax = info.max
        if x.dtype == jnp.uint64:
            x = jnp.minimum(x, jnp.uint64(i64max))
            smax = i64max
        m = mask.reshape((-1,) + (1,) * (x.ndim - 1))
        # gather the W local scalars and reduce them here: the TPU
        # compiler lowers a 64-bit all-reduce only for sums ("Supported
        # lowering only of Sum all reduce" for an s64 pmin)
        lo = jnp.min(lax.all_gather(
            jnp.min(jnp.where(m, x, smax)).astype(jnp.int64), AXIS))
        hi = jnp.max(lax.all_gather(
            jnp.max(jnp.where(m, x, info.min)).astype(jnp.int64), AXIS))
        rows.append(jnp.stack([lo, hi]))
    return jnp.stack(rows)


def presorted_range_leaves(mex: MeshExec, cap: int, leaves) -> Tuple[int, ...]:
    """Narrowable leaf indices when a presorted classify program should
    bolt on the range analysis — the same worth-it policy as phase A
    (volume gate, W > 1, knob on, no capture in flight)."""
    W = mex.num_workers
    if not (W > 1 and xchg_narrow_enabled()
            and mex.loop_recorder is None
            and W * cap * leaf_item_bytes(leaves) >= _NARROW_MIN_BYTES):
        return ()
    return _narrowable_leaves(leaves)


def _ex_cumsum(x):
    return jnp.cumsum(x) - x


def _planner_of(mex):
    """The mesh's adaptive planner (api/planner.py) when live, else
    None — attribute reads only (no api import: the planner object is
    attached by the Context, exactly like the decision ledger)."""
    pl = getattr(mex, "planner", None)
    if pl is not None and pl.enabled:
        return pl
    return None


def resolve_mode(mex: MeshExec) -> str:
    """Exchange mode precedence: env THRILL_TPU_EXCHANGE, then the
    mesh's configured mode, then dense. Single source of truth for
    every caller that gates on the exchange plan (the Sort fused path
    must agree with the plan the generic exchange would pick).

    The env override is captured ONCE at mesh construction
    (``MeshExec._env_exchange``) — this used to be an ``os.environ``
    read on every plan step of every exchange. Set the variable before
    building the mesh; mid-process toggles still work through
    ``mex.exchange_mode`` (which Context sets from Config)."""
    if hasattr(mex, "_env_exchange"):
        env = mex._env_exchange
    else:                                  # bare stubs in tests
        env = os.environ.get("THRILL_TPU_EXCHANGE")
    return env or getattr(mex, "exchange_mode", "dense")


@jax.named_scope("send_slice")
def send_slice(x, starts, counts, M: int):
    """Traced helper (call it under ``SCOPE``): the send blocks of one
    leaf, cut out of its dest-grouped rows. ``x`` is [cap, ...];
    block ``b`` of the returned [B, M, ...] holds rows ``starts[b] ..
    starts[b] + M - 1`` of ``x`` with rows ``j >= counts[b]`` zeroed.
    Nothing is scattered: a block is a copy of a row range.

    The zero rows are part of the contract (the fused sort's merge
    reads the key words of a received block's unused rows as zeros).
    ``starts[b] + M`` may pass ``cap`` — ``M`` is sticky and up to
    twice the mean block — and ``lax.dynamic_slice`` would clamp such
    a start, shifting the data: the slices read through ``M`` zero
    rows kept behind ``x``, which no start with ``counts[b] > 0`` can
    pass (such a block ends inside ``x``). A block longer than ``M``
    gives its first ``M`` rows; the caller's overflow flag decides."""
    trail = x.shape[1:]
    xp = jnp.concatenate([x, jnp.zeros((M,) + trail, x.dtype)])
    j = jnp.arange(M).reshape((M,) + (1,) * len(trail))
    zero = jnp.zeros((), x.dtype)
    return jnp.stack([
        jnp.where(j < counts[b],
                  lax.dynamic_slice_in_dim(xp, starts[b], M, axis=0),
                  zero)
        for b in range(starts.shape[0])])


@jax.named_scope(SCOPE)
def ship_blocks(x, off, n_send, W: int, M: int):
    """Traced helper: cut one leaf into [W, M] padded per-destination
    blocks (:func:`send_slice`: destination ``d``'s block is rows
    ``off[d] .. off[d] + n_send[d] - 1`` of ``x``, zeros behind) and
    all_to_all them; returns the received [W*M, ...] rank-ordered runs
    (run w = source w's items). The send side scatters nothing: ``x``
    must hold each destination's rows contiguously, in order, at
    ``off[d]`` (the contract of :func:`exchange_presorted`)."""
    blocks = send_slice(x, off, n_send, M)
    recv = lax.all_to_all(blocks, AXIS, split_axis=0,
                          concat_axis=0, tiled=True)
    return recv.reshape((W * M,) + x.shape[1:])


def send_counts(dest: jnp.ndarray, W: int) -> jnp.ndarray:
    """Traced helper (inside shard_map): per-destination send histogram,
    all-gathered into the replicated [W, W] matrix every worker needs
    for the host planning step. ``dest`` uses W for invalid items.
    Counted in ``overall_stats()["send_hists_by_compare"]`` per dispatch
    where it counts by comparison."""
    from ..core.pallas_kernels import histogram_path, partition_histogram
    if histogram_path(dest.shape[0], W) == "compare":
        note("send_hists_by_compare")
    send = partition_histogram(dest, W)
    return lax.all_gather(send, AXIS)


def exchange_presorted(mex: MeshExec, treedef, sorted_dest, sorted_leaves,
                       S: np.ndarray, min_cap: int = 1,
                       ident: Tuple = (),
                       ranges: Optional[np.ndarray] = None
                       ) -> DeviceShards:
    """Ship items that are ALREADY grouped by destination.

    Public entry for operators whose upstream order makes destinations
    monotone (Sort: items are key-sorted, so splitter rank never
    decreases) — they skip the generic phase-A destination sort
    entirely. Contract, exactly: with ``off = exclusive cumsum of
    S[w]``, worker w's valid items bound for destination d are rows
    ``off[d] .. off[d] + S[w, d] - 1`` of its leaves, in the order they
    are to arrive; every invalid slot lies behind them (rows
    ``sum(S[w])`` and up). ``sorted_dest`` is [W, cap] int32, monotone
    over the valid rows, with W marking invalid slots;
    ``sorted_leaves`` are [W, cap, ...] in that same order; ``S[w, d]``
    counts w's items bound for d (as produced by ``send_counts``). The
    send side of every plan (dense chunks, 1-factor rounds, ragged)
    cuts destination d's block out of the leaves as that row range and
    scatters nothing, so a valid row outside its range is lost, not
    repaired. ``ranges`` ([L, 2]
    int64 over the narrowable leaves, see
    :func:`presorted_range_leaves`) opts the call into phase-B row
    narrowing — presorted callers compute it inside their own phase-A
    program, where the data is already resident.
    """
    return _exchange_planned(mex, treedef, sorted_dest, sorted_leaves, S,
                             min_cap=min_cap, ident=ident, ranges=ranges)


def _phase_a(shards: DeviceShards, dest_builder: Callable,
             cache_key: Tuple, want_ranges: bool = True,
             span_fields: Optional[dict] = None):
    """Phase A: destination, local dest-sort, send counts. Returns
    (treedef, sorted_dest, sorted_leaves, send_mat, range_mat) with the
    [W, W] send matrix REPLICATED ON DEVICE — whether the planner syncs
    it to the host (classic path) or dispatches phase B straight off it
    (optimistic capacity-cache path) is the caller's decision.

    ``range_mat`` ([L, 2] int64, replicated; None when no leaf is
    narrowable or narrowing is off) carries the all-reduced [min, max]
    of every integer leaf's valid items — the dtype/range analysis the
    phase-B row narrowing feeds on. Computing it here costs two
    reductions per leaf inside a program that already sorts the shard;
    whether anything READS it (the synced plan step, or an optimistic
    miss heal) is again the caller's decision. A caller whose phase B
    never narrows passes ``want_ranges=False`` and skips the analysis
    entirely. ``span_fields`` go on the ``phase_a`` span as they are."""
    mex = shards.mesh_exec
    # an upstream optimistic exchange may still owe its overflow check:
    # heal it before this program bakes the (possibly truncated)
    # columns into a new shuffle
    shards.validate_pending()
    W = mex.num_workers
    cap = shards.cap
    leaves, treedef = jax.tree.flatten(shards.tree)
    # Narrowing pays on VOLUME: W=1 exchanges move nothing, and a
    # kilobyte shuffle saves less than the range analysis adds to its
    # phase-A compile — the same worth-it policy as phase-B chunking.
    # The gate is deterministic across processes (cap/W/dtypes are
    # globally agreed shapes).
    narrow_worth = (want_ranges and W > 1 and xchg_narrow_enabled()
                    and W * cap * leaf_item_bytes(leaves)
                    >= _NARROW_MIN_BYTES)
    nidx = _narrowable_leaves(leaves) if narrow_worth else ()
    key_a = ("xchg_a", cache_key, cap, treedef, nidx,
             tuple((l.dtype, l.shape[2:]) for l in leaves))

    def build_a():
        def fa(counts_dev, *ls):
            count = counts_dev[0, 0]
            mask = jnp.arange(cap) < count
            tree = jax.tree.unflatten(treedef, [l[0] for l in ls])
            widx = lax.axis_index(AXIS)
            dest = dest_builder(tree, mask, widx).astype(jnp.int32)
            dest = jnp.where(mask, jnp.clip(dest, 0, W - 1), W)
            from ..core.device_sort import sort_words
            from ..core.rowmove import take_rows_multi
            # the sort of the destinations and the gather behind it
            with jax.named_scope(SCOPE), jax.named_scope("dest_sort"):
                (sorted_dest,), perm = sort_words(
                    [dest.astype(jnp.uint64)])
                sorted_dest = sorted_dest.astype(jnp.int32)
                sorted_ls = take_rows_multi([l[0] for l in ls], perm)
            # replicate the [W, W] send-count matrix: every process can
            # then fetch it locally (multi-controller safe host step)
            all_send = send_counts(sorted_dest, W)
            outs = (sorted_dest[None], all_send,
                    *[sl[None] for sl in sorted_ls])
            if nidx:
                outs = outs + (leaf_ranges_traced(
                    [ls[li][0] for li in nidx], mask),)
            return outs

        from jax.sharding import PartitionSpec as P
        out_specs = (P(AXIS), P()) + (P(AXIS),) * len(leaves)
        if nidx:
            out_specs = out_specs + (P(),)
        return mex.smap(fa, 1 + len(leaves), out_specs=out_specs)

    fa = mex.cached(key_a, build_a)
    with _trace.span_of(getattr(mex, "tracer", None), "exchange",
                        "phase_a", rows=W * cap, **(span_fields or {})):
        out_a = fa(shards.counts_device(), *leaves)
    sorted_dest, send_mat = out_a[0], out_a[1]
    if nidx:
        sorted_leaves = list(out_a[2:-1])
        range_mat = out_a[-1]
    else:
        sorted_leaves = list(out_a[2:])
        range_mat = None
    return treedef, sorted_dest, sorted_leaves, send_mat, range_mat


def exchange(shards: DeviceShards, dest_builder: Callable, cache_key: Tuple,
             min_cap: int = 1, span_fields: Optional[dict] = None
             ) -> DeviceShards:
    """Move every valid item to the worker computed by ``dest_builder``.

    ``dest_builder(tree, valid_mask, worker_index) -> int32 [cap]`` is
    traced inside the phase-A program; ``cache_key`` must identify it
    (plus its static parameters) for executable caching.

    Steady state pays NO mid-shuffle host sync: once this call site's
    padded capacities are cached (the first, synced run), phase B
    dispatches optimistically on the cached plan with the send matrix
    staying device-resident; a capacity miss is detected by a deferred
    device flag and healed by re-running the synced plan from the
    retained phase-A output (lineage-level, never wrong data).
    ``span_fields`` go on phase A's ``exchange`` span.
    """
    mex = shards.mesh_exec
    # a loop capture is recording: leaf ranges are VALUES of loop data
    # (carry-dependent), so reading them would taint the tape —
    # captured exchanges ship full-width rows, and the capture-time
    # phase A skips the analysis entirely so the replayed tape carries
    # no dead per-iteration range reductions
    treedef, sorted_dest, sorted_leaves, send_mat, range_mat = _phase_a(
        shards, dest_builder, cache_key,
        want_ranges=mex.loop_recorder is None, span_fields=span_fields)
    if mex.num_workers > 1:
        cap = sorted_leaves[0].shape[1] if sorted_leaves else 0
        cap_ident = _dense_cap_ident(cache_key, cap, treedef,
                                     sorted_leaves)
        caps = _optimistic_ok(mex, cap_ident, min_cap, ident=cache_key,
                              counts=shards._counts_host)
        if caps is not None:
            return _exchange_optimistic(
                mex, treedef, sorted_dest, sorted_leaves, send_mat,
                caps, ident=cache_key, min_cap=min_cap,
                range_mat=range_mat)
    # the exchange barrier: the host plan sync blocks until phase A's
    # send matrix lands — wait attribution (common/doctor.py) charges
    # the blocked window to the "exchange" lane
    doc = getattr(mex, "doctor", None)
    t0 = time.perf_counter() if doc is not None else 0.0
    S = mex.fetch(send_mat)                       # [W, W] S[w, d]
    if doc is not None:
        doc.record_wait("xchg.plan_sync", None,
                        time.perf_counter() - t0, lane="exchange")
    # the tiny [L, 2] range matrix rides the SAME host-sync window as
    # the send matrix (raw transfer: one logical plan sync, not a
    # second counted mid-pipeline fetch)
    ranges = None if range_mat is None else mex._fetch_raw(range_mat)
    return _exchange_planned(mex, treedef, sorted_dest, sorted_leaves, S,
                             min_cap=min_cap, ident=cache_key,
                             smat_dev=send_mat, ranges=ranges)


def _sticky_caps(mex: MeshExec, ident: Tuple, needed: Tuple[int, ...]
                 ) -> Tuple[int, ...]:
    """Monotone capacity agreement per program identity.

    Loops (PageRank etc.) re-plan every iteration; if capacities chased
    the data exactly, every wiggle past a power of two would recompile.
    Capacities only ever GROW for a given program identity, so once a
    loop reaches steady state its executables are reused verbatim.
    """
    cache = getattr(mex, "_sticky_caps", None)
    if cache is None:
        cache = mex._sticky_caps = {}
    prev = cache.get(ident)
    if prev is None:
        # a plan-store seed (service/plan_store.py) pre-ratchets the
        # site to its remembered steady-state capacities — monotone
        # merge below, exactly as if this process had learned them
        prev = _seeded_caps(mex, ident)
    grown = tuple(round_up_pow2(n) for n in needed)
    if prev is not None and len(prev) == len(grown):
        grown = tuple(max(p, g) for p, g in zip(prev, grown))
    cache[ident] = grown
    return grown


def dense_all_to_all_applies(mex: MeshExec, S: np.ndarray,
                             row_bytes: int = 8) -> bool:
    """Would the planner use the single dense all_to_all for this send
    matrix? Shared predicate so fused callers (Sort's run-merge path)
    take the fused program exactly when the generic exchange would have
    taken the dense plan."""
    return resolve_mode(mex) == "dense" and not _skewed(S, row_bytes,
                                                        mex)


def account_traffic(mex: MeshExec, S: np.ndarray, item_bytes: int,
                    site: str = "", **log_extra: Any) -> None:
    """Traffic accounting shared by every exchange plan (reference:
    net::Manager tx/rx counters feeding the end-of-job OverallStats
    AllReduce, api/context.cpp:1275-1341). On multi-slice meshes the
    bytes are split by tier: same-slice pairs ride ICI, cross-slice
    pairs DCN. Called exactly once per LOGICAL exchange — the
    optimistic path calls it at deferred-check time (hit), or lets the
    healed synced re-run account instead (miss).

    Partition-skew attribution (common/doctor.py) rides the same
    choke point: the per-worker receive totals of THIS send matrix
    feed the site's hot-slot detector, the ``skew_ratio`` lane fields
    of the exchange log line, and the ``kind=skew`` plan-lane
    instants ``ctx.explain()`` renders."""
    rows, local = int(S.sum()), int(np.trace(S))
    moved = rows - local                          # off-diagonal items
    mex.stats_exchanges += 1
    mex.stats_xchg_rows_in += rows
    mex.stats_xchg_rows_local += local
    mex.stats_items_moved += moved
    mex.stats_bytes_moved += moved * item_bytes
    sid = mex.slice_id
    if mex.num_slices > 1:
        cross = sid[:, None] != sid[None, :]
        dcn_items = int(S[cross].sum())
        mex.stats_bytes_dcn += dcn_items * item_bytes
        mex.stats_bytes_ici += (moved - dcn_items) * item_bytes
    else:
        dcn_items = 0
        mex.stats_bytes_ici += moved * item_bytes
    skew_ratio = hot_worker = hot_rows = None
    doc = getattr(mex, "doctor", None)
    if doc is not None and S.shape[0] > 1:
        # total receive rows per worker INCLUDING the diagonal: the
        # hot slot is whoever holds the most rows after the shuffle,
        # local items included — that worker's downstream compute is
        # the one the partition function overloaded
        skew = doc.record_exchange(
            site or "xchg:?", S.sum(axis=0), item_bytes,
            tracer=getattr(mex, "tracer", None),
            ledger=_decisions.ledger_of(mex))
        if skew is not None:
            ratio, hot_worker, hot_rows = skew
            skew_ratio = round(ratio, 3)
    log = getattr(mex, "logger", None)
    if log is not None and log.enabled:
        sent = (S.sum(axis=1) - np.diag(S)).astype(int)
        recv = (S.sum(axis=0) - np.diag(S)).astype(int)
        skew_extra = {}
        if site:
            skew_extra["site"] = site
        if skew_ratio is not None:
            # hot_rows is the hot worker's DIAGONAL-INCLUDED receive
            # total — the figure the ratio was computed from
            # (per_worker_recv below is off-diagonal by its own
            # long-standing contract); the offline doctor_report
            # reads it so both reports state the same rows
            skew_extra["skew_ratio"] = skew_ratio
            skew_extra["hot_worker"] = hot_worker
            skew_extra["hot_rows"] = hot_rows
        log.line(event="exchange", items=moved,
                 bytes=moved * item_bytes,
                 bytes_dcn=dcn_items * item_bytes,
                 per_worker_sent=sent.tolist(),
                 per_worker_recv=recv.tolist(),
                 **skew_extra, **log_extra)


def one_factor_rounds(mex: MeshExec) -> List[np.ndarray]:
    """Round schedule for the pairwise exchange: a list of partner
    permutations partner[w] covering every ordered pair exactly once
    (the identity round is excluded — the caller scatters locally).

    Single slice: the classic rotation partner = (w + r) % W
    (reference: 1-factor scheduling, thrill/net/group.hpp:90-107).
    Multi-slice (workers blocked by slice, equal block size B): rounds
    are decomposed over (slice shift ds, chip shift dc) so every round
    is TIER-PURE — either all pairs same-slice (ICI) or all cross-slice
    (DCN). Tier-pure rounds pad only to their own tier's maximum (a
    mixed round pays the global max even when DCN traffic is light),
    and the DCN rounds are grouped last so the latency-bound tail rides
    the wide-ICI rounds first.
    """
    W = mex.num_workers
    sid = mex.slice_id
    nS = mex.num_slices
    blocked = (nS > 1 and W % nS == 0 and
               np.array_equal(sid, np.repeat(np.arange(nS), W // nS)))
    if not blocked:
        return [np.array([(w + r) % W for w in range(W)])
                for r in range(1, W)]
    B = W // nS
    s, c = np.arange(W) // B, np.arange(W) % B
    rounds = []
    for dc in range(1, B):                         # intra-slice (ICI)
        rounds.append(s * B + (c + dc) % B)
    for ds in range(1, nS):                        # cross-slice (DCN)
        for dc in range(B):
            rounds.append(((s + ds) % nS) * B + (c + dc) % B)
    return rounds


def leaf_item_bytes(leaves) -> int:
    """Per-item byte width across [W, cap, ...] leaves."""
    return sum(int(np.dtype(l.dtype).itemsize) *
               int(np.prod(l.shape[2:], dtype=np.int64))
               for l in leaves)


# Break-even padded-byte volume per extra program launch: the dense
# all_to_all is ONE launch padded to the global cell maximum; the
# 1-factor schedule is (W-1) serialized launches padded per round.
# 1-factor wins iff the padding it saves outweighs its extra launches:
#
#   saved_padded_bytes > extra_launches * BYTES_EQ
#
# where BYTES_EQ = round_overhead * exchange_bandwidth, both measured
# on the actual mesh by a sweep of payload sizes through both plans:
#   * virtual 8-device CPU mesh (this image, 2026-07-30, plan pinned
#     during calibration): round_overhead 119 us, dense bw 378 MB/s
#     -> BYTES_EQ ~45 KiB
#   * "tpu": NOT MEASURED. The value is the guess earlier rounds fell
#     through to (~10-30 us launch overhead at multi-GB/s effective
#     -> O(1 MiB)); a four-chip cell that takes both plans would
#     measure it (ROADMAP S10).
# A platform with no entry raises: a device nobody priced is an error,
# not a default. Override with THRILL_TPU_XCHG_BYTES_EQ.
_BYTES_EQ_MEASURED = {"cpu": 45_000, "tpu": 1 << 20}
# Exchange bandwidth (bytes/s) for the LIVE calibration below — the
# other factor of BYTES_EQ. The launch-overhead factor is measured on
# this very mesh (the dispatch-latency spine); bandwidth stays a
# baked platform constant because measuring it needs a sized payload
# sweep, not a passive observer.
_BYTES_EQ_BANDWIDTH = {"cpu": 378e6,
                       "tpu": 4e9}      # not measured (see above)
_BYTES_EQ_MIN_SAMPLES = 256


def _bytes_eq(mex: MeshExec) -> int:
    import os
    env = os.environ.get("THRILL_TPU_XCHG_BYTES_EQ")
    if env:
        try:
            return int(env)
        except ValueError:
            pass
    platform = mex.devices[0].platform if mex.devices else "cpu"
    static = _BYTES_EQ_MEASURED[platform]
    # Live calibration: the dispatch-latency spine's running minimum
    # (parallel/mesh.py) is this mesh's pure launch overhead — compile
    # calls and data-bound dispatches are strictly slower, so the min
    # converges on it from above. BYTES_EQ = overhead * bandwidth, so a
    # machine 4x slower than the constants were measured on flips the
    # dense/1-factor choice where its hardware actually crosses over.
    # Clamped to [static/4, static*4] (the min is an estimate, not a
    # license to leave the measured regime) and gated on a sample count
    # so fresh meshes — including every plan-choice test — keep the
    # deterministic static constant. THRILL_TPU_XCHG_BYTES_EQ_CAL=0
    # pins the static value regardless of history.
    if (os.environ.get("THRILL_TPU_XCHG_BYTES_EQ_CAL", "1") != "0"
            and getattr(mex, "_disp_lat_n", 0) >= _BYTES_EQ_MIN_SAMPLES):
        bw = _BYTES_EQ_BANDWIDTH[platform]
        cal = int(mex._disp_lat_min * bw)
        cal = max(static // 4, min(cal, static * 4))
        led = _decisions.ledger_of(mex)
        if led is not None and led.enabled \
                and not getattr(mex, "_bytes_eq_logged", False):
            # once per mesh: the drift of the live measurement vs the
            # baked constant, audited immediately (actual = static)
            mex._bytes_eq_logged = True
            rec = led.record(
                "bytes_eq", "xchg:bytes_eq", "calibrated",
                predicted=cal, rejected=[("static", static)],
                reason="launch-min %.0fus x %s bw"
                       % (mex._disp_lat_min * 1e6, platform),
                samples=int(mex._disp_lat_n))
            led.resolve(rec, static)
        return cal
    return static


def _strategy_costs(mex: MeshExec, S: np.ndarray,
                    row_bytes: int) -> Tuple[int, int, int]:
    """(dense_bytes, onefactor_bytes, n_rounds): the estimated padded
    fabric volume of each candidate plan for this send matrix — the
    inputs of the dense-vs-1-factor choice, shared by :func:`_skewed`
    and the decision ledger's ``xchg_strategy`` record.

    Rows entering the fabric: dense ships W slots of the global max per
    worker; 1-factor ships each round's pair maximum. Fabric rows
    exclude self-traffic on BOTH sides: the dense plan's diagonal slot
    and the 1-factor identity round are local scatters."""
    W = S.shape[0]
    M_dense = int(S.max())
    rounds = one_factor_rounds(mex)
    M_rounds = [max(int(S[np.arange(W), to].max()), 1) for to in rounds]
    rb = max(row_bytes, 1)
    return (W * (W - 1) * M_dense * rb, W * sum(M_rounds) * rb,
            len(rounds))


def _skewed(S: np.ndarray, row_bytes: int, mex: MeshExec) -> bool:
    """Does the measured cost model prefer the 1-factor schedule over
    the single dense all_to_all for this send matrix?

    A sparse-but-balanced matrix (e.g. a neighbor shift) saves nothing
    and stays on the single all_to_all; a 100:1 hot-key skew saves
    ~W x the padding and flips as soon as the savings clear the
    per-round launch overhead."""
    dense_b, of_b, n_rounds = _strategy_costs(mex, S, row_bytes)
    return dense_b - of_b > n_rounds * _bytes_eq(mex)


def _dense_cap_ident(ident: Tuple, cap: int, treedef, sorted_leaves
                     ) -> Tuple:
    """Sticky-capacity / capacity-plan-cache key for the dense plan:
    per CALL SITE (ident), not per shape — two unrelated same-shaped
    exchanges must not ratchet each other's capacities."""
    return ("xchg_caps", ident, cap, treedef,
            tuple((l.dtype, l.shape[2:]) for l in sorted_leaves))


def _chunk_count(mex: MeshExec, W: int, M_pad: int,
                 item_bytes: int) -> int:
    """How many row-range chunks phase B splits into.

    ``THRILL_TPU_OVERLAP=0`` forces the single bulk dispatch;
    ``THRILL_TPU_XCHG_CHUNKS=K`` pins K; the auto policy chunks only
    exchanges whose padded volume is worth pipelining (chunking a
    kilobyte shuffle pays K-1 extra dispatches for nothing — and every
    chunk shape is its own compiled program). With the adaptive
    planner attached the choice is the planner's; the policy itself is
    :func:`chunk_policy` either way (ONE implementation — the
    planner-on and planner-off paths cannot drift)."""
    pl = _planner_of(mex)
    if pl is not None:
        return pl.chunk_count(W, M_pad, item_bytes)
    return chunk_policy(W, M_pad, item_bytes)


def chunk_policy(W: int, M_pad: int, item_bytes: int) -> int:
    """The phase-B chunking policy: overlap kill switch, env pin, then
    the measured break-even auto rule. Shared verbatim by the legacy
    per-site branch and the adaptive planner (api/planner.py)."""
    if not overlap_enabled():
        return 1
    env = os.environ.get("THRILL_TPU_XCHG_CHUNKS")
    if env:
        try:
            return max(1, min(int(env), M_pad))
        except ValueError:
            pass
    if W * M_pad * item_bytes < _CHUNK_MIN_BYTES:
        return 1
    return max(1, min(_CHUNK_DEFAULT, M_pad))


_CHUNK_DEFAULT = 4
_CHUNK_MIN_BYTES = 1 << 20
# minimum padded exchange volume (W * cap * item bytes) for phase-A
# range analysis + phase-B narrowing: below this the compile-time cost
# of the analysis exceeds what thinner rows could ever save
_NARROW_MIN_BYTES = 1 << 15
# every Nth use of a cached capacity plan takes the synced path anyway,
# so a site whose data turned skewed after warmup regains the 1-factor
# plan within N exchanges instead of never (perf-only: the overflow
# flag already guards correctness)
_CAP_RESYNC_EVERY = 32


def _optimistic_ok(mex: MeshExec, cap_ident: Tuple, min_cap: int,
                   ident: Tuple = (),
                   counts=None) -> Optional[Tuple[int, int]]:
    """Cached (M_pad, out_cap) when this site may dispatch phase B
    WITHOUT the host sync, else None.

    Requirements: the overlap/cap-cache knobs are on, the site's last
    synced plan was the dense one (a skew-flipped or ragged site needs
    the host S every time), a capacity plan is cached, no loop capture
    is recording (captures keep today's synced semantics so tapes bake
    the same plan they always did), and single-controller (a deferred
    per-process heal would desynchronize the collective schedule —
    same reasoning as the memory ladder's multi-process guard).

    With the adaptive planner attached (api/planner.py), the cached
    plan additionally survives the planner's verdict: a site marked
    for re-optimization (an audit or deferred check caught the learned
    state lying), or host-known input ``counts`` proving the cached
    capacities CANNOT hold (a guaranteed miss), re-chooses the synced
    plan instead — the stale sticky state is dropped so the re-plan
    ratchets from the current data, exactly the plan a cold run would
    build."""
    if not cap_cache_enabled():
        return None
    if mex.loop_recorder is not None:
        return None
    if getattr(mex, "num_processes", 1) > 1 \
            and not getattr(mex, "_plan_seed_symmetric", True):
        # per-process optimism on a multi-controller mesh is safe only
        # when every rank provably holds the SAME plan state. That is
        # the DEFAULT: in-process-learned state derives from the
        # replicated send matrix under the lockstep submission
        # contract, so a storeless steady-state service overlaps its
        # exchanges too (planner edge (a), ISSUE 18). The deferred
        # heal is then lockstep: the overflow flag is a function of
        # the replicated send matrix alone (narrow-range verdicts are
        # pmax'd), and checks drain at the same program points on
        # every controller. The flag only goes FALSE when seeds of
        # unknown provenance were installed (a per-rank store read —
        # install_plan_seeds without the symmetric attestation); the
        # rank-0 broadcast path re-attests it True. Without the
        # guarantee, keep the synced plan every time.
        return None
    if resolve_mode(mex) != "dense":
        return None
    plans = getattr(mex, "_xchg_plan", None)
    if plans is None:
        plans = mex._xchg_plan = {}
    kind = plans.get(cap_ident)
    if kind is None:
        # warm restart: the plan store remembers this site's last
        # synced verdict — a "dense" seed (with seeded capacities
        # below) lets the FIRST exchange of a fresh process dispatch
        # optimistically, zero host plan syncs
        kind = plan_seed(mex, "plan", cap_ident)
        if kind is not None:
            kind = plans[cap_ident] = str(kind)
    if kind != "dense":
        return None
    cache = getattr(mex, "_sticky_caps", None)
    if cache is None:
        cache = mex._sticky_caps = {}
    seeded = False
    caps = cache.get(cap_ident)
    if caps is None:
        caps = _seeded_caps(mex, cap_ident)
        if caps is not None:
            cache[cap_ident] = caps
            seeded = True
    if not caps or len(caps) != 2 or caps[1] < min_cap:
        return None
    pl = _planner_of(mex)
    if pl is not None:
        site = "xchg:" + _ident_digest(ident)[:10]
        if seeded:
            pl.note_seeded(site)
        ok, why = pl.optimistic_verdict(site, caps, counts,
                                        mex.num_workers)
        if not ok:
            # re-optimization: invalidate the learned state the lie
            # lives in so the forced synced plan re-ratchets from the
            # current data, and put the switched decision (with both
            # plans' costs) where explain() shows it
            cache.pop(cap_ident, None)
            getattr(mex, "_sticky_ranges", {}).pop(cap_ident, None)
            pl.note_switch()
            need = None
            if counts is not None:
                need = -(-int(np.asarray(counts).sum())
                         // max(mex.num_workers, 1))
            pl.record_replan(
                _decisions.ledger_of(mex), site, "synced",
                predicted=need,
                rejected=[("optimistic", float(caps[1]))],
                reason=why, unit="rows")
            faults.note("recovery", what="planner.replan",
                        site=site, why=why[:120], _quiet=True)
            return None
    # periodic re-plan: the dense-vs-1-factor skew decision needs the
    # host S, which steady-state hits elide — without this, skew that
    # develops AFTER warmup (and stays inside the monotone caps) would
    # keep the padded dense plan forever. Every Nth use of a site runs
    # the synced path, re-evaluating skew and re-recording the plan.
    hits = getattr(mex, "_xchg_plan_uses", None)
    if hits is None:
        hits = mex._xchg_plan_uses = {}
    n = hits.get(cap_ident, 0) + 1
    hits[cap_ident] = n
    if n % _CAP_RESYNC_EVERY == 0:
        return None
    return caps


def _dispatch_chunked(mex: MeshExec, treedef, sorted_dest, sorted_leaves,
                      smat, M_pad: int, out_cap: int, narrow=None,
                      ident: Tuple = ()):
    """The dense phase-B program(s): K row-range chunk dispatches over
    a shared output accumulator, all plan values derived IN-TRACE from
    the replicated [W, W] send matrix ``smat``.

    Chunk j ships destination-slot range [lo_j, hi_j) of every (src,
    dst) pair: the all_to_all blocks are [W, hi_j-lo_j] and the receive
    scatter lands rows at ``roff[src] + slot`` — exactly the bulk
    program's positions, so any K (including 1, the bulk form) is
    bit-identical. Each chunk is its own ``_CountedJit`` dispatch, so
    admission control, the OOM-retry ladder and dispatch stats cover
    every chunk, and jax async dispatch pipelines chunk i's collective
    with chunk i+1's block cutting. The FIRST chunk additionally returns the
    device-resident output counts and the capacity-overflow flag (both
    functions of ``smat`` alone), so the optimistic path's deferred
    check blocks only until chunk 0 lands — chunks 1..K-1 and the
    consumer's next program keep overlapping.

    ``narrow`` (per-leaf dtype-str or None) ships eligible integer
    leaves across the fabric as their narrowed dtype — the cast is
    exact for in-range values, so results stay bit-identical; the
    scatter accumulator holds the narrow form and widens once, at the
    last chunk. Chunk 0's overflow flag then ALSO checks in-trace that
    every valid value fits its narrow dtype: synced plans derive the
    spec from the current data (the check can only pass), optimistic
    dispatches run on the LEARNED spec and data that outgrew it routes
    to the synced heal instead of truncating. One program serves both
    paths — a separate guarded twin would double every site's phase-B
    compiles for a check that costs two reductions.

    Returns (out_leaves, counts_dev [W, 1] int32, flag [1] int32).
    """
    W = mex.num_workers
    cap = sorted_leaves[0].shape[1] if sorted_leaves else \
        sorted_dest.shape[1]
    leafsig = tuple((l.dtype, l.shape[2:]) for l in sorted_leaves)
    n_leaves = len(sorted_leaves)
    item_bytes = leaf_item_bytes(sorted_leaves)
    K = _chunk_count(mex, W, M_pad, item_bytes)
    led = _decisions.ledger_of(mex)
    if led is not None:
        site = "xchg:" + _ident_digest(ident)[:10]
        vol = W * M_pad * item_bytes
        # mirror _chunk_count's precedence exactly: the overlap kill
        # switch wins over the env pin, and an unparseable pin falls
        # through to the auto policy — the recorded reason must match
        # the path actually taken
        env_k = os.environ.get("THRILL_TPU_XCHG_CHUNKS")
        try:
            int(env_k)          # any parseable pin governs (clamped)
            pinned = True
        except (TypeError, ValueError):
            pinned = False
        led.record(
            "xchg_chunks", site, str(K), predicted=vol,
            reason=("bulk: overlap off" if not overlap_enabled()
                    else "forced" if pinned
                    else "bulk: volume below pipelining break-even"
                    if K == 1 else "chunked: volume worth pipelining"))
        if narrow is not None:
            wide_b = W * (W - 1) * M_pad * item_bytes
            led.record(
                "xchg_narrow", site, "narrow",
                predicted=W * (W - 1) * M_pad
                * _narrow_item_bytes(sorted_leaves, narrow),
                rejected=[("wide", wide_b)],
                reason="learned integer ranges fit narrower dtypes",
                leaves=sum(1 for s in narrow if s is not None))
    bounds = dense_range_bounds(M_pad, K)
    ranges = [(int(bounds[j]), int(bounds[j + 1])) for j in range(K)
              if bounds[j + 1] > bounds[j]]
    from jax.sharding import PartitionSpec as P

    def chunk_program(lo: int, hi: int, first: bool, last: bool):
        M_j = hi - lo
        key = ("xchg_chunk", cap, M_pad, out_cap, lo, hi, first, last,
               W, treedef, leafsig, narrow)

        def build():
            def f(sdest, smat_a, *ls):
                from ..core import rowmove
                widx = lax.axis_index(AXIS)
                S_row = jnp.take(smat_a, widx, axis=0).astype(jnp.int32)
                S_col = jnp.take(smat_a, widx, axis=1).astype(jnp.int32)
                roff = _ex_cumsum(S_col)
                # the window lo:hi of a destination's block starts at
                # row off[dest] + lo of the dest-sorted leaf
                start = _ex_cumsum(S_row) + lo
                n_send = jnp.clip(S_row - lo, 0, M_j)
                jj = jnp.arange(M_j)
                n_from = jnp.clip(S_col - lo, 0, M_j)
                pos = jnp.where(jj[None, :] < n_from[:, None],
                                roff[:, None] + lo + jj[None, :],
                                out_cap)
                # clamp: under a capacity overflow positions can pass
                # the dump row — those rows are garbage either way and
                # the flag below routes the whole exchange to the
                # synced re-run
                pos = jnp.minimum(pos.reshape(-1), out_cap)
                pack = rowmove.enabled()
                srcs, accs = ls[:n_leaves], ls[n_leaves:]
                outs = []
                range_bad = jnp.zeros((), jnp.int32)
                for li, l in enumerate(srcs):
                    xw = l[0]
                    nd = narrow[li] if narrow is not None else None
                    if nd is not None:
                        if first:
                            info = np.iinfo(np.dtype(nd))
                            v = sdest[0] < W
                            vm = v.reshape((-1,) + (1,)
                                           * (xw.ndim - 1))
                            oob = vm & ((xw < info.min)
                                        | (xw > info.max))
                            range_bad = jnp.maximum(
                                range_bad,
                                jnp.max(oob.astype(jnp.int32)))
                        xw = xw.astype(np.dtype(nd))
                    x, m = rowmove.pack_rows(xw) if pack \
                        else (xw, None)
                    recv = ship_blocks(x, start, n_send, W, M_j)
                    if first:
                        acc = jnp.zeros((out_cap + 1,) + x.shape[1:],
                                        x.dtype)
                    else:
                        acc = accs[li][0]
                    acc = acc.at[pos].set(recv)
                    if last:
                        wide = rowmove.unpack_rows(acc[:out_cap], m)
                        if nd is not None:
                            wide = wide.astype(l.dtype)
                        outs.append(wide[None])
                    else:
                        outs.append(acc[None])
                if not first:
                    return tuple(outs)
                cnt = jnp.sum(S_col).astype(jnp.int32)[None, None]
                ovf = jnp.logical_or(
                    smat_a.max() > M_pad,
                    smat_a.sum(axis=0).max() > out_cap)
                ovf = ovf.astype(jnp.int32)
                if narrow is not None:
                    # values past the narrow ranges spoil the cast on
                    # SOME worker: replicate the verdict so the
                    # deferred check sees it wherever it drains
                    ovf = jnp.maximum(ovf,
                                      lax.pmax(range_bad, AXIS))
                return (cnt, ovf.reshape(1), *outs)

            na = 2 + n_leaves + (0 if first else n_leaves)
            in_specs = (P(AXIS), P()) + (P(AXIS),) * (na - 2)
            out_specs = ((P(AXIS), P()) if first else ()) \
                + (P(AXIS),) * n_leaves
            return mex.smap(f, na, out_specs=out_specs,
                            in_specs=in_specs)

        return mex.cached(key, build)

    armed = faults.REGISTRY.active()
    # chunk i's accumulator is consumed exactly once by chunk i+1:
    # donate it so XLA aliases instead of copying the [W, out_cap]
    # buffers K-1 times. CPU has no input-output aliasing (and the OOM
    # ladder's donation-disarm story stays simplest un-donated under
    # armed faults / capture), so the twin is TPU/GPU-only.
    donate = (bool(mex.devices)
              and mex.devices[0].platform not in ("cpu",)
              and mex.loop_recorder is None and not armed)
    acc_pos = tuple(range(2 + n_leaves, 2 + 2 * n_leaves))
    counts_dev = flag = None
    accs: List[Any] = []
    # every chunk program cuts W send blocks per leaf (send_slice)
    send_slices = len(ranges) * W * n_leaves
    mex.stats_xchg_send_slices += send_slices
    with _trace.span_of(getattr(mex, "tracer", None), "exchange",
                        "phase_b", chunks=len(ranges),
                        send_slices=send_slices,
                        narrowed=narrow is not None or None):
        for j, (lo, hi) in enumerate(ranges):
            first, last = j == 0, j == len(ranges) - 1
            fn = chunk_program(lo, hi, first, last)
            if armed:
                default_policy().run(
                    lambda j=j: faults.check(_F_CHUNK, chunk=j,
                                             chunks=len(ranges)),
                    what="xchg.chunk")
            if first:
                out = fn(sorted_dest, smat, *sorted_leaves)
                counts_dev, flag = out[0], out[1]
                accs = list(out[2:])
            else:
                if donate and acc_pos:
                    call = fn.donating(acc_pos)
                    # aliasing is real here (non-CPU, no capture): count
                    # the chunk handoffs whose accumulators were donated
                    mex.stats_xchg_donated += len(acc_pos)
                else:
                    call = fn
                accs = list(call(sorted_dest, smat, *sorted_leaves,
                                 *accs))
    mex.stats_padded_rows += W * M_pad
    # wire truth vs raw equivalent: narrowed rows cross the fabric at
    # their cast width; the raw counter records what full-width rows
    # would have shipped (wire_compress_ratio's denominator)
    wire_rows = W * (W - 1) * M_pad
    mex.stats_bytes_wire_device += wire_rows * _narrow_item_bytes(
        sorted_leaves, narrow)
    mex.stats_bytes_wire_device_raw += wire_rows * item_bytes
    return accs, counts_dev, flag


def _exchange_optimistic(mex: MeshExec, treedef, sorted_dest,
                         sorted_leaves, send_mat, caps: Tuple[int, int],
                         ident: Tuple, min_cap: int = 1,
                         range_mat=None) -> DeviceShards:
    """Phase B on the CACHED capacity plan: no host sync, counts come
    back device-resident, and a deferred check (drained at the next
    consumer boundary / host realization, like the hinted-join
    overflow) verifies the cached capacities actually held — on a miss
    the exchange re-runs from the retained phase-A output under the
    freshly synced plan and heals the shards in place.

    Row narrowing rides the same optimism: the spec LEARNED from past
    synced runs narrows this dispatch, and chunk 0's flag verifies
    every value still fits it — data that outgrew the learned ranges
    is a miss like any other, healed by the synced re-run (which
    re-reads the device ranges and widens the sticky spec)."""
    M_pad, out_cap = caps
    W = mex.num_workers
    item_bytes = leaf_item_bytes(sorted_leaves)
    cap = sorted_leaves[0].shape[1] if sorted_leaves else 0
    cap_ident = _dense_cap_ident(ident, cap, treedef, sorted_leaves)
    narrow = None
    if range_mat is not None:
        narrow = _pack_degraded(
            _sticky_spec(mex, cap_ident, sorted_leaves))
    # the optimistic-vs-synced decision: predicted = the cached output
    # capacity the dispatch trusts; the actual need is only known at
    # deferred-check time, where the audit joins (hit or miss)
    dec = _decisions.record_of(
        mex, "xchg_optimistic", "xchg:" + _ident_digest(ident)[:10],
        "optimistic", predicted=out_cap,
        rejected=[("synced", None)], unit="rows",
        reason="capacity plan cached; host sync elided", m_pad=M_pad)
    with _trace.span_of(getattr(mex, "tracer", None), "exchange",
                        "optimistic", m_pad=M_pad, out_cap=out_cap):
        out_leaves, counts_dev, flag = _dispatch_chunked(
            mex, treedef, sorted_dest, sorted_leaves, send_mat, M_pad,
            out_cap, narrow=narrow, ident=ident)
    tree = jax.tree.unflatten(treedef, out_leaves)
    shards = DeviceShards(mex, tree, counts_dev)

    def check(counts: np.ndarray):
        doc = getattr(mex, "doctor", None)
        t0 = time.perf_counter() if doc is not None else 0.0
        overflowed = bool(mex._fetch_raw(flag).reshape(-1)[0])
        S = mex._fetch_raw(send_mat).astype(np.int64)
        if doc is not None:
            doc.record_wait("xchg.deferred_check", None,
                            time.perf_counter() - t0, lane="exchange")
        # the optimistic-vs-synced verdict, at the moment it is
        # actually known (deferred-check time)
        _trace.instant_of(getattr(mex, "tracer", None), "exchange",
                          "cap_hit" if not overflowed
                          else "capacity_miss",
                          m_pad=M_pad, out_cap=out_cap)
        # audit join: the truth the optimistic dispatch deferred — how
        # many rows each worker actually had to receive vs the cached
        # capacity it trusted (err = overprovision factor on a hit)
        _decisions.resolve_of(
            mex, dec, max(int(S.sum(axis=0).max()), 1),
            verdict="hit" if not overflowed else "miss")
        if not overflowed:
            # the exchange is accounted HERE, not at dispatch: a miss
            # must count one (synced) exchange, not an optimistic one
            # plus its healed re-run
            mex.stats_cap_cache_hits += 1
            mex.stats_exchanges_overlapped += 1
            account_traffic(mex, S, item_bytes,
                            site="xchg:" + _ident_digest(ident)[:10],
                            overlapped=True, cap_hit=True)
            pl = _planner_of(mex)
            if pl is not None and pl.skew_developed(S, item_bytes):
                # the observed send matrix now prefers the 1-factor
                # schedule: mark the site so the NEXT exchange re-syncs
                # and re-chooses immediately instead of riding the
                # cached dense plan out to the periodic resync window
                pl.mark_replan(
                    "xchg:" + _ident_digest(ident)[:10],
                    "deferred check observed a skewed send matrix")
            return None
        # capacity (or narrow-range) miss: the cached plan truncated —
        # re-run phases host+B from the retained phase-A output (the
        # synced plan grows the sticky caps and re-learns the ranges,
        # so the NEXT run hits again)
        mex.stats_cap_cache_misses += 1
        faults.note("recovery", what="xchg.capacity_miss",
                    cached=(M_pad, out_cap))
        ranges = (None if range_mat is None
                  else mex._fetch_raw(range_mat))
        healed = _exchange_planned(mex, treedef, sorted_dest,
                                   sorted_leaves, S, min_cap=min_cap,
                                   ident=ident, smat_dev=send_mat,
                                   ranges=ranges)
        shards.tree = healed.tree
        return healed.counts

    shards._counts_check = check
    # backstop drain point: any tracked fetch / action egress heals an
    # exchange whose output a pipeline abandoned before consuming.
    # WEAK ref only — the hinted-join precedent (join.py): a lingering
    # queue entry must pin no device buffers, or a fetch-free steady-
    # state loop would grow one [W, out_cap] output per query and an
    # HbmGovernor spill could never actually free the HBM
    ref = weakref.ref(shards)

    def _backstop():
        s = ref()
        if s is not None:
            s.validate_pending()

    mex._pending_checks.append(_backstop)
    return shards


def _exchange_planned(mex: MeshExec, treedef, sorted_dest, sorted_leaves,
                      S: np.ndarray, min_cap: int = 1,
                      ident: Tuple = (),
                      smat_dev: Optional[Any] = None,
                      ranges: Optional[np.ndarray] = None
                      ) -> DeviceShards:
    """Phases host+B given phase-A output (also used by scatter paths).

    ``smat_dev`` is the replicated device copy of ``S`` when phase A
    produced one (saves the plan upload); callers with a host-only
    plan (Sort's presorted entry) leave it None. ``ranges`` is the
    fetched [L, 2] per-leaf min/max when phase A computed it — the
    narrow spec derived from it (union'd with the site's remembered
    ranges, so it covers the current data by construction) ships the
    padded rows at their narrowed widths."""
    W = mex.num_workers
    cap = sorted_leaves[0].shape[1] if sorted_leaves else 0
    R = S.sum(axis=0)                             # recv totals per worker
    new_counts = R.astype(np.int64)

    account_traffic(mex, S, leaf_item_bytes(sorted_leaves),
                    site="xchg:" + _ident_digest(ident)[:10])

    if W == 1:
        # no movement: items are already dest-sorted (valid first)
        tree = jax.tree.unflatten(treedef, sorted_leaves)
        return DeviceShards(mex, tree, new_counts)

    # every path below constructs a plan FROM THE SYNCED HOST S — the
    # event the plan store exists to make a warm restart skip
    count_plan_build(mex)
    cap_ident = _dense_cap_ident(ident, cap, treedef, sorted_leaves)
    mode = resolve_mode(mex)
    item_bytes = leaf_item_bytes(sorted_leaves)
    # one cost evaluation serves both the skew verdict and the decision
    # record, so the recorded estimates are EXACTLY the numbers the
    # choice was made from (same math as _skewed). With the adaptive
    # planner attached the CHOICE is the planner's (api/planner.py
    # exchange_strategy — the same inequality, owned by the one cost
    # model); without it the legacy per-site form decides.
    dense_b, of_b, n_rounds = _strategy_costs(mex, S, item_bytes)
    pl = _planner_of(mex)
    if pl is not None:
        chosen_mode, _, _, _why = pl.exchange_strategy(S, item_bytes,
                                                       mode)
        skew = mode == "dense" and chosen_mode == "onefactor"
    else:
        skew = (mode == "dense"
                and dense_b - of_b > n_rounds * _bytes_eq(mex))
    led = _decisions.ledger_of(mex)
    if led is not None:
        # the strategy choice, with the rejected plan's estimated cost
        # — audited immediately against the true (unpadded) payload:
        # err = how much padding the chosen plan ships per real byte
        site = "xchg:" + _ident_digest(ident)[:10]
        if mode == "ragged":
            chosen, pred, rej, why = "ragged", (
                (int(S.sum()) - int(np.trace(S))) * item_bytes), \
                [("dense", dense_b)], "configured mode"
        elif mode == "onefactor" or skew:
            chosen, pred, rej = "onefactor", of_b, [("dense", dense_b)]
            why = "skewed send matrix" if skew else "configured mode"
        else:
            chosen, pred, rej = "dense", dense_b, [("onefactor", of_b)]
            why = "balanced send matrix"
        rec = led.record("xchg_strategy", site, chosen, predicted=pred,
                         rejected=rej, reason=why,
                         items=int(S.sum()))
        led.resolve(rec, (int(S.sum()) - int(np.trace(S)))
                    * item_bytes)
    # the narrow spec is derived ONCE, before the strategy branch, and
    # keyed by the DENSE cap_ident — every phase-B flavor (dense
    # chunked, 1-factor rounds, ragged) shares one learned range store
    # per site. Synced paths union the current ranges in, so the spec
    # covers this exchange's data by construction (cast is exact, no
    # in-trace guard needed); the chunk-0 overflow guard remains on
    # the optimistic dense path, which trusts ranges it did not fetch.
    narrow = _pack_degraded(_spec_from_ranges(
        mex, cap_ident, sorted_leaves,
        _narrowable_leaves(sorted_leaves), ranges))
    with _trace.span_of(getattr(mex, "tracer", None), "exchange",
                        "synced", mode=mode):
        if mode == "ragged":
            mex._xchg_plan[cap_ident] = "sync"
            return _exchange_ragged(mex, treedef, sorted_leaves, S,
                                    min_cap, narrow=narrow)
        if mode == "onefactor" or skew:
            # a skew-flipped site stays synced: the dense-vs-1-factor
            # decision needs the host S, which the optimistic path
            # elides
            mex._xchg_plan[cap_ident] = "sync"
            return _exchange_onefactor(mex, treedef, sorted_dest,
                                       sorted_leaves, S, min_cap,
                                       ident=ident, narrow=narrow)

        M_pad, out_cap = _sticky_caps(
            mex, cap_ident,
            (max(int(S.max()), 1), max(int(R.max()), min_cap, 1)))
        mex._xchg_plan[cap_ident] = "dense"
        smat = smat_dev if smat_dev is not None else \
            mex.put_small(S.astype(np.int32), replicated=True)
        out_leaves, _counts_dev, _flag = _dispatch_chunked(
            mex, treedef, sorted_dest, sorted_leaves, smat, M_pad,
            out_cap, narrow=narrow, ident=ident)
        tree = jax.tree.unflatten(treedef, out_leaves)
        return DeviceShards(mex, tree, new_counts)


def _exchange_onefactor(mex: MeshExec, treedef, sorted_dest, sorted_leaves,
                        S: np.ndarray, min_cap: int = 1,
                        ident: Tuple = (),
                        narrow=None) -> DeviceShards:
    """Skew-proof dense exchange: W-1 ``ppermute`` rounds, one partner
    per round, each round padded only to ITS pair maximum.

    The reference schedules point-to-point exchanges the same way
    (1-factor rounds, thrill/net/group.hpp:90-107). Under a 100:1 key
    skew the uniform all_to_all pads every pair to the global maximum
    (W x waste); here round r ships worker w -> (w + r) % W with
    capacity max_w S[w, (w+r)%W], so bytes track the actual data. The
    diagonal (r = 0) is a local scatter with no communication.
    """
    W = mex.num_workers
    cap = sorted_leaves[0].shape[1] if sorted_leaves else 0
    R = S.sum(axis=0)
    new_counts = R.astype(np.int64)
    rounds = one_factor_rounds(mex)               # tier-pure if sliced
    cap_ident = ("xchg_of_caps", ident, cap, treedef,
                 tuple((l.dtype, l.shape[2:]) for l in sorted_leaves))
    needed = tuple(
        max(int(S[np.arange(W), to].max()), 1) for to in rounds
    ) + (max(int(R.max()), min_cap, 1),)
    caps = _sticky_caps(mex, cap_ident, needed)
    M_rounds, out_cap = caps[:-1], caps[-1]
    mex.stats_padded_rows += sum(M_rounds)
    # rounds ship at the narrowed width; _raw keeps the full-width
    # equivalent (the two halves of wire_compress_ratio)
    of_rows = W * sum(M_rounds)
    mex.stats_bytes_wire_device += of_rows * _narrow_item_bytes(
        sorted_leaves, narrow)
    mex.stats_bytes_wire_device_raw += of_rows * leaf_item_bytes(
        sorted_leaves)

    key_b = ("xchg_of", cap, M_rounds, out_cap, mex.num_slices, treedef,
             narrow,
             tuple((l.dtype, l.shape[2:]) for l in sorted_leaves))
    wide_dts = [l.dtype for l in sorted_leaves]

    def build_b():
        def fb(sdest, srow, scol, *ls):
            from ..core import rowmove
            d = sdest[0]
            S_row = srow[0]
            S_col = scol[0]
            off = _ex_cumsum(S_row)
            roff = _ex_cumsum(S_col)
            i = jnp.arange(cap)
            widx = lax.axis_index(AXIS)
            raw = [l[0] for l in ls]
            if narrow is not None:
                # cast eligible leaves to their learned narrow dtype
                # before any round ships; the spec covers this data
                # (synced plan, ranges union'd), so the round-trip
                # cast is exact
                raw = [x if narrow[li] is None
                       else x.astype(np.dtype(narrow[li]))
                       for li, x in enumerate(raw)]
            if rowmove.enabled():
                xs, metas = rowmove.pack_leaves(raw)
            else:
                xs, metas = raw, [None] * len(raw)
            outs = [jnp.zeros((out_cap + 1,) + x.shape[1:], x.dtype)
                    for x in xs]
            # identity round: local scatter, no communication
            sel0 = d == widx
            slot0 = i - jnp.take(off, widx)
            pos0 = jnp.where(sel0, jnp.take(roff, widx) + slot0, out_cap)
            outs = [o.at[pos0].set(x) for o, x in zip(outs, xs)]
            for r, to in enumerate(rounds):
                inv = np.empty(W, dtype=np.int64)
                inv[to] = np.arange(W)
                d_r = jnp.take(jnp.asarray(to), widx)   # partner I send to
                s_r = jnp.take(jnp.asarray(inv), widx)  # partner I recv from
                M_r = M_rounds[r]
                # my partner's block, cut out of the dest-sorted rows
                start = jnp.take(off, d_r)[None]
                n_send = jnp.take(S_row, d_r)[None]
                perm = [(w, int(to[w])) for w in range(W)]
                j = jnp.arange(M_r)
                n_recv = jnp.take(S_col, s_r)
                pos = jnp.where(j < n_recv, jnp.take(roff, s_r) + j,
                                out_cap)
                for li, x in enumerate(xs):
                    with jax.named_scope(SCOPE):
                        buf = send_slice(x, start, n_send, M_r)[0]
                        recv = lax.ppermute(buf, AXIS, perm=perm)
                    outs[li] = outs[li].at[pos].set(recv)
            res = []
            for li, (o, m) in enumerate(zip(outs, metas)):
                y = rowmove.unpack_rows(o[:out_cap], m)
                if y.dtype != wide_dts[li]:
                    y = y.astype(wide_dts[li])     # widen back
                res.append(y[None])
            return tuple(res)

        return mex.smap(fb, 3 + len(sorted_leaves))

    fb = mex.cached(key_b, build_b)
    srow = mex.put_small(S.astype(np.int32))
    scol = mex.put_small(S.T.copy().astype(np.int32))
    # one send block per leaf and round (send_slice)
    mex.stats_xchg_send_slices += len(rounds) * len(sorted_leaves)
    out_leaves = list(fb(sorted_dest, srow, scol, *sorted_leaves))
    tree = jax.tree.unflatten(treedef, out_leaves)
    return DeviceShards(mex, tree, new_counts)


def _ragged_builder(mex: MeshExec, out_cap: int, num_leaves: int,
                    narrow=None):
    """The jitted ragged-exchange program (shared by the execution path
    and by :func:`lower_ragged_exchange`, which plan-validates it on
    builds whose XLA backend cannot execute the op). ``narrow`` casts
    eligible leaves to their learned narrow dtype before the collective
    and widens after (exact: the synced spec covers the data)."""

    def f(srow, scol, olanding, *ls):
        from ..core import rowmove
        S_row = srow[0].astype(jnp.int32)     # my sends by dest
        S_col = scol[0].astype(jnp.int32)     # my recvs by source
        in_off = _ex_cumsum(S_row)
        # where MY chunk lands inside each destination's buffer:
        # sources before me writing to that destination
        out_off = olanding[0].astype(jnp.int32)
        pack = rowmove.enabled()
        outs = []
        for li, l in enumerate(ls):
            x0 = l[0]
            wide_dt = x0.dtype
            nd = narrow[li] if narrow is not None else None
            if nd is not None:
                x0 = x0.astype(np.dtype(nd))
            x, m = rowmove.pack_rows(x0) if pack else (x0, None)
            out = jnp.zeros((out_cap,) + x.shape[1:], x.dtype)
            with jax.named_scope(SCOPE):
                res = lax.ragged_all_to_all(
                    x, out, in_off, S_row, out_off, S_col,
                    axis_name=AXIS)
            y = rowmove.unpack_rows(res, m)
            if y.dtype != wide_dt:
                y = y.astype(wide_dt)              # widen back
            outs.append(y[None])
        return tuple(outs)

    return mex.smap(f, 3 + num_leaves)


def _warn_ragged_untested(mex: MeshExec) -> None:
    """Loud one-time gate: the ragged path cannot RUN on this image
    (XLA:CPU lacks the op), so a user forcing it off-TPU must know the
    path is lowering-validated only (see __graft_entry__ dryrun)."""
    if getattr(mex, "_warned_ragged", False):
        return
    mex._warned_ragged = True
    plat = mex.devices[0].platform if mex.devices else "?"
    if plat not in ("tpu",):
        import sys
        print(f"thrill_tpu: THRILL_TPU_EXCHANGE=ragged on platform "
              f"'{plat}' — lax.ragged_all_to_all is UNIMPLEMENTED on "
              f"XLA:CPU; this path is plan/lowering-validated on this "
              f"build but has never executed here. Expect a compile "
              f"error; use dense/onefactor off-TPU.", file=sys.stderr)


def _exchange_ragged(mex: MeshExec, treedef, sorted_leaves, S: np.ndarray,
                     min_cap: int = 1, narrow=None) -> DeviceShards:
    """TPU fast path: ``lax.ragged_all_to_all`` — no per-pair padding.

    Phase-A output is already destination-contiguous, which is exactly
    the operand layout ragged_all_to_all wants: per-destination input
    offsets are the exclusive cumsum of the send-count row; receive
    offsets group by source (rank order), preserving the same
    deterministic item order as the dense path. XLA:CPU lacks this op,
    so the path is only selected via THRILL_TPU_EXCHANGE=ragged.
    """
    _warn_ragged_untested(mex)
    R = S.sum(axis=0)
    new_counts = R.astype(np.int64)
    # ragged ships exactly the off-diagonal items — no padding tax;
    # narrowed widths on the device counter, full width on _raw
    ragged_items = int(S.sum()) - int(np.trace(S))
    mex.stats_bytes_wire_device += ragged_items * _narrow_item_bytes(
        sorted_leaves, narrow)
    mex.stats_bytes_wire_device_raw += ragged_items * leaf_item_bytes(
        sorted_leaves)
    out_cap = round_up_pow2(max(int(R.max()), min_cap, 1))
    key = ("xchg_ragged", out_cap, treedef, narrow,
           tuple((l.dtype, l.shape[1:]) for l in sorted_leaves))
    fb = mex.cached(key, lambda: _ragged_builder(mex, out_cap,
                                                 len(sorted_leaves),
                                                 narrow=narrow))
    srow = mex.put_small(S.astype(np.int32))
    scol = mex.put_small(S.T.copy().astype(np.int32))
    # landing[w, d] = sum of S[0:w, d] (receiver-side offset of w's chunk)
    landing = (np.cumsum(S, axis=0) - S).astype(np.int32)
    out_leaves = list(fb(srow, scol, mex.put_small(landing), *sorted_leaves))
    tree = jax.tree.unflatten(treedef, out_leaves)
    return DeviceShards(mex, tree, new_counts)


def lower_ragged_exchange(mex: MeshExec, leaf_specs, S: np.ndarray,
                          min_cap: int = 1) -> str:
    """Trace + lower (NOT compile) the ragged exchange program over the
    current mesh and return its StableHLO text.

    This is the strongest validation available on builds whose XLA
    backend lacks the op: the full plan — offset/size computation,
    packed row movement, shard_map specs, static shapes — is traced
    exactly as the execution path would (same builder), and the caller
    can assert the ragged-all-to-all collective is present. Executed by
    the driver's ``dryrun_multichip`` so a pod user is not the first
    trace of this code.

    ``leaf_specs``: [(dtype, row_shape), ...] for the phase-A sorted
    leaves (leading dims [W, cap] are derived from ``S``).
    """
    W = mex.num_workers
    cap = int(round_up_pow2(max(int(S.sum(axis=1).max()), min_cap, 1)))
    out_cap = int(round_up_pow2(max(int(S.sum(axis=0).max()),
                                    min_cap, 1)))
    fb = _ragged_builder(mex, out_cap, len(leaf_specs))
    i32 = jax.ShapeDtypeStruct((W, W), jnp.int32)
    leaves = [jax.ShapeDtypeStruct((W, cap) + tuple(shape), dtype)
              for dtype, shape in leaf_specs]
    lowered = fb.lower(i32, i32, i32, *leaves)
    return lowered.as_text()


# The host-path shuffle lives in data/multiplexer.py (host_exchange):
# single-process bucketing plus the cross-process framed-batch plane.
