"""Device-resident iteration: LoopPlan capture, replay and donation.

Thrill's iterative examples drive a Collapse'd loop DIA per iteration
(reference: examples/page_rank/page_rank.hpp:71-131) — and so did this
port: every iteration re-built the Python DIA graph, re-ran the pull
recursion, re-planned fusion and re-entered the dispatch path. For a
body whose compiled programs are cheap (the dense-gather join + the
scatter ReduceToIndex engine), that host-side work IS the iteration
cost. This module is the Pathways move for data-flow loops
(arXiv:2203.12533): run the body ONCE through the existing pull
recursion + fusion planner, record the resulting sequence of compiled
dispatches as a :class:`LoopPlan` tape, and replay the tape for the
remaining iterations with the loop-carried buffers threaded through —
zero graph construction, zero re-planning, zero host round trips for
iterations 2..N.

How the tape stays correct:

* Recording happens at the ONE choke point every device program passes
  through (``parallel.mesh._CountedJit.__call__``). Each recorded call
  classifies its arguments: a loop-carry leaf, the output of an
  earlier recorded call, or a CONSTANT (anything else — materialized
  upstream shards, ``put_small``-cached plan arrays, Bind operands).
  Classification is by buffer identity, so the capture first copies
  every carry leaf into a fresh buffer: an initial carry that aliases
  a closure constant of the body (or another carry slot) must not get
  the constant misclassified as loop-varying.
* Dataflow pruning: calls whose outputs never reach the loop carry are
  dropped; calls that are needed but do NOT depend on the carry are
  iteration-invariant — their captured outputs become constants and
  the calls are never re-run (this is what makes in-body pulls of
  Keep'd upstream tables free on replay).
* A carry-out leaf that is neither a recorded output nor a carry
  passthrough means the body computed state OUTSIDE the recorded
  dispatch stream (eager host math) — the capture is rejected loudly
  and the loop falls back to plain per-iteration execution.
* The tape assumes per-iteration plan values (exchange send matrices,
  ZipWithIndex offsets, join capacities) are ITERATION-INVARIANT —
  true for the fixed-shape loops this layer targets (PageRank,
  k-means, SGD) where every such value derives from counts that do not
  change across iterations. Invariance of a fetched plan value is
  verified per output LEAF: when host plan logic reads an output of a
  carry-dependent dispatch, the call's jaxpr input→output reachability
  (:class:`_LeafTaint`) decides whether THAT output depends on the
  carry — a constant-topology W>1 shuffle's send matrix (fixed key
  column riding next to the changing ranks) captures, a genuinely
  data-dependent plan still rejects, and every analysis gap falls back
  to the conservative per-call verdict. ``THRILL_TPU_LOOP_REPLAY=0``
  restores the exact per-iteration planning behavior.
* KNOWN BLIND SPOT — carry-dependent Python control flow: a body that
  branches on a scalar it computes with EAGER jnp math and converts
  directly (``if float(jnp.sum(x)) < eps``, ``bool()``, ``.item()``,
  ``np.asarray()`` on an eager result) freezes the iteration-1 branch
  into the tape. The eager value never feeds a recorded dispatch (so
  the constant-provenance guard never sees it) and bypasses
  ``mex.fetch`` (so the fetch taint never fires) — scalar conversion
  on a raw ``jax.Array`` is the one host read this layer cannot
  intercept. Convergence checks belong OUTSIDE ``Iterate`` (run a
  fixed block of iterations, test, repeat — the recipe in
  examples/k_means.py), or read loop data through DIA actions /
  ``mex.fetch``, both of which reject the capture loudly.

Buffer donation: on replayed dispatches the previous iteration's
carry and intermediates are owned by the loop, so their HBM is donated
back to XLA (``donate_argnums`` twins of the compiled programs) instead
of copied — disabled automatically on backends without donation
support (XLA:CPU no-ops with a warning), while fault injection is
armed (a retried dispatch must not have consumed its inputs), for the
first replay (whose carry the capture graph still references), and for
a carry that was just sealed into a checkpoint epoch.

Whole-loop lowering: a body that collapses to ONE fused dispatch — no
exchange, no host fallback, every argument a carry leaf or a constant
— is lowered into a single ``jax.jit(lax.fori_loop)`` program over the
remaining iterations: one dispatch for the whole loop.

Failure semantics: every replayed iteration passes the
``api.loop.replay`` fault site; an injected or real dispatch failure
logs ``event=loop_replay_fallback``, counts in
``ctx.overall_stats()['loop_replay_fallbacks']`` and degrades to full
re-planning (the body runs again through the pull recursion, which
re-captures), so a broken tape can slow the loop down but never
corrupt it. ``Iterate(..., checkpoint_every=k)`` seals the carry into
a durable epoch every k iterations via api/checkpoint.py; a resumed
run restores the newest loop epoch and continues from the next
iteration.

One tape for every call of a loop: a job that runs the same loop again
(the next batch of a service, the next job of a benchmark window) used
to capture again, because a tape's constants are the buffers of ITS
call's upstream tables. ``Iterate(..., invariants=...)`` names what the
body reads unchanged in every iteration and hands it to the body after
the carry. Buffers of invariant DIAs are recorded as ("inv", ...) refs
and not as constants; needed calls that depend on them and not on the
carry form the plan's PROLOGUE. A body that is a plain function carrying
nothing of its own (no closure cells, no default arguments, no ``self``)
and whose tape read nothing else of its call (every other constant a
content-cached plan array or a host operand; no host read of an
invariant or of a value computed from one) is kept on the mesh under
the loop's token: the body OBJECT (by identity and held, as ``jax.jit``
keys a function), name, carry signature and host counts, invariant
signatures and host counts, host invariants by content. The next call
with that token REBINDS it (``loop`` span ``rebind``): it materializes its own
invariants, runs the prologue once on them, and replays from its first
iteration, the whole loop in the one ``fori`` program (its trip count
is an operand). Anything else keeps the old behaviour, a capture per
call; a rebind or a replay that fails forgets the kept tape, counts in
``loop_replay_fallbacks`` and captures afresh. Between calls a kept
tape holds no call's buffers (``unbind``).
"""

from __future__ import annotations

import dataclasses
import os
import time
import types
import weakref
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..common import faults
from ..common.trace import span_of
from ..core.jaxpr_deps import output_deps as _jaxpr_output_deps
from ..data.shards import DeviceShards, HostShards
from . import fusion
from .dia import DIA
from .dia_base import DIABase

_F_REPLAY = faults.declare("api.loop.replay")

# argument refs that reach a whole-loop program as runtime operands:
# plan constants and the current call's invariant / prologue buffers
_OPERAND_KINDS = ("const", "inv", "pro")


# ----------------------------------------------------------------------
# plan-state persistence: loop-capture tape metadata
# ----------------------------------------------------------------------
# The capture iteration's expensive parts are the ANALYSIS — the
# per-output-leaf taint verification re-traces call programs as jaxprs
# — and, for loops that can never capture, the futile capture attempts
# themselves (a full carry copy plus a recorder pass each, twice,
# before the miss streak gives up). Both outcomes are pure functions
# of the tape: which compiled programs ran (their MeshExec cache keys)
# and how their arguments/outputs were wired. Persisting that
# metadata in the plan store lets a warm restart skip the work:
#
# * a loop whose tape previously analyzed clean re-validates by digest
#   (same program keys, same wiring, same fetched plan reads) and
#   skips the taint re-traces — the tape is trusted because the
#   analysis inputs are provably identical;
# * a loop that previously REJECTED capture runs plain from iteration
#   1, skipping the capture probes entirely.
#
# Stale metadata degrades LOUDLY: a digest mismatch logs
# ``event=loop_seed_stale`` and runs the full fresh analysis — the
# seed can cost nothing but the log line. Correctness-neutral like
# every plan-store value: a trusted tape still re-records THIS run's
# calls; only the verification that the recorded wiring is replayable
# is reused, never the wiring itself.


def export_plan_state(mex) -> dict:
    """Per-loop tape metadata (plan keys + wiring + donation twins) as
    digest maps — the plan store's on-disk form (service/plan_store.py
    ``loop_tape`` kind)."""
    from ..data.exchange import _ident_digest, merge_unconsumed_seeds
    return merge_unconsumed_seeds(mex, {
        "loop_tape": {_ident_digest(k): v for k, v in
                      getattr(mex, "_loop_tapes", {}).items()},
    })


def import_plan_state(mex, state: dict, *,
                      symmetric: bool = False) -> int:
    from ..data.exchange import install_plan_seeds
    return install_plan_seeds(mex, state, ("loop_tape",),
                              symmetric=symmetric)


def _note_tape(mex, token, meta: Optional[dict]) -> None:
    """Remember this loop's capture outcome for export."""
    if meta is None:
        return
    tapes = getattr(mex, "_loop_tapes", None)
    if tapes is None:
        tapes = mex._loop_tapes = {}
    tapes[token] = meta


def replay_enabled() -> bool:
    """THRILL_TPU_LOOP_REPLAY=0 restores plain per-iteration planning."""
    return os.environ.get("THRILL_TPU_LOOP_REPLAY", "1") not in (
        "0", "off", "false")


def donation_enabled() -> bool:
    """THRILL_TPU_LOOP_DONATE overrides; default: on where XLA supports
    input-output aliasing (donation on XLA:CPU is a no-op + warning)."""
    v = os.environ.get("THRILL_TPU_LOOP_DONATE")
    if v is not None:
        return v not in ("0", "off", "false")
    return jax.default_backend() != "cpu"


def fori_enabled() -> bool:
    """THRILL_TPU_LOOP_FORI=0 keeps replay per-iteration (tape calls
    dispatched one by one) instead of lowering the remaining
    iterations into one whole-loop ``lax.fori_loop`` program."""
    return os.environ.get("THRILL_TPU_LOOP_FORI", "1") not in (
        "0", "off", "false")


# ----------------------------------------------------------------------
# tape capture
# ----------------------------------------------------------------------

@dataclasses.dataclass
class _Call:
    """One recorded dispatch: the counted-jit callable plus classified
    argument references.  ``arg_refs``: ("carry", slot) | ("val",
    (call_idx, out_idx)) | ("const", buffer) | ("inv", (k, leaf)): a
    buffer of the k-th invariant DIA's shards | ("pro", (call_idx,
    out_idx)): an output of the plan's prologue (analysis only) |
    ("tree", treedef, [leaf refs]) for pytree arguments that MIX
    loop-owned leaves with constants (a jit_cached body called on the
    carry dict).  Filled
    during analysis: ``donate_pos`` — argument positions whose buffers
    are loop-owned and dead after this call.  ``leaf_kinds`` (flatten
    order across all arguments = jaxpr invar order) and ``avals``
    support the per-output-LEAF taint refinement: a fetched output
    that provably depends only on constant/invariant input leaves
    does not poison the tape even when ANOTHER output of the same
    call is carry-dependent."""
    fn: Any
    arg_refs: List[Tuple]
    out_buffers: List[Any]
    donate_pos: Tuple[int, ...] = ()
    leaf_kinds: Optional[List[Tuple]] = None
    avals: Optional[Tuple] = None


def _leaf_refs(refs):
    """Iterate the leaf-level refs of an arg_refs list (trees
    flattened)."""
    for ref in refs:
        if ref[0] == "tree":
            for s in ref[2]:
                yield s
        else:
            yield ref


class _Recorder:
    """Installed as ``mex.loop_recorder`` around the capture iteration's
    body run; sees every ``_CountedJit`` dispatch."""

    def __init__(self, carry_ids: Dict[int, int],
                 known: Optional[list] = None,
                 inv_ids: Optional[Dict[int, Tuple[int, int]]] = None,
                 small_cache: Optional[dict] = None) -> None:
        self.carry_ids = carry_ids
        # buffers of the declared invariant DIAs' shards -> (k, leaf):
        # what a later Iterate call of the same loop rebinds
        self.inv_ids = inv_ids or {}
        # the constants every call of this loop would see: plan arrays
        # of the content-keyed put_small cache and host operands
        # converted right before a dispatch (asarray_blessed). A
        # constant from anywhere else (an upstream table, a source
        # that uploaded its data during the capture) may belong to
        # this call alone, and then the tape is not kept for the next
        self._small_cache = small_cache if small_cache is not None else {}
        self._operands: set = set()
        self.unshared: Optional[str] = None
        self.calls: List[_Call] = []
        self.produced: Dict[int, Tuple[int, int]] = {}
        self.plan_reads: set = set()   # (call, out) leaves fetched to host
        self.dispatch_s = 0.0            # issue time inside dispatches
        self.dirty: Optional[str] = None
        # constant provenance: device arrays live BEFORE the capture
        # iteration (upstream tables, plan caches, Bind operands) and
        # host uploads made during it (mesh.put blesses) are legitimate
        # tape constants; any OTHER array created during the body is
        # eager device math whose value could depend on the carry — a
        # tape would freeze it at iteration-1 values, so reject. The
        # snapshot holds WEAK refs so it cannot pin the process's HBM
        # through the capture iteration; lookups verify identity, so a
        # pre-live array that dies and hands its id to a fresh eager
        # result reads as unknown (reject — slow but correct).
        self._known: Dict[int, Any] = {}
        for a in (known or []):
            try:
                self._known[id(a)] = weakref.ref(a)
            except TypeError:
                self._known[id(a)] = (lambda a=a: a)

    def bless(self, buf, operand: bool = False) -> None:
        """mesh.put uploaded ``buf`` during this capture, or (``operand``)
        a host operand was converted for a dispatch. Blessed buffers
        are held strongly: the tape's bound args reference them anyway,
        and a blessing must not silently expire."""
        self._known[id(buf)] = (lambda buf=buf: buf)
        if operand:
            self._operands.add(id(buf))

    def _is_shared(self, a) -> bool:
        return id(a) in self._operands \
            or any(v is a for v in self._small_cache.values())

    def _is_known(self, a) -> bool:
        r = self._known.get(id(a))
        return r is not None and r() is a

    def on_fetch(self, arr) -> None:
        """Host plan logic fetched ``arr`` during the capture run. If a
        recorded dispatch produced it, the body's between-dispatch
        host code READ loop data — remember the producing (call, out)
        LEAF so analysis can reject the tape when that specific output
        is carry-dependent (its fetched value would vary per
        iteration: a data-dependent exchange send matrix, a join size
        agreement). A fetched CARRY leaf is carry-dependent by
        definition (e.g. the carry's device counts sizing an exchange)
        — reject outright."""
        if id(arr) in self.carry_ids:
            self.dirty = ("host plan logic fetched a carry leaf "
                          "during capture (carry-dependent plan)")
            return
        if id(arr) in self.inv_ids:
            self.unshared = "host plan logic read an invariant's buffer"
        src = self.produced.get(id(arr))
        if src is not None:
            self.plan_reads.add(src)

    def _leaf_ref(self, a) -> Optional[Tuple]:
        slot = self.carry_ids.get(id(a))
        if slot is not None:
            return ("carry", slot)
        if id(a) in self.produced:
            return ("val", self.produced[id(a)])
        inv = self.inv_ids.get(id(a))
        if inv is not None:
            return ("inv", inv)
        if isinstance(a, np.ndarray):
            # a host array feeding a dispatch may be a fetched copy
            # of loop-VARIANT data (multi-controller egress); a
            # tape would freeze it — reject the capture instead
            self.dirty = ("numpy argument entered a recorded "
                          "dispatch (host round trip in the body)")
            return None
        if isinstance(a, jax.Array) and self._known \
                and not self._is_known(a):
            # created during the body but not by a recorded dispatch
            # or a host upload: eager device math, possibly over the
            # carry — its frozen value would corrupt every replay
            self.dirty = ("eager device math fed a recorded dispatch "
                          "during capture (unrecorded jax op in the "
                          "body?)")
            return None
        if isinstance(a, jax.Array) and self.unshared is None \
                and not self._is_shared(a):
            self.unshared = ("a device array that is neither an "
                             "invariant nor a plan constant entered a "
                             "dispatch")
        return ("const", a)

    def on_call(self, fn, args, kwargs, out) -> None:
        if self.dirty is not None:
            return
        if kwargs:
            self.dirty = "dispatch with keyword arguments"
            return
        refs: List[Tuple] = []
        leaf_kinds: List[Tuple] = []     # flatten order = jaxpr invars
        for a in args:
            leaves, td = jax.tree.flatten(a)
            if len(leaves) == 1 and leaves[0] is a:
                ref = self._leaf_ref(a)
                if ref is None:
                    return
                refs.append(ref)
                leaf_kinds.append(ref)
                continue
            subs = []
            for l in leaves:
                s = self._leaf_ref(l)
                if s is None:
                    return
                subs.append(s)
            leaf_kinds.extend(subs)
            if all(s[0] == "const" for s in subs):
                refs.append(("const", a))     # wholly-constant pytree
            else:
                refs.append(("tree", td, subs))
        try:
            # abstract argument shapes for the per-output-leaf taint
            # refinement (re-tracing with ShapeDtypeStructs is cheap
            # and happens only for fetched, carry-dependent calls)
            avals = tuple(
                jax.tree.map(lambda l: jax.ShapeDtypeStruct(
                    jnp.shape(l), jnp.result_type(l)), a)
                for a in args)
        except Exception:
            avals = None                  # conservative: no refinement
        out_leaves = jax.tree.leaves(out)
        idx = len(self.calls)
        for j, o in enumerate(out_leaves):
            self.produced[id(o)] = (idx, j)
        self.calls.append(_Call(fn, refs, out_leaves,
                                leaf_kinds=leaf_kinds, avals=avals))


# ----------------------------------------------------------------------
# per-output-leaf taint refinement (jaxpr input->output reachability)
# ----------------------------------------------------------------------

def _call_output_deps(c: "_Call") -> Optional[List[frozenset]]:
    """Per-output-leaf invar dependence of one recorded call, from a
    fresh abstract trace of its program; None (refinement unavailable)
    on any failure — the caller then falls back to call-level taint."""
    if c.leaf_kinds is None or c.avals is None:
        return None
    target = getattr(c.fn, "raw", None) or getattr(c.fn, "_jitted",
                                                   None)
    if target is None:
        return None
    try:
        closed = jax.make_jaxpr(target)(*c.avals)
        return _jaxpr_output_deps(closed.jaxpr)
    except Exception:
        return None


class _LeafTaint:
    """Transitive per-output-LEAF carry dependence over a recorded
    tape: output (i, j) is carry-dependent iff the jaxpr-level
    reachability of call ``i`` connects it to a carry input leaf or to
    a carry-dependent output of an earlier call (judged recursively at
    leaf level). Conservative at every gap: a call whose program
    cannot be re-traced falls back to its call-level verdict. Traces
    are computed lazily and memoized — only calls actually reachable
    from a fetched output pay one abstract trace."""

    def __init__(self, calls: List["_Call"], dep: List[bool]) -> None:
        self.calls = calls
        self.dep = dep
        self._out_deps: Dict[int, Optional[List[frozenset]]] = {}
        self._pair: Dict[Tuple[int, int], bool] = {}

    def pair_dep(self, i: int, j: int) -> bool:
        key = (i, j)
        hit = self._pair.get(key)
        if hit is not None:
            return hit
        if not self.dep[i]:
            self._pair[key] = False
            return False
        od = self._out_deps.get(i, ...)
        if od is ...:
            od = self._out_deps[i] = _call_output_deps(self.calls[i])
        kinds = self.calls[i].leaf_kinds
        r = True                        # conservative default
        if od is not None and kinds is not None and j < len(od):
            r = False
            for k in od[j]:
                if k >= len(kinds):
                    r = True
                    break
                ref = kinds[k]
                if ref[0] == "carry" or (
                        ref[0] == "val" and self.pair_dep(*ref[1])):
                    r = True
                    break
        self._pair[key] = r
        return r


# ----------------------------------------------------------------------
# the plan
# ----------------------------------------------------------------------

def _exact_ident(x) -> bool:
    """Does ``_canon(x)`` carry full content identity? Mirrors
    _canon's branches: tuples recurse; callables are exact when their
    token embeds a bytecode hash (i.e. they have ``__code__``);
    everything else is exact unless its repr is address-bearing (which
    _canon degrades to a bare class name two distinct objects would
    share)."""
    if isinstance(x, tuple):
        return all(_exact_ident(e) for e in x)
    if callable(x) and not isinstance(x, type):
        if getattr(x, "__qualname__", None):
            return getattr(x, "__code__", None) is not None
        # falls through to _canon's repr branch below
    return " at 0x" not in repr(x)


def _tape_meta(calls: List[_Call], plan_reads, carry_out,
               n_carry: int) -> Optional[dict]:
    """The tape's persistable identity: per-call compiled-program keys
    (MeshExec cache-key digests) plus a wiring digest over argument
    refs, fetched plan reads and the carry mapping — exactly the
    inputs the capture analysis is a pure function of, so two tapes
    with equal metadata provably analyze the same. None when any call
    lacks a stable cache key (uncached program: no cross-process
    identity)."""
    import hashlib

    from ..data.exchange import _canon
    keys = []
    exact = True
    for c in calls:
        key = getattr(c.fn, "cache_key", None)
        if key is None:
            return None
        # _canon degrades some reprs to identities WITHOUT content
        # hashes (address-bearing objects -> bare class, callables
        # without __code__ -> bare qualname) — correctness-neutral for
        # capacities (they ratchet and heal) but NOT for trusting a
        # taint verdict: two distinct programs could digest equal.
        # _exact_ident walks the key structurally (mirroring _canon's
        # branches), so ordinary keys — strings, ints, dtypes,
        # treedefs, user functions incl. lambdas/locals (their tokens
        # carry bytecode+consts+closure hashes) — stay exact.
        if not _exact_ident(key):
            exact = False
        keys.append(hashlib.sha1(_canon(key).encode()).hexdigest())

    def rsig(ref):
        if ref[0] == "const":
            return "c"
        if ref[0] == "tree":
            return ("t", _canon(ref[1]),
                    tuple(rsig(s) for s in ref[2]))
        return ref                     # ("carry", s) / ("val", (i, j))

    wiring = repr((
        tuple(tuple(rsig(r) for r in c.arg_refs) for c in calls),
        tuple(sorted(plan_reads)),
        tuple("c" if r[0] == "const" else r for r in carry_out),
        n_carry))
    return {"capture": True, "calls": keys, "exact": exact,
            "wiring": hashlib.sha1(wiring.encode()).hexdigest()}


class LoopPlan:
    """A replayable tape over one loop iteration.

    ``carry_out``: per carry-leaf reference — ("val", (i, j)) into the
    live tape or ("carry", s) passthrough. ``counts`` (shards mode):
    the iteration-invariant host counts of the carry, or None when the
    counts thread through the tape as a device leaf. ``seed``: the
    plan store's remembered tape metadata for this loop — a digest
    match skips the taint re-traces (trusted tape), a mismatch is
    STALE and runs the full fresh analysis.

    Invariants: a needed call that depends on an invariant DIA's
    buffers but not on the carry is the same in every iteration and
    another in every ``Iterate`` call, so it goes to ``prologue``, run
    once per call by :meth:`bind`; ("inv", ...) and ("pro", ...) refs
    of the live calls read the CURRENT call's buffers (``_inv``,
    ``_pro``). ``unshared`` says why the tape serves its own call
    alone, or is None: then ``Iterate`` keeps it on the mesh."""

    def __init__(self, mex, calls: List[_Call], carry_out: List[Tuple],
                 n_carry: int, plan_reads: Optional[set] = None,
                 name: Optional[str] = None,
                 seed: Optional[dict] = None,
                 inv_leaves: Optional[List[List[Any]]] = None,
                 unshared: Optional[str] = None) -> None:
        self.mex = mex
        self.prologue: List[_Call] = []
        self._inv = inv_leaves
        self._pro: Optional[Dict[Tuple[int, int], Any]] = None
        self.unshared = unshared
        self.calls = calls
        self.carry_out = carry_out
        self.n_carry = n_carry
        self.name = name
        self.plan_reads = plan_reads or set()
        self.seed = seed if isinstance(seed, dict) else None
        self.seeded = False            # trusted warm-restart metadata
        self.seed_stale = False        # seed present but mismatched
        self.meta: Optional[dict] = None
        # set by _analyze when the tape cannot be replayed safely
        self.invalid: Optional[str] = None
        # shards-mode carry counts: the iteration-invariant host counts
        # replayed carries inherit (None = counts thread through the
        # tape as the last carry leaf)
        self.counts: Optional[np.ndarray] = None
        self.pruned_invariant = 0
        self.pruned_dead = 0
        self._fori: Any = None           # lazily built whole-loop program
        self._fori_failed = False
        self._analyze()

    # -- dataflow analysis ---------------------------------------------
    def _analyze(self) -> None:
        calls = self.calls
        n = len(calls)
        # carry dependence and invariant dependence (forward)
        dep = [False] * n
        idep = [False] * n
        for i, c in enumerate(calls):
            for ref in _leaf_refs(c.arg_refs):
                if ref[0] == "carry" or (ref[0] == "val"
                                         and dep[ref[1][0]]):
                    dep[i] = True
                if ref[0] == "inv" or (ref[0] == "val"
                                       and idep[ref[1][0]]):
                    idep[i] = True
        # tape identity (plan-store loop_tape metadata): computed over
        # the ORIGINAL calls/wiring/plan-reads — exactly the inputs
        # the taint verification below is a pure function of
        self.meta = _tape_meta(calls, self.plan_reads, self.carry_out,
                               self.n_carry)
        trusted = False
        if self.seed is not None:
            if self.meta is not None and self.seed.get("capture") \
                    and self.meta["exact"] and self.seed.get("exact") \
                    and self.seed.get("calls") == self.meta["calls"] \
                    and self.seed.get("wiring") == self.meta["wiring"]:
                # warm restart: this exact tape (same compiled-program
                # keys, same wiring, same fetched plan reads) analyzed
                # clean before — skip the per-output-leaf taint
                # re-traces, the capture iteration's expensive half
                trusted = self.seeded = True
            else:
                self.seed_stale = True
        # host plan logic that read a CARRY-DEPENDENT value during
        # capture (data-dependent exchange send matrix, a size
        # agreement) would be frozen by the tape at iteration-1 values
        # — reject. Dependence is judged per output LEAF: when the
        # producing call is carry-dependent overall, its jaxpr's
        # input->output reachability decides whether THIS output
        # depends on a carry leaf or only on constants/invariant
        # values (a constant-topology shuffle's send matrix derives
        # from a fixed key column riding next to the changing ranks —
        # per-CALL taint would reject it, per-leaf taint captures it).
        # Refinement failures fall back to the per-call verdict.
        if not trusted:
            taint = _LeafTaint(calls, dep)
            for i, j in self.plan_reads:
                if dep[i] and taint.pair_dep(i, j):
                    self.invalid = ("host plan logic read a "
                                    "carry-dependent value during "
                                    "capture (data-dependent exchange "
                                    "plan?)")
                    break
        # liveness (backward from the carry outputs)
        needed = [False] * n
        stack = [ref[1][0] for ref in self.carry_out if ref[0] == "val"]
        while stack:
            i = stack.pop()
            if needed[i]:
                continue
            needed[i] = True
            for ref in _leaf_refs(calls[i].arg_refs):
                if ref[0] == "val":
                    stack.append(ref[1][0])
        live_idx = [i for i in range(n) if needed[i] and dep[i]]
        pro_idx = [i for i in range(n)
                   if needed[i] and not dep[i] and idep[i]]
        self.pruned_invariant = sum(1 for i in range(n)
                                    if needed[i] and not dep[i])
        self.pruned_dead = n - sum(needed)
        remap = {old: new for new, old in enumerate(live_idx)}
        pro_remap = {old: new for new, old in enumerate(pro_idx)}

        def rewrite(ref):
            if ref[0] == "val":
                src, j = ref[1]
                if src in remap:
                    return ("val", (remap[src], j))
                if src in pro_remap:
                    # the same in every iteration of THIS call: read
                    # from the call's prologue outputs
                    return ("pro", (pro_remap[src], j))
                # invariant producer: its captured output IS the
                # value for every future iteration
                return ("const", calls[src].out_buffers[j])
            if ref[0] == "tree":
                return ("tree", ref[1], [rewrite(s) for s in ref[2]])
            return ref

        def rewritten(idx):
            return [_Call(calls[i].fn,
                          [rewrite(r) for r in calls[i].arg_refs],
                          calls[i].out_buffers) for i in idx]

        live = rewritten(live_idx)
        self.prologue = rewritten(pro_idx)
        # this call's prologue outputs are the captured ones
        self._pro = {(p, j): o for p, c in enumerate(self.prologue)
                     for j, o in enumerate(c.out_buffers)}
        out = [rewrite(ref) for ref in self.carry_out]
        if self.unshared is None:
            if any(idep[i] for i, _ in self.plan_reads):
                self.unshared = ("host plan logic read a value computed "
                                 "from an invariant")
            elif any(ref[0] not in ("val", "carry") for ref in out):
                self.unshared = "a carry leaf is constant across iterations"
        self.calls = live
        self.carry_out = out
        # donation positions are recomputed per capture (cheap, pure
        # python over the refs) — the wiring digest in the metadata
        # fully determines them, so a trusted seed's donation twins
        # provably match what this analysis just derived
        self._mark_donations()
        # live calls must not pin the capture iteration's HBM: their
        # recorded outputs are never read again (invariant producers'
        # outputs were just folded into ("const", ...) refs above)
        for c in self.calls + self.prologue:
            c.out_buffers = None
        # which (call, out) pairs later steps / the carry actually read
        used: set = set()
        for c in self.calls:
            for ref in _leaf_refs(c.arg_refs):
                if ref[0] == "val":
                    used.add(ref[1])
        for ref in self.carry_out:
            if ref[0] == "val":
                used.add(ref[1])
        self.used_outputs = used

    def _mark_donations(self) -> None:
        """Static donation plan: an argument buffer is donatable when
        it is loop-owned (a carry leaf or a live call's output), this
        is its LAST use in the iteration, and it does not survive into
        the next carry. Pytree arguments stay pinned (jax donates whole
        arguments; a mixed tree would donate its constants too)."""
        survivors = set()
        for slot, ref in enumerate(self.carry_out):
            if ref[0] in ("carry", "val"):
                survivors.add((ref[0], ref[1]))
            else:
                # folded-const carry-out: slot hands back the SAME
                # buffer every iteration (and holds it on entry from
                # the previous iteration's carry) — donating it would
                # free a buffer the loop still owns
                survivors.add(("carry", slot))
        by_ref: Dict[Tuple, List[int]] = {}
        for slot, ref in enumerate(self.carry_out):
            if ref[0] in ("carry", "val"):
                by_ref.setdefault((ref[0], ref[1]), []).append(slot)
        for slots in by_ref.values():
            if len(slots) > 1:
                # aliased carry-out: these slots hand back ONE buffer,
                # so the next iteration's incoming carry leaves alias —
                # donating any one view would free the buffer another
                # slot still reads mid-iteration
                for s in slots:
                    survivors.add(("carry", s))
        last_use: Dict[Tuple, Tuple[int, int]] = {}
        for i, c in enumerate(self.calls):
            seen_here: Dict[Tuple, int] = {}
            for p, ref in enumerate(c.arg_refs):
                if ref[0] not in ("carry", "val"):
                    continue
                key = (ref[0], ref[1])
                seen_here[key] = seen_here.get(key, 0) + 1
                last_use[key] = (i, p)
            # a buffer passed twice to one call cannot be donated;
            # neither can one this call ALSO reads through a pytree
            # argument (donating would free a buffer the same dispatch
            # reads) — position -1 never matches a donatable slot
            for key, k in seen_here.items():
                if k > 1:
                    last_use.pop(key, None)
            for ref in c.arg_refs:
                if ref[0] == "tree":
                    for s in ref[2]:
                        if s[0] != "const":
                            last_use[(s[0], s[1])] = (i, -1)
        for i, c in enumerate(self.calls):
            pos = tuple(sorted(
                p for p, ref in enumerate(c.arg_refs)
                if ref[0] in ("carry", "val")
                and (ref[0], ref[1]) not in survivors
                and last_use.get((ref[0], ref[1])) == (i, p)))
            c.donate_pos = pos

    # -- this call's buffers --------------------------------------------
    def _resolve(self, ref, carry=None, vals=None):
        """The buffer behind an argument ref: a constant, the current
        call's invariant or prologue output, or (inside an iteration)
        a ``carry`` leaf or an earlier call's output in ``vals``."""
        kind = ref[0]
        if kind == "const":
            return ref[1]
        if kind == "inv":
            return self._inv[ref[1][0]][ref[1][1]]
        if kind == "pro":
            return self._pro[ref[1]]
        if kind == "carry":
            return carry[ref[1]]
        if kind == "val":
            return vals[ref[1]]
        return jax.tree.unflatten(
            ref[1], [self._resolve(s, carry, vals) for s in ref[2]])

    def bind(self, inv_leaves: List[List[Any]]) -> None:
        """Take over another ``Iterate`` call's invariant buffers and
        run the prologue on them, once."""
        self._inv = inv_leaves
        self._pro = pro = {}
        for p, call in enumerate(self.prologue):
            out = call.fn(*[self._resolve(ref) for ref in call.arg_refs])
            for j, o in enumerate(jax.tree.leaves(out)):
                pro[(p, j)] = o

    def unbind(self) -> None:
        """Let go of the call's buffers: a kept plan must not pin one
        job's tables in HBM until the next one comes."""
        self._inv = self._pro = None

    # -- execution ------------------------------------------------------
    def replay(self, carry: List[Any], donate: bool,
               donate_carry: bool = True) -> List[Any]:
        """Run one tape iteration over ``carry`` leaves; returns the
        next carry leaves. ``donate_carry=False`` pins the incoming
        carry buffers (first replay; the iteration after a checkpoint
        seal)."""
        mex = self.mex
        vals: Dict[Tuple[int, int], Any] = {}

        for i, call in enumerate(self.calls):
            args = [self._resolve(ref, carry, vals)
                    for ref in call.arg_refs]
            fn = call.fn
            if donate and call.donate_pos:
                pos = call.donate_pos
                if not donate_carry:
                    pos = tuple(p for p in pos
                                if call.arg_refs[p][0] != "carry")
                if pos:
                    fn = call.fn.donating(pos)
                    mex.stats_loop_donated_bytes += sum(
                        getattr(args[p], "nbytes", 0) for p in pos)
            out = fn(*args)
            for j, o in enumerate(jax.tree.leaves(out)):
                if (i, j) in self.used_outputs:
                    vals[(i, j)] = o
        return [self._resolve(ref, carry, vals) for ref in self.carry_out]

    # -- whole-loop fori_loop lowering ---------------------------------
    def fori_eligible(self) -> bool:
        """Every recorded call retains its raw (pre-jit) program, so
        the whole tape can be re-traced inside ONE ``lax.fori_loop``
        body — exchanges and host fallbacks never record, so any
        all-device tape qualifies."""
        return bool(self.calls) and all(
            getattr(c.fn, "raw", None) is not None for c in self.calls)

    def _fori_consts(self) -> Tuple:
        """Constant operands in tape order (tree args contribute their
        const LEAVES, in flatten order — the fori body consumes them
        from the same traversal)."""
        return tuple(self._resolve(ref) for c in self.calls
                     for ref in _leaf_refs(c.arg_refs)
                     if ref[0] in _OPERAND_KINDS)

    def run_fori(self, carry: List[Any],
                 k: int) -> Optional[Tuple[List[Any], int]]:
        """Lower the remaining ``k`` iterations into ONE jitted
        ``lax.fori_loop`` dispatch over the whole tape, or return None
        when the body cannot be lowered (version/topology limits);
        else the next carry and the index plans the dispatch computed
        (its ``loop`` span says so, its ``dispatch`` span cannot).

        The incoming carry is never donated here: fori only ever runs
        as the FIRST replay after a (re)capture, whose carry buffers
        the capture graph still references. ``k`` is an operand and
        not part of the program: the call that captured runs k = n - 1
        through it and a call that rebinds the tape k = n."""
        if self._fori_failed or not self.fori_eligible():
            return None
        calls = self.calls
        # a call that computes index plans in place (api/fusion.py
        # Segment.index_plan: ReduceToIndex's fold over sorted runs),
        # all from operands of the loop, derives them from nothing that
        # changes with the iteration: the plans run once, before the
        # loop, and the loop runs the call's form that takes them
        plans = [fusion.index_plans(c.fn) for c in calls]
        hoisted = frozenset(
            i for i, (c, ip) in enumerate(zip(calls, plans))
            if ip is not None and ip.body is not None and all(
                c.arg_refs[p][0] in _OPERAND_KINDS
                for reads, _ in ip.plans for p in reads))
        # a carry leaf that is the same buffer in every iteration is
        # closed over by the program, whatever it was computed from
        out_slots: List[Tuple] = [
            ("const", self._resolve(r)) if r[0] in ("inv", "pro") else r
            for r in self.carry_out]
        used = self.used_outputs
        if self._fori is None:
            # two plans with the same per-call programs and wiring are
            # the SAME loop — share one compiled fori program through
            # the mesh cache (a fresh capture per driver call must not
            # recompile the whole-loop dispatch)
            def ref_sig(r):
                if r[0] in _OPERAND_KINDS:
                    return ("const",)
                if r[0] == "tree":
                    return ("tree", r[1],
                            tuple(ref_sig(s) for s in r[2]))
                return r

            # a const carry-out leaf is CLOSED OVER by the traced body
            # (folded invariant producer), so the compiled program is
            # keyed on that buffer's identity — never shared across
            # captures holding different values
            out_sig = tuple(("const", id(r[1])) if r[0] == "const"
                            else r for r in out_slots)
            key = ("loop_fori",
                   tuple(getattr(c.fn, "cache_key", None)
                         or ("rawid", id(c.fn.raw)) for c in calls),
                   tuple(tuple(ref_sig(r) for r in c.arg_refs)
                         for c in calls),
                   tuple(sorted(used)), out_sig, tuple(sorted(hoisted)))

            built = []

            def build():
                built.append(True)
                # the compiled closure lives in the mesh cache for the
                # MESH's lifetime — it must not pin this plan's const
                # ARGUMENT buffers (they arrive through the runtime
                # ``consts`` operand; only const carry-OUT leaves are
                # intentionally closed over, that's what the id-keying
                # above is for)
                def strip(r):
                    if r[0] in _OPERAND_KINDS:
                        return ("const", None)
                    if r[0] == "tree":
                        return ("tree", r[1], [strip(s) for s in r[2]])
                    return r
                call_plan = [(c.fn.raw, [strip(r) for r in c.arg_refs],
                              plans[i] if i in hoisted else None)
                             for i, c in enumerate(calls)]

                def loop_fn(carry_t, consts, k):
                    ci = iter(consts)

                    def operand(ref):
                        """The loop's operand behind a ref, taken in
                        tape order; None for what an iteration makes."""
                        if ref[0] == "const":
                            return next(ci)
                        if ref[0] == "tree":
                            return [operand(s) for s in ref[2]]
                        return None

                    operands = [[operand(r) for r in refs]
                                for _, refs, _ in call_plan]
                    before = {
                        i: [o for reads, plan in ip.plans
                            for o in plan(*[operands[i][p] for p in reads])]
                        for i, (_, _, ip) in enumerate(call_plan)
                        if ip is not None}

                    def body(_, c):
                        vals: Dict[Tuple[int, int], Any] = {}

                        def resolve(ref, op):
                            if ref[0] == "carry":
                                return c[ref[1]]
                            if ref[0] == "val":
                                return vals[ref[1]]
                            if ref[0] == "const":
                                return op
                            return jax.tree.unflatten(
                                ref[1], [resolve(s, o)
                                         for s, o in zip(ref[2], op)])

                        for i, (raw, refs, ip) in enumerate(call_plan):
                            args = [resolve(r, o)
                                    for r, o in zip(refs, operands[i])]
                            out = (raw(*args) if ip is None
                                   else ip.body(*args, *before[i]))
                            for j, o in enumerate(jax.tree.leaves(out)):
                                if (i, j) in used:
                                    vals[(i, j)] = o
                        return tuple(
                            c[ref[1]] if ref[0] == "carry"
                            else ref[1] if ref[0] == "const"
                            else vals[ref[1]] for ref in out_slots)

                    return lax.fori_loop(0, k, body, tuple(carry_t))

                # the whole-loop program dispatches through the
                # _CountedJit choke point like every other device
                # entry: HBM admission control, the OOM-retry ladder
                # and the dispatch counters cover it (an OOM here used
                # to bypass rung 1/2 entirely and only degrade via
                # Iterate's re-plan fallback). counted_jit keeps
                # parallel/mesh.py the single module constructing jits
                # (the choke-point source audit in test_tracing.py)
                return self.mex.counted_jit(loop_fn)

            try:
                fn = self.mex.cached(key, build)
                if built:                        # fresh program: probe
                    fn.lower(tuple(carry), self._fori_consts(),
                             np.int32(k))
            except Exception as e:               # version/topology limits
                self._fori_failed = True
                log = getattr(self.mex, "logger", None)
                if log is not None and log.enabled:
                    log.line(event="loop_fori_unavailable",
                             loop=self.name, error=repr(e)[:200])
                return None
            self._fori = fn
        # the dispatch counter ticks inside _CountedJit.__call__ now
        out = self._fori(tuple(carry), self._fori_consts(), np.int32(k))
        # the calls' plans ran in every iteration, or once where they
        # are hoisted; the whole-loop program's own dispatch counts none
        index_plans = sum(c.fn.index_plans * (1 if i in hoisted else k)
                          for i, c in enumerate(calls))
        self.mex.stats_r2i_index_plans += index_plans
        self.mex.stats_r2i_dense_plans += sum(
            c.fn.dense_plans * (1 if i in hoisted else k)
            for i, c in enumerate(calls))
        # likewise what the calls' traces noted, done in every iteration
        for c in calls:
            self.mex.add_noted(c.fn.noted, runs=k)
        return list(out), index_plans


# ----------------------------------------------------------------------
# carry plumbing
# ----------------------------------------------------------------------

class _LoopCarryNode(DIABase):
    """Source node wrapping the loop-carried shards of one iteration.
    It has no parents, yet starts no pipeline: it joins ``pipe``, the
    pipeline of the loop's input, so that a job that loops stays one
    pipeline in the span records (common/trace.py)."""

    def __init__(self, ctx, shards, pipe: Optional[int] = None) -> None:
        super().__init__(ctx, "LoopCarry")
        if pipe is not None:
            self.pipe = pipe
        self._carry = shards

    def compute(self):
        return self._carry


def _carry_dia(ctx, shards, pipe: Optional[int] = None) -> DIA:
    return DIA(_LoopCarryNode(ctx, shards, pipe))


def _place_carry_leaf(mex, leaf):
    """Where a leaf of a pytree carry lives: replicated over the mesh,
    as the programs of an iteration hand it back (``AllGatherArrays``
    under a recorder, a whole-loop program). A carry that came in on
    one device, or from the host, would make the call that runs the
    whole-loop program with it another compile than the warm one. A
    host leaf is a counted upload."""
    if mex.num_processes > 1:
        return jnp.asarray(leaf)
    if isinstance(leaf, jax.Array):
        return jax.device_put(leaf, mex.replicated)
    return mex._put_replicated(np.asarray(leaf))


def _shards_carry_ids(shards: DeviceShards) -> Tuple[Dict[int, int], int]:
    leaves = jax.tree.leaves(shards.tree)
    ids = {id(l): s for s, l in enumerate(leaves)}
    n = len(leaves)
    if shards._counts_dev is not None and shards._counts_host is None:
        ids[id(shards._counts_dev)] = n
        n += 1
    return ids, n


def _leaf_sig(leaves: Sequence[Any]) -> Tuple:
    return tuple((jnp.dtype(l.dtype), tuple(l.shape)) for l in leaves)


# ----------------------------------------------------------------------
# Iterate
# ----------------------------------------------------------------------

def Iterate(ctx, body: Callable, carry, n: int, *, name: str = "loop",
            checkpoint_every: Optional[int] = None,
            invariants: Sequence[Any] = ()):
    """Run ``body`` ``n`` times with ``carry`` threaded through,
    replaying a captured LoopPlan for iterations 2..N.

    ``carry`` is either a DIA / DeviceShards (``body(dia) -> dia``, the
    Collapse-loop idiom) or a pytree of device arrays (``body(tree) ->
    tree``, the k-means centroid idiom). The body must be
    iteration-index-independent: same graph, same shapes every
    iteration (the capture contract; violations reject the capture and
    fall back to plain per-iteration planning, they cannot corrupt —
    with ONE exception the recorder cannot see: Python control flow on
    a directly-converted eager scalar (``if float(jnp.sum(x)) < eps``)
    bakes the iteration-1 branch into the tape; see the module
    docstring's "known blind spot" and keep convergence checks outside
    ``Iterate``).

    ``checkpoint_every=k`` (DIA/DeviceShards carries only — a pytree
    carry raises) seals the carry into a durable epoch every k
    iterations when the Context has a CheckpointManager
    (THRILL_TPU_CKPT_DIR); a resumed run restores the newest loop epoch
    for ``name`` and continues after it. Returns the final carry in
    the same form it was given (DIA in, DIA out).

    ``invariants`` are what the body reads unchanged in every
    iteration, handed to it after the carry (``body(carry,
    *invariants)``): DIAs (an edge list, a degree table) and host
    values (sizes, small numpy arrays). They are what makes a loop the
    SAME loop in the next call: a body that is a plain function with
    no closure cells, no default arguments and no ``self`` (a
    module-level ``def``) whose tape took everything that differs
    between calls from the carry and the invariant DIAs is kept on the
    mesh, and a later ``Iterate`` of the same body OBJECT, name, carry
    signature, invariant signatures and host values REBINDS it to its
    own invariants and replays from its first iteration: no capture.
    Like ``jax.jit``, the body is keyed by identity and whatever else
    it reads (module globals) is taken as it was at capture. A bound
    method, a ``functools.partial``, a callable object or a function
    made by a factory captures in every call, as before.

    The whole loop runs under one ``stage`` span named ``Iterate``,
    the root of its ``loop`` spans, of the carry's pipeline; a pytree
    carry has none, and the loop joins that of its first invariant
    DIA."""
    if n <= 0:
        return carry
    node = carry.node if isinstance(carry, DIA) \
        else carry if isinstance(carry, DIABase) else None
    if node is None:
        # a carry that is no DIA belongs to no pipeline: the loop joins
        # that of its first invariant DIA, which its body reads in
        # every iteration
        node = next((x.node if isinstance(x, DIA) else x
                     for x in invariants
                     if isinstance(x, (DIA, DIABase))), None)
    if node is not None:
        span = node.stage_span("Iterate")
        pipe = node.pipe
    else:
        span = span_of(getattr(ctx, "tracer", None), "stage", "Iterate")
        pipe = None
    with span:
        return _iterate(ctx, body, carry, n, name, checkpoint_every,
                        tuple(invariants), pipe)


def _iterate(ctx, body, carry, n, name, checkpoint_every, invariants,
             pipe):
    mex = ctx.mesh_exec
    log = ctx.logger
    mgr = getattr(ctx, "checkpoint", None)

    # -- normalize the carry -------------------------------------------
    dia_mode = isinstance(carry, (DIA, DIABase))
    if dia_mode:
        if isinstance(carry, DIABase):
            carry = DIA(carry)
        state = carry._link().pull(consume=True)
    elif isinstance(carry, (DeviceShards, HostShards)):
        dia_mode = True
        state = carry
    else:
        state = jax.tree.map(lambda l: _place_carry_leaf(mex, l), carry)

    if checkpoint_every and not dia_mode:
        # sealing requires the shard-file epoch path (DIA/DeviceShards
        # carries); silently skipping would deliver NO durability the
        # caller asked for — refuse up front instead
        raise ValueError(
            "Iterate(checkpoint_every=...) requires a DIA/DeviceShards "
            "carry; pytree carries cannot be sealed into checkpoint "
            "epochs (wrap the state in a DIA, or drop checkpoint_every)")

    start = 0
    if mgr is not None and checkpoint_every and dia_mode:
        restored = mgr.try_restore_loop(name)
        if restored is not None:
            state, start = restored
            start += 1                       # resume AFTER the epoch

    can_replay = (replay_enabled()
                  and not (mgr is not None and mgr.auto)
                  and (not dia_mode or isinstance(state, DeviceShards)))

    def run_body(st):
        """One plain iteration: st -> next st, through the full pull
        recursion + fusion planner."""
        if dia_mode:
            out = body(_carry_dia(ctx, st, pipe), *invariants)
            if isinstance(out, DIABase):
                out = DIA(out)
            return out._link().pull(consume=True)
        return body(st, *invariants)

    def seal(st, i):
        if mgr is not None and checkpoint_every and dia_mode \
                and (i + 1) % checkpoint_every == 0 and i + 1 < n:
            mgr.save_loop_state(name, i, st)
            return True
        return False

    plan: Optional[LoopPlan] = None
    donate = donation_enabled()
    miss_streak = 0          # consecutive capture misses: a miss is
    # almost always deterministic (eager body math, data-dependent
    # plan, W>1 shuffle) — re-attempting burns a full carry copy +
    # recorder pass per iteration; two strikes and the rest of the
    # loop runs plain (one retry tolerates a first iteration whose
    # carry shape was still stabilizing)
    # plan-store loop-tape metadata: the remembered capture outcome
    # for this (name, carry-signature) loop — a clean tape's digests
    # let the capture skip its taint re-traces, a known-uncapturable
    # loop skips the capture probes entirely
    tape_token = _tape_token(name, dia_mode, state, body) \
        if can_replay else None
    tape_seed = None
    seed_mode: Optional[str] = None
    last_miss: Dict[str, str] = {}
    if tape_token is not None:
        from ..data.exchange import plan_seed as _plan_seed
        tape_seed = _plan_seed(mex, "loop_tape", tape_token)
        if isinstance(tape_seed, dict) \
                and tape_seed.get("capture") is False:
            # warm restart: this loop previously rejected capture for
            # a deterministic reason — run plain from iteration 1,
            # skipping the probes (each a full carry copy + recorder
            # pass). LOUD: logged with the remembered reason; if the
            # body changed enough to capture now, its carry signature
            # almost always changed too (fresh token, no seed).
            miss_streak = 2
            seed_mode = "nocapture"
            _note_tape(mex, tape_token, tape_seed)
            if log.enabled:
                log.line(event="loop_seed_nocapture", loop=name,
                         reason=str(tape_seed.get("reason", "?"))[:200])
            tape_seed = None
    report = {"name": name, "iters": n - start, "captures": 0, "replays": 0,
              "fori_iters": 0, "fallbacks": 0, "capture_s": 0.0,
              "replay_s": 0.0, "calls": 0, "pruned": 0,
              "donated_bytes0": mex.stats_loop_donated_bytes}
    tracer = getattr(ctx, "tracer", None)
    tr_on = tracer is not None and tracer.enabled
    fresh_plan, ckpt = True, False
    # the tape of an earlier call of this very loop, rebound to this
    # call's invariants: no capture at all
    inv_leaves = share_token = None
    if tape_token is not None and miss_streak < 2 \
            and not (checkpoint_every and mgr is not None) \
            and _carries_nothing(body):
        inv_leaves = _invariant_leaves(invariants)
        share_token = _share_token(body, tape_token, state, invariants,
                                   inv_leaves)
    kept = mex.loop_plans.get(share_token) \
        if share_token is not None else None
    if kept is not None:
        sp = (tracer.begin("loop", "rebind", loop=name)
              if tr_on else None)
        try:
            kept.bind([leaves for leaves, _ in inv_leaves])
            plan = kept
            mex.stats_loop_plan_rebinds += 1
            report["rebound"] = True
        except Exception as e:
            # LOUD: the kept tape's prologue failed on this call's
            # buffers; forget it and capture afresh
            kept.unbind()
            del mex.loop_plans[share_token]
            mex.stats_loop_fallbacks += 1
            report["fallbacks"] += 1
            faults.note("recovery", what="loop_rebind", loop=name,
                        error=repr(e)[:200])
            if log.enabled:
                log.line(event="loop_rebind_fallback", loop=name,
                         error=repr(e)[:200])
        finally:
            if sp is not None:
                tracer.end(sp, calls=len(kept.prologue))
    bound = [kept] if kept is not None else []
    i = start
    try:
        while i < n:
            if plan is None:
                # ---- capture (or plain) iteration ------------------------
                t0 = time.perf_counter()
                d0 = mex.stats_dispatches
                sp = (tracer.begin("loop", "capture", loop=name, iter=i)
                      if tr_on else None)
                try:
                    if can_replay and miss_streak < 2:
                        state, plan = _capture(
                            ctx, run_body, state, name=name, it=i,
                            seed=tape_seed, info=last_miss,
                            inv_leaves=inv_leaves
                            if share_token is not None else None)
                        if plan is not None:
                            bound.append(plan)
                            if share_token is not None:
                                _keep_plan(mex, share_token, plan, log)
                            miss_streak = 0
                            mex.stats_loop_plan_builds += 1
                            report["captures"] += 1
                            report["calls"] = len(plan.calls)
                            report["pruned"] = (plan.pruned_invariant
                                                + plan.pruned_dead)
                            if plan.seeded:
                                seed_mode = "tape"
                            elif plan.seed_stale:
                                seed_mode = "stale"
                            if tape_token is not None:
                                _note_tape(mex, tape_token, plan.meta)
                        else:
                            miss_streak += 1
                            if miss_streak >= 2 and tape_token is not None:
                                # deterministic reject: remember it so a
                                # warm restart skips the capture probes
                                _note_tape(mex, tape_token, {
                                    "capture": False,
                                    "reason": last_miss.get("reason",
                                                            "?")[:200]})
                    else:
                        state = run_body(state)
                finally:
                    if sp is not None:
                        tracer.end(sp, mode=("capture" if plan is not None
                                             else "plain"))
                dt = time.perf_counter() - t0
                report["capture_s"] += dt
                if log.enabled:
                    log.line(event="iteration", loop=name, iter=i,
                             mode="capture" if plan is not None else "plain",
                             seconds=round(dt, 6),
                             dispatches=mex.stats_dispatches - d0,
                             plan_calls=(len(plan.calls)
                                         if plan is not None else None))
                ckpt = seal(state, i)
                i += 1
                fresh_plan = True
                continue

            # ---- replayed iterations -------------------------------------
            leaves, treedef = _carry_leaves(state, dia_mode, plan)
            if leaves is None:
                plan = None                      # carry shape drifted
                continue
            remaining = n - i
            # whole-loop lowering: only when no checkpoint epoch is due
            # inside the window (an epoch needs the carry on the host) —
            # checkpoint_every without a CheckpointManager seals nothing,
            # so it must not cost the fori lowering either
            fori_ok = fori_enabled() \
                and not (checkpoint_every and mgr is not None) \
                and plan.fori_eligible() and remaining > 1
            t0 = time.perf_counter()
            d0 = mex.stats_dispatches
            sp = (tracer.begin("loop", "replay", loop=name, iter=i)
                  if tr_on else None)
            try:
                try:
                    if faults.REGISTRY.active():
                        faults.check(_F_REPLAY, loop=name, iter=i)
                    if fori_ok:
                        ran = plan.run_fori(leaves, remaining)
                        if ran is not None:
                            out, index_plans = ran
                            mex.stats_loop_fori_iters += remaining
                            report["fori_iters"] += remaining
                            state = _rebuild_carry(out, treedef, dia_mode,
                                                   mex, plan)
                            dt = time.perf_counter() - t0
                            report["replay_s"] += dt
                            if sp is not None:
                                sp.attrs["fori_iters"] = remaining
                                sp.attrs["index_plans"] = index_plans
                            if log.enabled:
                                log.line(event="loop_replay", loop=name,
                                         iter=i, iters=remaining, fori=True,
                                         seconds=round(dt, 6))
                            i = n
                            continue
                    out = plan.replay(
                        leaves,
                        donate and not faults.REGISTRY.active(),
                        donate_carry=not fresh_plan and not ckpt)
                except Exception as e:
                    # LOUD degradation: a failed replayed dispatch falls
                    # back to full re-planning for this iteration (the body
                    # path, which re-captures); the loop slows down, it
                    # never lies. Unless donation already consumed part of
                    # the carry mid-iteration — then there is nothing to
                    # re-plan FROM, and the only honest outcome is a clear
                    # error, not a deleted-array crash deep inside the pull
                    # recursion.
                    if sp is not None:
                        sp.attrs["error"] = repr(e)[:200]
                    if any(getattr(l, "is_deleted", lambda: False)()
                           for l in leaves):
                        raise RuntimeError(
                            f"loop '{name}' iteration {i}: a replayed "
                            f"dispatch failed after part of the loop carry "
                            f"was donated; cannot degrade to re-planning. "
                            f"Re-run with THRILL_TPU_LOOP_DONATE=0 (or "
                            f"from the last checkpoint epoch).") from e
                    mex.stats_loop_fallbacks += 1
                    report["fallbacks"] += 1
                    faults.note("recovery", what="loop_replay", loop=name,
                                iter=i, error=repr(e)[:200])
                    if log.enabled:
                        log.line(event="loop_replay_fallback", loop=name,
                                 iter=i, error=repr(e)[:200])
                    if share_token is not None:
                        # a tape that failed serves no later call either
                        mex.loop_plans.pop(share_token, None)
                    plan = None
                    continue
                mex.stats_loop_replays += 1
                report["replays"] += 1
                state = _rebuild_carry(out, treedef, dia_mode, mex, plan)
                dt = time.perf_counter() - t0
                report["replay_s"] += dt
                if log.enabled:
                    log.line(event="loop_replay", loop=name, iter=i,
                             dispatches=mex.stats_dispatches - d0,
                             seconds=round(dt, 6))
                ckpt = seal(state, i)
                fresh_plan = False
                i += 1
            finally:
                if sp is not None:
                    tracer.end(sp)

    finally:
        # a kept plan must not pin this call's tables until the next
        for p in bound:
            p.unbind()
    report["donated_bytes"] = (mex.stats_loop_donated_bytes
                               - report.pop("donated_bytes0"))
    if seed_mode is not None:
        # plan-store tape-metadata outcome: "tape" (trusted, analysis
        # skipped), "stale" (digest mismatch, fresh analysis),
        # "nocapture" (known-uncapturable, probes skipped)
        report["seed"] = seed_mode
    mex.loop_reports.append(report)
    if log.enabled:
        log.line(event="loop_done", **{k: (round(v, 6)
                                           if isinstance(v, float) else v)
                                       for k, v in report.items()})
    if dia_mode:
        return _carry_dia(ctx, state, pipe)
    return state


def _tape_token(name: str, dia_mode: bool, state,
                body) -> Optional[Tuple]:
    """Plan-store identity of one loop's tape: name + the BODY's
    canonical identity (module.qualname + bytecode hash — two loops
    sharing the default name must not share a tape record, or an
    uncapturable sibling's ``capture: False`` would force a capturable
    one to run plain forever) + carry signature (leaf dtypes/shapes,
    capacity, counts mode). None when the carry cannot be signed
    (host storage, conversion failure)."""
    from ..data.exchange import _canon
    try:
        body_id = _canon(body)
        if dia_mode:
            if not isinstance(state, DeviceShards):
                return None
            sig = (_leaf_sig(jax.tree.leaves(state.tree)), state.cap,
                   state._counts_host is not None)
        else:
            sig = (_leaf_sig(jax.tree.leaves(state)),)
    except Exception:
        return None
    return ("loop_tape", name, bool(dia_mode), body_id, sig)


def _invariant_leaves(invariants) -> Optional[List[Tuple[List[Any], Tuple]]]:
    """Each invariant DIA's materialized buffers, in the order the
    tape numbers them, with their signature; None where one is not
    device-resident. A rebound tape never pulls the invariants, so a
    deferred validation they owe runs here."""
    out = []
    for x in invariants:
        node = x.node if isinstance(x, DIA) else x
        if not isinstance(node, DIABase):
            continue
        shards = node.materialize(consume=False)
        if not isinstance(shards, DeviceShards):
            return None
        shards.validate_pending()
        leaves = list(jax.tree.leaves(shards.tree))
        counts = shards._counts_host
        if counts is None:
            leaves.append(shards._counts_dev)
        out.append((leaves, (
            _leaf_sig(leaves), str(jax.tree.structure(shards.tree)),
            shards.cap,
            None if counts is None else tuple(int(c) for c in counts))))
    return out


def _carries_nothing(body) -> bool:
    """May a tape of ``body`` serve another call? Only where the body
    object IS the whole of the body: a plain function with no closure
    cells, no default arguments and no ``self``. A bound method
    forwards ``__code__`` and ``__closure__`` to its function and
    differs only in ``__self__``; a factory-made ``def body(c, x, a=a)``
    differs only in ``__defaults__``: two such bodies have one
    bytecode and would replay each other's constants."""
    return (type(body) is types.FunctionType
            and not body.__closure__
            and not body.__defaults__
            and not body.__kwdefaults__)


def _share_token(body, tape_token, state, invariants, inv_leaves):
    """What makes two ``Iterate`` calls the same loop: the body OBJECT
    (a function hashes and compares by identity, and the key holds it,
    so no other function can take its place: what ``jax.jit`` does; the
    bytecode hash in ``tape_token`` merges plan-store records and is no
    identity), the name and the carry's signature, the invariant DIAs'
    signatures (host-known counts by value: the tape bakes them), the
    host invariants by content, the carry's host counts. None where
    one of them cannot be signed: the tape then serves its own call
    alone."""
    if inv_leaves is None:
        return None
    host = []
    for x in invariants:
        if isinstance(x, (DIA, DIABase)):
            continue
        if isinstance(x, np.ndarray) and x.nbytes <= 4096:
            host.append((x.dtype.str, x.shape, x.tobytes()))
        elif isinstance(x, (bool, int, float, str, bytes, type(None),
                            np.generic)):
            host.append((type(x).__name__, repr(x)))
        else:
            return None
    counts = getattr(state, "_counts_host", None)
    return (body, tape_token, tuple(sig for _, sig in inv_leaves),
            tuple(host),
            None if counts is None else tuple(int(c) for c in counts))


def _keep_plan(mex, token, plan: "LoopPlan", log) -> None:
    """Keep a freshly captured tape on the mesh for the next call of
    the same loop, or say why not."""
    if plan.unshared is not None:
        if log.enabled:
            log.line(event="loop_tape_unshared", loop=plan.name,
                     reason=plan.unshared)
        return
    kept = mex.loop_plans
    while len(kept) >= 32:
        del kept[next(iter(kept))]
    kept[token] = plan


def _capture(ctx, run_body, state, name="loop", it=0, seed=None,
             info=None, inv_leaves=None):
    """Run one body iteration with the tape recorder installed.
    Returns (next_state, LoopPlan or None). ``seed`` is the plan
    store's remembered tape metadata (LoopPlan trusts a digest match);
    ``info`` (dict) receives the miss reason for the caller's own
    metadata bookkeeping; ``inv_leaves`` are the invariant DIAs'
    buffers (:func:`_invariant_leaves`) where the loop may be kept for
    later calls."""
    mex = ctx.mesh_exec
    log = ctx.logger

    def miss(reason, out_state):
        if info is not None:
            info["reason"] = reason
        if log.enabled:
            log.line(event="loop_capture_miss", loop=name, iter=it,
                     reason=reason)
        return out_state, None

    # De-alias the carry before recording: classification is by buffer
    # IDENTITY, so a carry leaf sharing its buffer with a closure
    # constant of the body (or with another carry slot) would record a
    # lying ("carry", s) ref for the constant — every leaf gets a
    # fresh buffer only the carry can be holding. One eager copy per
    # capture, nothing per replay.
    try:
        if isinstance(state, DeviceShards):
            state.tree = jax.tree.map(jnp.copy, state.tree)
            if state._counts_dev is not None \
                    and state._counts_host is None:
                state._counts_dev = jnp.copy(state._counts_dev)
        else:
            leaves = jax.tree.leaves(state)
            if not all(isinstance(l, jax.Array) for l in leaves):
                return miss("carry is not device-resident",
                            run_body(state))
            state = jax.tree.map(jnp.copy, state)
    except Exception as e:                 # non-addressable shards
        return miss(f"carry copy failed ({e!r})", run_body(state))
    if isinstance(state, DeviceShards):
        carry_ids, n_carry = _shards_carry_ids(state)
    else:
        leaves = jax.tree.leaves(state)
        carry_ids = {id(l): s for s, l in enumerate(leaves)}
        n_carry = len(leaves)
    bufs = [leaves for leaves, _ in inv_leaves or ()]
    rec = _Recorder(
        carry_ids, known=list(jax.live_arrays()),
        inv_ids={id(l): (k, j) for k, leaves in enumerate(bufs)
                 for j, l in enumerate(leaves)},
        small_cache=mex._put_small_cache)
    prev = mex.loop_recorder
    if prev is not None:
        # nested Iterate inside a capturing body: the inner loop's
        # dispatches bypass the OUTER recorder (this capture replaces
        # it), so the outer tape would silently skip the whole inner
        # loop on replay — dirty the outer capture so it rejects
        # loudly; the inner loop may still capture for itself
        prev.dirty = "nested Iterate inside a capturing body"
    mex.loop_recorder = rec
    try:
        out_state = run_body(state)
    finally:
        mex.loop_recorder = prev
    if rec.dirty is not None:
        return miss(rec.dirty, out_state)
    if mex._pending_checks:
        # an unresolved deferred validation (un-drained hinted-join
        # overflow check) cannot be replayed — it would never run
        return miss("pending deferred validations", out_state)

    # map the produced carry back onto the tape
    host_counts = None
    if isinstance(out_state, DeviceShards):
        if not isinstance(state, DeviceShards):
            return miss("carry storage changed", out_state)
        out_leaves = jax.tree.leaves(out_state.tree)
        in_leaves = jax.tree.leaves(state.tree)
        if _leaf_sig(out_leaves) != _leaf_sig(in_leaves) \
                or out_state.cap != state.cap \
                or (jax.tree.structure(out_state.tree)
                    != jax.tree.structure(state.tree)):
            return miss("carry schema/shape drifted", out_state)
        if state._counts_host is not None:
            # host-known input counts were baked into the tape's
            # dispatches as blessed constants — they must provably hold
            # for EVERY iteration's input, i.e. the body must hand the
            # same host counts back (then by induction every replay's
            # input matches the baked values); a count-changing body
            # with stable leaf shapes/cap would otherwise replay a
            # silently wrong valid mask
            if out_state._counts_host is None:
                return miss("carry counts went device-resident across "
                            "the iteration (baked host count constants "
                            "cannot be checked)", out_state)
            if not np.array_equal(np.asarray(state._counts_host),
                                  np.asarray(out_state._counts_host)):
                return miss("carry counts changed across the iteration "
                            "(baked count constants would lie on "
                            "replay)", out_state)
        if out_state._counts_host is not None:
            host_counts = out_state._counts_host
        else:
            out_leaves = out_leaves + [out_state._counts_dev]
    elif isinstance(out_state, HostShards):
        return miss("body produced host storage", out_state)
    else:
        out_leaves = jax.tree.leaves(out_state)
        if _leaf_sig(out_leaves) != _leaf_sig(jax.tree.leaves(state)) \
                or (jax.tree.structure(out_state)
                    != jax.tree.structure(state)):
            return miss("carry schema/shape drifted", out_state)
    carry_out = []
    for leaf in out_leaves:
        if id(leaf) in rec.produced:
            carry_out.append(("val", rec.produced[id(leaf)]))
        elif id(leaf) in carry_ids:
            carry_out.append(("carry", carry_ids[id(leaf)]))
        else:
            return miss("carry leaf produced outside the recorded "
                        "dispatch stream (eager host math in the "
                        "body?)", out_state)
    plan = LoopPlan(mex, rec.calls, carry_out, n_carry, name=name,
                    plan_reads=rec.plan_reads, seed=seed, inv_leaves=bufs,
                    unshared=rec.unshared)
    if plan.seed_stale and log.enabled:
        # stale plan-store metadata: LOUD, and the full fresh
        # analysis just ran — the seed cost nothing but this line
        log.line(event="loop_seed_stale", loop=name, iter=it)
    if plan.invalid is not None:
        return miss(plan.invalid, out_state)
    if host_counts is not None:
        plan.counts = host_counts.copy()
    if log.enabled:
        log.line(event="loop_plan", loop=name, calls=len(plan.calls),
                 pruned_invariant=plan.pruned_invariant,
                 pruned_dead=plan.pruned_dead,
                 fori=plan.fori_eligible(),
                 seeded=plan.seeded or None,
                 donatable=sum(len(c.donate_pos) for c in plan.calls))
    return out_state, plan


def _carry_leaves(state, dia_mode, plan):
    """Current carry as tape-slot-ordered leaves (the capture's input
    convention); (None, None) when the state no longer matches."""
    if dia_mode:
        leaves = list(jax.tree.leaves(state.tree))
        treedef = jax.tree.structure(state.tree)
        if plan.n_carry == len(leaves) + 1:
            # the tape threads device-resident counts as a carry slot
            leaves.append(state.counts_device())
        elif plan.n_carry != len(leaves):
            return None, None
        return leaves, treedef
    leaves = jax.tree.leaves(state)
    if len(leaves) != plan.n_carry:
        return None, None
    return leaves, jax.tree.structure(state)


def _rebuild_carry(out_leaves, treedef, dia_mode, mex, plan):
    if not dia_mode:
        return jax.tree.unflatten(treedef, out_leaves)
    if plan.counts is not None:
        tree = jax.tree.unflatten(treedef, out_leaves)
        return DeviceShards(mex, tree, plan.counts.copy())
    tree = jax.tree.unflatten(treedef, out_leaves[:-1])
    return DeviceShards(mex, tree, out_leaves[-1])
