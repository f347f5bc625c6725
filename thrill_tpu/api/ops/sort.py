"""Distributed sample sort.

Reference: thrill/api/sort.hpp:64 — PreOp reservoir-samples while
spilling; MainOp gathers samples on worker 0, picks p-1 splitters,
classifies every item down a branchless splitter tree into per-worker
stream writers (tie-break by global index for balance on equal keys,
api/sort.hpp:487-502); receivers sort runs and multiway-merge.

TPU-native design, bulk-synchronous device programs in which the
payload is gathered exactly ONCE per phase and only (validity, key
words, global index) flow through sort networks:
 1. keys:     local argsort of the key words + quantile sampling —
              outputs the permutation, sorted words and samples, with
              NO payload movement (the worker-0 splitter step collapses
              to the single controller). W == 1 finishes here with a
              single payload gather.
 2. classify: destination = lexicographic rank among splitters
              ((words, index) compare, so duplicate keys spread evenly
              across workers exactly like the reference's tie-break).
              Items are already key-sorted, so destinations are
              MONOTONE — destination grouping needs no second sort; the
              same program gathers the payload once (by the phase-1
              permutation) and the planned all-to-all ships it.
 3. merge:    one local sort of the received (words, index) pairs +
              one payload gather — the analog of sort-runs + multiway
              merge (received runs are rank-ordered and internally
              sorted; the chunked engine exploits tile sortedness).

The result is globally sorted across worker ranks and stable: equal
keys keep their original global order, making Sort and SortStable one
code path (the reference needs a separate CatStream variant).
"""

from __future__ import annotations

from typing import Callable, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ...core import keys as keymod
from ...data import exchange
from ...data.shards import DeviceShards, HostShards
from ...parallel.mesh import AXIS
from ..dia import DIA
from ..dia_base import DIABase
from ...common.partition import dense_range_bounds

OVERSAMPLE = 32  # samples per worker; splitter error ~ 1/OVERSAMPLE


def quantile_positions(count, cap: int):
    """Traced helper: OVERSAMPLE quantile positions over the valid
    prefix [0, count) of a sorted column (clipped to [0, cap))."""
    count_f = jnp.maximum(count, 1)
    qpos = ((jnp.arange(OVERSAMPLE, dtype=jnp.int64) * 2 + 1)
            * count_f // (2 * OVERSAMPLE))
    return jnp.clip(qpos, 0, cap - 1)


def choose_splitters(samples, W: int, ncols: int) -> np.ndarray:
    """Host helper: W-1 equidistant splitters from SORTED sample tuples
    (each a flat tuple of ints, ncols wide) -> uint64 matrix
    [max(W-1,1), ncols]. The worker-0 splitter step collapsed to the
    single controller (reference: FindAndSendSplitters,
    api/sort.hpp:337-378)."""
    splitters = np.zeros((max(W - 1, 1), ncols), dtype=np.uint64)
    if samples and W > 1:
        for j in range(1, W):
            s = samples[min(len(samples) - 1, (j * len(samples)) // W)]
            splitters[j - 1] = np.array(s, dtype=np.uint64)
    return splitters


class SortNode(DIABase):
    # EM operator: asks the stage negotiation for as much worker RAM as
    # available (reference: SortNode uses DIAMemUse::Max for its
    # ReceiveItems capacity, api/sort.hpp MainOp + dia_base.cpp:121-270)
    MEM_USE = "max"

    def __init__(self, ctx, link, key_fn: Optional[Callable],
                 compare_fn: Optional[Callable], stable: bool) -> None:
        super().__init__(ctx, "Sort", [link])
        self.key_fn = key_fn or (lambda x: x)
        self.compare_fn = compare_fn
        self.stable = stable

    def _fuse_segment(self):
        """W == 1 local sort (key-only argsort + one payload gather) as
        a fused segment. The W > 1 sample sort needs its splitter
        agreement and all-to-all — a fusion barrier — and stays on the
        phased path."""
        from .. import fusion
        from ...core import host_radix
        if self.context.num_workers != 1 or self.compare_fn is not None \
                or host_radix.eligible(self.context.mesh_exec):
            return None
        key_fn = self.key_fn

        def trace(fctx, tree, mask, _bound):
            count = jnp.sum(mask.astype(jnp.int32))
            return (_sort_rows_local(key_fn, tree, mask),
                    jnp.arange(mask.shape[0]) < count)

        return fusion.Segment(label="Sort",
                              token=("sort_w1_fused", self.key_fn),
                              trace=trace, preserves_counts=True,
                              already_compact=True, dia_id=self.id)

    def compute_plan(self):
        from .. import fusion
        seg = self._fuse_segment()
        if seg is None:
            return None
        plan = fusion.pull_plan(self.parents[0])
        if not plan.stitchable:
            return fusion.wrap(self._compute_on(plan.finish()))
        plan.append(seg)
        return plan

    def compute(self):
        plan = self.compute_plan()
        if plan is not None:
            return plan.finish()
        return self._compute_on(self.parents[0].pull())

    def _compute_on(self, shards):
        if isinstance(shards, HostShards):
            return self._compute_host(shards)
        if self.compare_fn is not None:
            return self._compute_host(shards.to_host_shards("sort-compare-fn"))
        return _device_sample_sort(shards, self.key_fn,
                                   (self.key_fn,))

    # above this many items the host path sorts external-memory style:
    # sorted runs spilled to Files, k-way merged (reference:
    # SortAndWriteToFile + PartialMultiwayMerge, api/sort.hpp:665-699,
    # 216-271). Overridable for tests via THRILL_TPU_HOST_SORT_RUN.
    HOST_RUN_SIZE = 1 << 20

    def _compute_host(self, shards: HostShards):
        # multi-controller: the EM/in-memory host sort needs the global
        # item stream; replicate, compute identically, keep local lists
        from ...data import multiplexer
        mex = self.context.mesh_exec
        if multiplexer.multiprocess(mex):
            rep = multiplexer.ensure_replicated(mex, shards, "sort-host")
            return multiplexer.localize(mex, self._compute_host_impl(rep))
        return self._compute_host_impl(shards)

    def _compute_host_impl(self, shards: HostShards):
        import functools
        import os
        W = shards.num_workers
        if self.compare_fn is not None:
            sort_key = functools.cmp_to_key(
                lambda a, b: -1 if self.compare_fn(a, b)
                else (1 if self.compare_fn(b, a) else 0))
        else:
            sort_key = self.key_fn

        run_size = int(os.environ.get("THRILL_TPU_HOST_SORT_RUN") or
                       self._granted_run_size(shards))
        run_size = max(run_size, 16)
        self._granted_run_size_last = run_size
        n = shards.total
        if n <= run_size:
            items = [it for l in shards.lists for it in l]
            items.sort(key=sort_key)
            bounds = dense_range_bounds(n, W).tolist()
            return HostShards(W, [items[bounds[w]:bounds[w + 1]]
                                  for w in range(W)])
        try:
            return HostShards(W, self._em_sort(shards, sort_key,
                                               run_size, W))
        except (TypeError, ValueError, AttributeError):
            # unpicklable items cannot spill; fall back in-memory
            items = [it for l in shards.lists for it in l]
            items.sort(key=sort_key)
            bounds = dense_range_bounds(n, W).tolist()
            return HostShards(W, [items[bounds[w]:bounds[w + 1]]
                                  for w in range(W)])

    def _granted_run_size(self, shards: HostShards) -> int:
        """In-RAM run capacity in items from the negotiated grant.

        The reference sizes its ReceiveItems capacity from the granted
        RAM over the item size (api/sort.hpp:665-699); host items here
        are Python objects spilled pickled, so the estimate probes the
        first item's pickled size (plus interpreter overhead)."""
        if not self.mem_limit:
            return self.HOST_RUN_SIZE
        first = next((it for l in shards.lists for it in l), None)
        if first is None:
            return self.HOST_RUN_SIZE
        try:
            import pickle
            est = len(pickle.dumps(
                first, protocol=pickle.HIGHEST_PROTOCOL)) + 64
        except Exception:
            est = 256
        return max(16, min(self.mem_limit // est, 1 << 26))

    def _em_sort(self, shards: HostShards, sort_key, run_size: int,
                 W: int):
        """External-memory sort: spill sorted runs, k-way merge them.

        A growing reservoir samples the stream while it spills
        (reference: ReservoirSamplingGrow in the Sort PreOp,
        api/sort.hpp:303) and yields W-1 splitters; the k-way merge then
        streams STRAIGHT into splitter-partitioned per-worker output
        lists — the merged sequence is never materialized twice.

        The phases run as an OVERLAPPED pipeline, not a blocking
        ladder (the foxxll analog this repo's out-of-core tier is
        built on): each completed run's sort+serialize+flush rides the
        bounded write-behind writer (data/writeback.py) so run k+1
        encodes while run k flushes — a writer failure re-raises on
        this thread at the next spill or the pre-merge barrier, never
        silent loss — and the k-way merge gives every run one block of
        readahead so the winner's next block is resident before the
        tournament needs it. ``THRILL_TPU_WRITEBACK=0`` /
        ``THRILL_TPU_PREFETCH=0`` restore the synchronous ladder
        byte-identically (same results, same spill-file naming).

        When this node owns the input exclusively (the consuming pull
        disposed the parent), shard lists are released as they spill so
        the spilled copy replaces — not duplicates — the resident items.
        """
        from ...common import faults
        from ...common.decisions import record_of, resolve_io_prefetch
        from ...common.iostats import IO as _IOSTATS, hit_rate, \
            overlap_frac
        from ...common.sampling import ReservoirSamplingGrow
        from ...data import records as native_records
        from ...data.block_pool import spill_pool
        from ...data.writeback import AsyncWriter, make_readahead
        from ...core import native_merge, order_key
        from ...core.multiway_merge import multiway_merge_files
        from ...vfs.file_io import prefetch_depth

        owns_input = self.parents[0].node.state == "DISPOSED"
        mex = self.context.mesh_exec
        io_base = _IOSTATS.snapshot()
        # spilled-run store keeps a quarter of the grant resident
        # before evicting runs to disk
        pool = spill_pool(self.context.config.spill_dir,
                          self.mem_limit)
        # resumable runs (core/em_runs.py): with checkpointing on, each
        # spilled run commits a CRC'd manifest under the checkpoint
        # dir; a relaunch with resume reloads committed runs instead of
        # re-sorting them (identity-checked — slot, position range,
        # first-item fingerprint). None when ctx.checkpoint is None or
        # THRILL_TPU_EM_RESUME=0: zero overhead on the default path.
        from ...core import em_runs
        run_store = em_runs.store_for(
            self.context, node_id=self.id, label=self.label, W=W,
            run_size=run_size, total=shards.total)
        sampler = ReservoirSamplingGrow(np.random.default_rng(17))
        # items carry their stream position: the (key, position)
        # tiebreak makes the EM sort stable AND lets splitters cut
        # inside equal-key runs, so low-cardinality keys cannot pile
        # every duplicate onto one worker (the reference breaks splitter
        # ties by global index the same way, api/sort.hpp:487-502)
        pair_key = lambda t: (sort_key(t[1]), t[0])  # noqa: E731
        # native merge path: when the key schema byte-encodes
        # (core/order_key.py), runs sort by raw key bytes and the merge
        # selection loop runs in C++ (native/mwmerge.cpp) instead of
        # heapq + per-item Python key calls. ``enc`` is probed from the
        # first item and demoted to None on ANY schema deviation —
        # item files always hold plain (pos, item) records in key
        # order, so runs spilled before a demotion merge fine on the
        # generic path.
        enc = None
        enc_state = "probe" if native_merge.available() else "off"
        enc_arr = None      # vectorized S-array encoder (int/str)
        files = []          # item Files, (pos, item) records
        key_files = []      # parallel key-byte Files (native path)
        run = []            # native: (kb, pos, item); generic: (pos, item)
        # columnar run state (native fast path): kb rows live in S-w
        # numpy arrays, items in a parallel list, positions implicit
        # (col_pos0 + index) — zero per-item Python objects until the
        # vectorized spill. Any batch the array encoder can't handle
        # exactly folds the columnar state into `run` tuples and
        # continues on the listcomp path; a full schema deviation
        # demotes to the generic engine as before.
        col_arrs: list = []
        col_items: list = []
        col_pos0 = 0
        # native-record spiller: when the ITEMS themselves vectorize
        # into fixed-dtype columns (data/records.py schema probe), a
        # fully-columnar run spills through _records_job — the payload
        # encode, memcmp argsort, pos+payload gather and block handoff
        # ALL run inside the write-behind job, off the main thread's
        # critical path, and the native calls release the GIL so the
        # writer genuinely overlaps the next run's encode. A run the
        # encoder cannot represent exactly degrades to the per-item
        # path inside the job (never wrong data); the key-columnar
        # state is unaffected.
        rec_probe = "probe"
        rec_enc = None
        pos = 0
        # real-memory feedback: run_size is an ESTIMATE from one
        # pickled item; the RSS budget is ground truth and spills the
        # run early when actual interpreter growth passes the grant
        # (reference: ReceiveItems spills on mem::memory_exceeded,
        # api/sort.hpp:679)
        from ...data.file import DEFAULT_BLOCK_ITEMS, File
        from ...mem.manager import RssBudget
        budget = RssBudget(self.mem_limit or 0)

        def run_len():
            return len(run) + len(col_items)

        def decolumnize():
            """Fold columnar batches into (kb, pos, item) tuples so the
            mixed-width tuple path can continue the run."""
            nonlocal col_arrs, col_items, col_pos0
            p = col_pos0
            for arr in col_arrs:
                w_ = arr.dtype.itemsize
                raw = arr.tobytes()     # raw memory: no NUL stripping
                n_ = len(arr)
                run.extend(zip(
                    (raw[i * w_:(i + 1) * w_] for i in range(n_)),
                    range(p, p + n_), col_items[p - col_pos0:
                                                p - col_pos0 + n_]))
                p += n_
            col_arrs, col_items, col_pos0 = [], [], 0

        # write-behind spill: each completed run's sort+serialize+write
        # is ONE FIFO job on the bounded writer — run k+1's encode (the
        # main thread) overlaps run k's argsort/disk-write (GIL-
        # releasing; the job's pickle fraction is not, and bounds the
        # wall-clock win — ARCHITECTURE "Out-of-core storage tier").
        # Slots are reserved at submit so run order in ``files`` is
        # the arrival order regardless of who executes.
        writer = AsyncWriter("em_sort.spill")

        def _widen_concat(arrs):
            """One S-W key array from per-batch arrays of possibly
            different widths (str batches pad to their own max): widen
            with zero pads — order-safe by the padding argument in
            order_key make_array_batch_encoder."""
            W_ = max(a.dtype.itemsize for a in arrs)
            for j, a in enumerate(arrs):
                w_ = a.dtype.itemsize
                if w_ != W_:
                    buf = np.zeros((len(a), W_), np.uint8)
                    buf[:, :w_] = a.view(np.uint8).reshape(
                        len(a), w_)               # zero-copy source
                    arrs[j] = buf.reshape(-1).view(f"S{W_}")
            return arrs[0] if len(arrs) == 1 else np.concatenate(arrs)

        def _columnar_job(arrs, items_, p0, slot, meta=None):
            def job():
                b0 = pool.bytes_put
                arr = _widen_concat(arrs)
                order = np.argsort(arr)
                f = File(pool=pool)
                with f.writer() as w:
                    for i in order.tolist():
                        w.put((p0 + i, items_[i]))
                kf = File(pool=pool)
                native_merge.write_key_chunks_fixed(kf, arr[order])
                files[slot] = f
                key_files[slot] = kf
                if meta is not None:
                    run_store.submit_commit(slot, *meta, f, kf)
                return pool.bytes_put - b0
            return job

        def _records_job(arrs, items_, p0, slot, meta=None):
            """Native-records spill: the whole encode — vectorized
            payload columns, memcmp argsort, pos/payload gather, block
            handoff — runs INSIDE the write-behind job, so the main
            thread pays nothing beyond handing over the item list it
            already held, and the native calls (native/records.cpp)
            release the GIL for the job's heavy part. Any encode
            failure (schema deviation inside the run, injected
            ``data.records.encode``, or real) DEGRADES to the per-item
            pickle path on the same data — slower, never wrong, never
            poisons."""
            def job():
                b0 = pool.bytes_put
                arr = _widen_concat(arrs)
                order = native_records.argsort_rows(arr)
                f = File(pool=pool)
                enc = None
                try:
                    enc = rec_enc(items_)
                    if enc is not None:
                        native_records.write_run_blocks(
                            f, order, p0, enc[1], enc[0],
                            f.block_items)
                except Exception as e:
                    faults.note("recovery",
                                what="records.encode_degraded",
                                error=repr(e)[:200])
                    f.clear()
                    f = File(pool=pool)
                    enc = None
                if enc is None:
                    with f.writer() as w:
                        for i in order.tolist():
                            w.put((p0 + i, items_[i]))
                kf = File(pool=pool)
                native_merge.write_key_chunks_fixed(
                    kf, native_records.gather_rows(arr, order))
                files[slot] = f
                key_files[slot] = kf
                if meta is not None:
                    run_store.submit_commit(slot, *meta, f, kf)
                return pool.bytes_put - b0
            return job

        def _encoded_job(this_run, slot, meta=None):
            def job():
                b0 = pool.bytes_put
                this_run.sort()          # kb unique (pos suffix): pure
                f = File(pool=pool)      # memcmp, items never compared
                with f.writer() as w:
                    for kb, p, it in this_run:
                        w.put((p, it))
                kf = File(pool=pool)
                native_merge.write_key_chunks(kf, [t[0] for t in this_run])
                files[slot] = f
                key_files[slot] = kf
                if meta is not None:
                    run_store.submit_commit(slot, *meta, f, kf)
                return pool.bytes_put - b0
            return job

        def _generic_job(this_run, slot, meta=None):
            def job():
                b0 = pool.bytes_put
                f = _spill_run(pool, this_run, pair_key)
                files[slot] = f
                if meta is not None:
                    run_store.submit_commit(slot, *meta, f, None)
                return pool.bytes_put - b0
            return job

        def spill():
            nonlocal run
            if col_items and run:
                decolumnize()           # mixed run: one representation
            slot = len(files)
            files.append(None)
            key_files.append(None)
            meta = None
            if run_store is not None:
                # run identity in arrival order: (pos0, n, first-item
                # fingerprint) — computed BEFORE the job sorts anything
                if col_items:
                    p0, n_, first = col_pos0, len(col_items), \
                        col_items[0]
                elif enc is not None:
                    p0, n_, first = run[0][1], len(run), run[0][2]
                else:
                    p0, n_, first = run[0][0], len(run), run[0][1]
                meta = (p0, n_, em_runs.fingerprint(first))
                got = run_store.try_load(slot, *meta, pool,
                                         DEFAULT_BLOCK_ITEMS)
                if got is not None:
                    # committed run from the previous launch: adopt its
                    # blocks, skip the sort+serialize+write entirely.
                    # runs_reused counts here; spill_runs does NOT —
                    # the perf sentinel separates formed from reloaded.
                    files[slot], key_files[slot] = got
                    _IOSTATS.add(runs_reused=1)
                    col_arrs.clear()
                    col_items.clear()
                    run = []
                    return
            _IOSTATS.add(spill_runs=1)
            if col_items:
                # fully-columnar run: ordering is ONE argsort over the
                # S-w rows (C memcmp — no Python compares, no per-key
                # objects); the key file writes vectorized slices of
                # the sorted array. The pos suffix makes every row
                # distinct, so argsort stability is immaterial. With a
                # records-encodable item schema the whole job (payload
                # columns + sort + gather + handoff) runs natively in
                # the writer.
                if rec_enc is not None:
                    writer.submit(_records_job(list(col_arrs),
                                               list(col_items),
                                               col_pos0, slot, meta),
                                  tag=slot)
                else:
                    writer.submit(_columnar_job(list(col_arrs),
                                                list(col_items),
                                                col_pos0, slot, meta),
                                  tag=slot)
                col_arrs.clear()
                col_items.clear()
            elif enc is not None:
                writer.submit(_encoded_job(run, slot, meta), tag=slot)
            else:
                writer.submit(_generic_job(run, slot, meta), tag=slot)
            run = []

        def demote():
            """Schema deviation: strip key decoration from the live run
            and stop encoding; spilled runs stay valid as-is."""
            nonlocal enc, enc_state, enc_arr, run
            enc, enc_state, enc_arr = None, "off", None
            if col_items:
                run.extend(zip(range(col_pos0,
                                     col_pos0 + len(col_items)),
                               col_items))
                col_arrs.clear()
                col_items.clear()
            else:
                run = [(p, it) for _kb, p, it in run]

        def append_batch(batch):
            """Batch-at-a-time spill-side processing: ONE vectorized
            encode (or one listcomp) and ONE vectorized reservoir call
            per slice — per-item Python bookkeeping was the profiled
            bottleneck of the whole EM sort, bigger than the merge it
            feeds."""
            nonlocal enc, enc_state, enc_arr, pos, col_pos0
            nonlocal rec_probe, rec_enc
            if enc_state == "probe" and batch:
                enc = order_key.make_batch_encoder(sort_key(batch[0]))
                enc_state = "on" if enc is not None else "off"
                if enc is not None:
                    enc_arr = order_key.make_array_batch_encoder(
                        sort_key(batch[0]))
            if rec_probe == "probe" and batch:
                rec_probe = "done"
                rec_enc = native_records.make_run_encoder(batch[0])
            if enc is not None:
                keys = list(map(sort_key, batch))
                try:
                    arr = None
                    if enc_arr is not None and not run:
                        # batches of different widths coexist; spill
                        # widens them with order-safe zero pads
                        arr = enc_arr(keys, pos)
                    if arr is not None:
                        if not col_items:
                            col_pos0 = pos
                        col_arrs.append(arr)
                        col_items.extend(batch)
                    else:
                        if col_items:
                            decolumnize()
                        # kbs built fully BEFORE touching run: a
                        # mid-batch schema deviation leaves no partial
                        # decoration
                        kbs = enc(keys, range(pos, pos + len(batch)))
                        run.extend(zip(kbs,
                                       range(pos, pos + len(batch)),
                                       batch))
                except order_key.BATCH_ENCODE_ERRORS:
                    demote()
                    run.extend(zip(range(pos, pos + len(batch)), batch))
            else:
                run.extend(zip(range(pos, pos + len(batch)), batch))
            sampler.add_batch_indexed(pos, batch)
            pos += len(batch)

        # batch bound: one real RSS check per batch keeps the grant
        # feedback responsive even when run_size is huge, and caps the
        # transient key-bytes list a single encode pass builds
        MAX_BATCH = 1 << 16
        # phase decomposition for perf evidence: the run-formation
        # (encode+sort+spill) phase is engine-independent machinery;
        # the merge phase is where the native k-way engine replaces
        # heapq + per-item Python key calls (ref hot loop:
        # api/sort.hpp:216-271); ``_em_stats`` keeps the two phase
        # times apart
        import time as _time
        t_phase0 = _time.perf_counter()
        ra = None
        try:
            for lst in shards.lists:
                idx = 0
                while idx < len(lst):
                    take = min(run_size - run_len(), len(lst) - idx,
                               MAX_BATCH)
                    append_batch(lst[idx:idx + take])
                    idx += take
                    if run_len() >= run_size or \
                            (budget.exceeded_now() and run_len() >= 16):
                        spill()
                        budget.reset()
                if owns_input:
                    lst.clear()
            if run_len():
                spill()
            # pre-merge barrier: every run durably spilled (a writer
            # error re-raises HERE with its root cause — the merge
            # never reads a half-flushed run), THEN the block store's
            # own eviction queue drained — the merge's surgical
            # readahead consults resident(), and a settled store makes
            # that policy (and the perf sentinel's prefetch counters) a
            # pure function of the program, not of writer-thread timing
            writer.flush()
            if run_store is not None:
                # every in-flight run commit joined too: after this
                # barrier what is committed is committed, and the
                # consuming merge below may release the pool blocks
                run_store.drain()
            pool.flush()
            t_phase1 = _time.perf_counter()

            # merge readahead: one prefetch slot per run (planner-
            # recorded so explain()/the audit loop cover the choice)
            from ..planner import planner_of
            depth = prefetch_depth()
            pl = planner_of(mex)
            if pl is not None:
                depth = pl.io_prefetch_depth("em_sort.merge", depth)
            rec = record_of(mex, "io_prefetch", "em_sort.merge",
                            f"depth={depth}", predicted=1.0,
                            reason="readahead hit-rate target",
                            runs=len(files), depth=depth)
            ra = make_readahead(depth)
            submit = ra.submit if ra is not None else None
            io_merge0 = _IOSTATS.snapshot()

            samples = sorted(sampler.samples, key=pair_key)
            sample_at = [min(len(samples) - 1, (j * len(samples)) // W)
                         for j in range(1, W)] if samples else []
            out = [[] for _ in range(W)]
            w = 0
            if enc is not None and all(kf is not None
                                       for kf in key_files):
                # byte splitters fed as an extra merge run: partition
                # advances when a splitter pops — no per-item key
                # comparison or key-byte copy in Python at all
                split_kb = [enc([sort_key(samples[i][1])],
                                [samples[i][0]])[0]
                            for i in sample_at]
                native_merge.merge_partitioned(files, key_files,
                                               split_kb, out,
                                               consume=True,
                                               submit=submit)
            else:
                # W-1 (key, position) splitters from the reservoir
                split_keys = [pair_key(samples[i]) for i in sample_at]
                for t in multiway_merge_files(files, key=pair_key,
                                              consume=True,
                                              submit=submit):
                    k = pair_key(t)
                    while w < len(split_keys) and k > split_keys[w]:
                        w += 1
                    out[w].append(t[1])

            io_all = _IOSTATS.delta(_IOSTATS.snapshot(), io_base)
            io_merge = _IOSTATS.delta(_IOSTATS.snapshot(), io_merge0)
            hr = hit_rate(io_merge)
            # shared audit-join formula (common/decisions.py): the
            # planner's learned depth feeds off exactly this signal at
            # every readahead site
            resolve_io_prefetch(mex, rec, io_merge)
            self._em_stats = {
                "runs": len(files), "engine":
                    "native" if enc is not None else "py",
                # columnar blocks the native record format encoded (0 =
                # every run spilled through the per-item pickle path)
                "records_blocks": io_all.get("records_blocks", 0),
                # committed runs reloaded from the run store instead of
                # re-formed (core/em_runs.py; 0 without resume)
                "runs_reused": io_all.get("runs_reused", 0),
                "spill_s": round(t_phase1 - t_phase0, 3),
                "merge_s": round(_time.perf_counter() - t_phase1, 3),
                "overlap_frac": round(overlap_frac(io_all), 3),
                "io_wait_s": io_all["io_wait_s"],
                "io_busy_s": io_all["io_busy_s"],
                "prefetch_hit_rate": round(hr, 3),
                "writeback_bytes": writer.bytes_written,
                "writeback_sync": writer.sync}
            log = self.context.logger
            if log.enabled:
                log.line(event="writeback", what="em_sort.spill",
                         bytes=writer.bytes_written,
                         jobs=writer.jobs_run, sync=writer.sync)
                log.line(event="prefetch", what="em_sort.merge",
                         hits=io_merge["prefetch_hits"],
                         misses=io_merge["prefetch_misses"],
                         wait_s=io_merge["io_wait_s"], depth=depth)
        finally:
            writer.close(drain=False)
            if run_store is not None:
                run_store.close()
            if ra is not None:
                ra.shutdown(wait=True, cancel_futures=True)
            for f in files + key_files:
                if f is not None:
                    f.clear()
            pool.close()
        return out


def _spill_run(pool, run, sort_key):
    from ...data.file import File
    run.sort(key=sort_key)
    f = File(pool=pool)
    with f.writer() as w:
        for it in run:
            w.put(it)
    return f


def _sort_rows_local(key_fn: Callable, tree, valid):
    """One worker's whole local sort, traced: key-only argsort of
    (validity, key words, index), then the single payload gather.
    ``valid=None`` means every row is valid — the validity word is
    statically dropped (one fewer sort operand). Shared by the W == 1
    program below and the fused W == 1 segment (SortNode._fuse_segment),
    so the two cannot drift."""
    from ...core.device_sort import argsort_words
    from ...core.rowmove import take_rows_multi
    leaves, td = jax.tree.flatten(tree)
    words = keymod.encode_key_words(key_fn(tree))
    sort_words = list(words) + [jnp.arange(leaves[0].shape[0],
                                           dtype=jnp.uint64)]
    if valid is not None:
        sort_words = [(~valid).astype(jnp.uint32)] + sort_words
    perm = argsort_words(sort_words)
    return jax.tree.unflatten(td, take_rows_multi(leaves, perm))


def _w1_sort_fn(key_fn: Callable, treedef, full: bool):
    """The single-worker sort's per-shard function for ``mex.smap``. A
    module-level builder so that tests/core/test_tpu_aot_compile.py
    compiles this very program for the chip."""
    def f(counts_dev, *ls):
        tree = jax.tree.unflatten(treedef, [l[0] for l in ls])
        valid = None if full \
            else jnp.arange(ls[0].shape[1]) < counts_dev[0, 0]
        out = _sort_rows_local(key_fn, tree, valid)
        return tuple(o[None] for o in jax.tree.leaves(out))

    return f


def _device_sample_sort(shards: DeviceShards, key_fn: Callable,
                        token) -> DeviceShards:
    mex = shards.mesh_exec
    W = mex.num_workers
    cap = shards.cap
    leaves, treedef = jax.tree.flatten(shards.tree)
    total = shards.total
    if total == 0:
        return shards

    # global index offsets (host-known counts -> exclusive prefix)
    offsets = np.concatenate([[0], np.cumsum(shards.counts)])[:-1]

    # all shards full -> the validity sort word is statically dropped
    # (one fewer sort operand; the common case after Distribute/Generate)
    full = bool(np.all(shards.counts == cap))

    if W == 1:
        # CPU backend: device buffers are host memory, so the local
        # sort engine is the native stable radix sort — the same engine
        # class the reference picks for its in-RAM run sorts
        # (sort_algorithm_, api/sort.hpp). On TPU the jitted path below
        # runs instead.
        out = _host_radix_w1(mex, shards, key_fn, leaves, treedef, full)
        if out is not None:
            return out
        # single worker: one fused program — key-only argsort, then the
        # single payload gather. No samples, no splitters, no exchange.
        key1 = ("sort_w1", token, cap, full, treedef,
                tuple((l.dtype, l.shape[2:]) for l in leaves))

        f1 = mex.cached(key1, lambda: mex.smap(
            _w1_sort_fn(key_fn, treedef, full), 1 + len(leaves)))
        out1 = f1(shards.counts_device(), *leaves)
        tree = jax.tree.unflatten(treedef, list(out1))
        return DeviceShards(mex, tree, shards.counts.copy())

    # ---- phase 1: key-only local argsort + quantile samples ----------
    # No payload touches the sort network: only (validity, key words,
    # global index) are sorted; the permutation is carried forward and
    # the payload is gathered once, later, per phase.
    key1 = ("sort_keys", token, cap, full, treedef,
            tuple((l.dtype, l.shape[2:]) for l in leaves))
    holder = {}

    def build1():
        def f(counts_dev, offset_dev, *ls):
            count = counts_dev[0, 0]
            tree = jax.tree.unflatten(treedef, [l[0] for l in ls])
            gidx = offset_dev[0, 0] + jnp.arange(cap, dtype=jnp.int64)
            words = keymod.encode_key_words(key_fn(tree))
            holder["nwords"] = len(words)
            from ...core.device_sort import sort_words
            keys = list(words) + [gidx.astype(jnp.uint64)]
            if not full:
                valid = jnp.arange(cap) < count
                keys = [(~valid).astype(jnp.uint32)] + keys
            # the keys come back from the sort: no gather by ``perm``
            keys_s, perm = sort_words(keys)
            words_s = keys_s[-1 - len(words):-1]
            gidx_s = keys_s[-1].astype(jnp.int64)
            # quantile positions over the valid prefix (sorted: valid
            # items occupy [0, count))
            qpos = quantile_positions(count, cap)
            sample_words = jnp.stack(
                [jnp.take(w, qpos) for w in words_s], axis=1)  # [S, nw]
            sample_idx = jnp.take(gidx_s, qpos)                # [S]
            sample_valid = qpos < count
            return (jnp.stack(words_s, 1)[None], gidx_s[None],
                    perm[None], sample_words[None], sample_idx[None],
                    sample_valid[None])

        return mex.smap(f, 2 + len(leaves)), holder

    f1, h1 = mex.cached(key1, build1)
    out1 = f1(shards.counts_device(),
              mex.put_small(offsets.astype(np.int64)[:, None]), *leaves)
    words_mat, gidx_s, perm_dev, s_words, s_idx, s_valid = out1
    nwords = h1["nwords"]

    # ---- host: choose splitters (the "worker 0" step) ----------------
    sw = mex.fetch(s_words).reshape(W * OVERSAMPLE, nwords)
    si = mex.fetch(s_idx).reshape(W * OVERSAMPLE)
    sv = mex.fetch(s_valid).reshape(W * OVERSAMPLE)
    samples = sorted(tuple(int(x) for x in sw[i]) + (int(si[i]),)
                     for i in range(len(sv)) if sv[i])
    splitters = choose_splitters(samples, W, nwords + 1)

    # ---- phase 2: classify on sorted keys + single payload gather ----
    # Items are key-sorted, so destinations (rank among splitters) are
    # monotone: no destination sort is needed — this replaces the
    # generic exchange's phase-A argsort entirely. Splitters are a
    # RUNTIME operand (replicated like the send-count matrix), never
    # baked into the cached executable.
    # the eventual carrier is {__gidx, __words, tree}: build matching
    # leaf templates up front so the phase-B narrowing's range analysis
    # (exchange.leaf_ranges_traced) can ride this classify program —
    # the data is already resident here, no extra pass
    carrier_templates, _ = jax.tree.flatten({
        "__words": words_mat, "__gidx": gidx_s,
        "tree": jax.tree.unflatten(treedef, list(leaves))})
    nidx3 = exchange.presorted_range_leaves(mex, cap, carrier_templates)
    key2 = ("sort_classify", token, W, cap, nwords, treedef, nidx3,
            tuple((l.dtype, l.shape[2:]) for l in leaves))

    def build2():
        def f(spl_a, words_a, gidx_a, perm_a, counts_dev, *ls):
            spl = spl_a[0]                        # [W-1, nwords+1]
            wm = words_a[0]                       # [cap, nwords] sorted
            gi = gidx_a[0]
            p = perm_a[0]
            count = counts_dev[0, 0]
            valid = jnp.arange(cap) < count       # sorted: valid first
            d = jnp.zeros(cap, dtype=jnp.int32)
            for j in range(W - 1):
                gt = _lex_greater(wm, gi.astype(jnp.uint64), spl[j])
                d = d + gt.astype(jnp.int32)
            dest = jnp.where(valid, d, W)
            all_send = exchange.send_counts(dest, W)
            # the ONE payload gather of this phase
            from ...core.rowmove import take_rows_multi
            sorted_ls = take_rows_multi([l[0] for l in ls], p)
            outs = (dest[None], all_send,
                    *[sl[None] for sl in sorted_ls])
            if nidx3:
                carrier = [gi, wm] + list(sorted_ls)
                outs = outs + (exchange.leaf_ranges_traced(
                    [carrier[li] for li in nidx3], valid),)
            return outs

        from jax.sharding import PartitionSpec as P
        out_specs = (P(AXIS), P()) + (P(AXIS),) * len(leaves)
        if nidx3:
            out_specs = out_specs + (P(),)
        return mex.smap(f, 5 + len(leaves), out_specs=out_specs)

    f2 = mex.cached(key2, build2)
    spl_dev = mex.put_small(np.broadcast_to(
        splitters, (W,) + splitters.shape).copy())
    out2 = f2(spl_dev, words_mat, gidx_s, perm_dev,
              shards.counts_device(), *leaves)
    sorted_dest, send_mat = out2[0], out2[1]
    if nidx3:
        sorted_payload = list(out2[2:-1])
        range_mat = out2[-1]
    else:
        sorted_payload = list(out2[2:])
        range_mat = None
    S = mex.fetch(send_mat)

    # fused dense path: ship + MERGE the received rank-ordered runs in
    # one program (no compaction scatter, no phase-3 re-sort).
    # THRILL_TPU_SORT_FUSED=0 forces the generic exchange + full
    # re-sort fallback (perf A/B diagnostics).
    import os
    fused_ok = os.environ.get("THRILL_TPU_SORT_FUSED", "1") != "0"
    if fused_ok and exchange.dense_all_to_all_applies(
            mex, S, exchange.leaf_item_bytes(sorted_payload)
            + 8 * (nwords + 1)):
        return _fused_exchange_merge(mex, words_mat, gidx_s,
                                     sorted_payload, treedef, S, nwords,
                                     token)

    # carrier = words + gidx (already sorted, no gather needed) + payload
    carrier_tree = {
        "__words": words_mat, "__gidx": gidx_s,
        "tree": jax.tree.unflatten(treedef, sorted_payload),
    }
    carrier_leaves, treedef3 = jax.tree.flatten(carrier_tree)
    ranges = None if range_mat is None else mex._fetch_raw(range_mat)
    carrier = exchange.exchange_presorted(mex, treedef3, sorted_dest,
                                          carrier_leaves, S,
                                          ident=("sort_x", token),
                                          ranges=ranges)

    # ---- phase 3: merge received runs (keys-only sort + one gather) --
    cap3 = carrier.cap
    leaves3, _ = jax.tree.flatten(carrier.tree)
    key3 = ("sort_final", token, cap3, treedef3,
            tuple((l.dtype, l.shape[2:]) for l in leaves3))

    def build3():
        def f(counts_dev, *ls):
            count = counts_dev[0, 0]
            valid = jnp.arange(cap3) < count
            tree = jax.tree.unflatten(treedef3, [l[0] for l in ls])
            wm = tree["__words"]
            gi = tree["__gidx"]
            words = [wm[:, i] for i in range(nwords)]
            from ...core.device_sort import argsort_words
            invalid_word = (~valid).astype(jnp.uint32)
            perm = argsort_words([invalid_word] + words
                                 + [gi.astype(jnp.uint64)])
            # the ONE payload gather of this phase — all leaves batched
            # through one packed word matrix (core/rowmove.py)
            from ...core.rowmove import take_rows_multi
            out_leaves = take_rows_multi(
                jax.tree.leaves(tree["tree"]), perm)
            return tuple(l[None] for l in out_leaves)

        return mex.smap(f, 1 + len(leaves3))

    f3 = mex.cached(key3, build3)
    out3 = f3(carrier.counts_device(), *leaves3)
    tree = jax.tree.unflatten(treedef, list(out3))
    return DeviceShards(mex, tree, carrier.counts.copy())


def _host_radix_w1(mex, shards: DeviceShards, key_fn, leaves, treedef,
                   full: bool) -> Optional[DeviceShards]:
    """Single-worker sort on the CPU backend via the native stable LSD
    radix engine (core/host_radix.py). Returns None when inapplicable
    (non-CPU platform, native toolchain missing, or a key_fn that only
    works under tracing) so the caller falls through to the jitted
    engine."""
    from ...core import host_radix

    if not host_radix.eligible(mex):
        return None
    cap = shards.cap
    count = int(shards.counts[0])
    leaves_np = [np.asarray(l)[0] for l in leaves]       # [cap, ...]
    tree = jax.tree.unflatten(treedef, leaves_np)
    try:
        sort_words = keymod.encode_key_words_np(key_fn(tree))
    except Exception:
        return None                                      # trace-only key_fn
    if not full:
        # validity as the most significant word: invalid rows sort last;
        # radix stability keeps equal keys in global-index order, so no
        # iota tie-break word is needed
        sort_words = [(np.arange(cap) >= count).astype(np.uint64)] \
            + sort_words
    perm = host_radix.radix_argsort(sort_words)
    out_leaves = [
        host_radix.gather_rows(np.ascontiguousarray(l), perm)[None]
        for l in leaves_np]
    tree_out = jax.tree.unflatten(treedef,
                                  [mex.put(l) for l in out_leaves])
    return DeviceShards(mex, tree_out, shards.counts.copy())


def _fused_exchange_merge(mex, words_mat, gidx_s,
                          sorted_payload, treedef, S: np.ndarray,
                          nwords: int, token) -> DeviceShards:
    """Phase 2.5+3 fused: slice the send blocks out of the key-sorted
    rows, all_to_all, then MERGE the W received runs — one jitted
    program, one payload gather, no scatter.

    The received blocks land rank-ordered at static ``M_pad`` run
    boundaries, each run internally sorted by (key words, global index)
    — the sender classified over key-sorted items. Re-sorting them from
    scratch (the reference receivers sort run-by-run then multiway-merge,
    api/sort.hpp:665-699, 216-271) wastes the sortedness; here a bitonic
    merge tree over the run boundaries replaces both the phase-B
    compaction scatter and the phase-3 full sort. Falls back to the
    generic exchange + full sort for ragged/one-factor modes (those
    compact receives at dynamic boundaries).
    """
    from ...core.device_sort import (choose_engine, merge_sorted_runs,
                                     prepare_sort_words)
    W = mex.num_workers
    cap = words_mat.shape[1]
    R = S.sum(axis=0)
    new_counts = R.astype(np.int64)

    # capacity agreement — sticky like the generic dense exchange.
    # Sort's fused path always plans from the synced host S (splitter
    # agreement needs it anyway), so it is a plan build every time —
    # the plan store cannot elide it, only ratchet its capacities
    exchange.count_plan_build(mex)
    cap_ident = ("sort_fused_caps", token, cap, nwords, treedef,
                 tuple((l.dtype, l.shape[2:]) for l in sorted_payload))
    M_pad, out_cap = exchange._sticky_caps(
        mex, cap_ident, (max(int(S.max()), 1), max(int(R.max()), 1)))
    mex.stats_padded_rows += W * M_pad

    # carrier = payload + words matrix + gidx (the shipped columns);
    # the site tag keeps each Sort call site its own doctor skew
    # bucket (same convention as the generic exchange paths)
    exchange.account_traffic(
        mex, S, exchange.leaf_item_bytes(sorted_payload) + 8 * (nwords + 1),
        site="xchg:" + exchange._ident_digest(cap_ident)[:10])

    Wp = 1 << (W - 1).bit_length()                # runs padded to pow2
    Np = Wp * M_pad
    key = ("sort_fused", token, W, cap, M_pad, out_cap, nwords, treedef,
           tuple((l.dtype, l.shape[2:]) for l in sorted_payload))

    def build():
        def f(srow, scol, wm_a, gi_a, *ls):
            from ...core import rowmove
            S_row = srow[0]
            S_col = scol[0]
            # key-sorted rows are grouped by destination, valid first:
            # destination d's block is rows off[d] .. off[d]+S_row[d]-1
            off = exchange._ex_cumsum(S_row)

            def ship(x):
                return exchange.ship_blocks(x, off, S_row, W, M_pad)

            wm_r = ship(wm_a[0])                  # [W*M_pad, nwords]
            gi_r = ship(gi_a[0])                  # [W*M_pad]
            # payload rides the exchange AND the final gather as packed
            # u32 words; unpacked only at the very end
            if rowmove.enabled():
                payload_p, pmetas = rowmove.pack_leaves(
                    [l[0] for l in ls])
            else:
                payload_p, pmetas = [l[0] for l in ls], [None] * len(ls)
            payload_r = [ship(p) for p in payload_p]

            j = jnp.arange(M_pad)[None, :]
            valid = (j < S_col[:, None]).reshape(-1)   # [W*M_pad]

            words = [wm_r[:, k] for k in range(nwords)]
            # validity as a native u32 word: _split_words_u32 keeps
            # non-u64 words single, so no dead zero hi-word rides along
            sort_words = ([(~valid).astype(jnp.uint32)] + words
                          + [gi_r.astype(jnp.uint64)])
            sort_words, idt = prepare_sort_words(sort_words, Np)
            iota = jnp.arange(Np, dtype=idt)

            # pad runs W -> Wp: invalid word 1 + max key words sorts the
            # synthetic runs after every real row (real invalid rows
            # carry zero key words from the recv buffer)
            def pad_rows(a):
                if Wp == W:
                    return a
                return jnp.concatenate(
                    [a, jnp.full(Np - W * M_pad, jnp.iinfo(a.dtype).max,
                                 a.dtype)])

            arrs = [pad_rows(w) for w in sort_words] + [iota]
            # the merge sorts nothing itself: no record of its own
            if choose_engine(Np, sort_words, record=False) == "xla":
                res = lax.sort(tuple(arrs), dimension=0,
                               num_keys=len(arrs), is_stable=False)
                perm = res[-1][:out_cap].astype(jnp.int32)
            else:
                arrs = [a.reshape(Wp, M_pad) for a in arrs]
                merged = merge_sorted_runs(arrs)
                perm = merged[-1].reshape(-1)[:out_cap].astype(jnp.int32)

            # the ONE payload gather of this phase (clip: slots past the
            # valid total may point at synthetic pad rows)
            perm = jnp.minimum(perm, W * M_pad - 1)
            with jax.named_scope(rowmove.SCOPE):
                return tuple(
                    rowmove.unpack_rows(jnp.take(p, perm, axis=0), m)[None]
                    for p, m in zip(payload_r, pmetas))

        return mex.smap(f, 4 + len(sorted_payload))

    fb = mex.cached(key, build)
    srow = mex.put_small(S.astype(np.int32))
    scol = mex.put_small(S.T.copy().astype(np.int32))
    from ...common import trace as _trace
    # W send blocks per shipped leaf: key words, index, the payload's
    send_slices = W * (2 + len(sorted_payload))
    mex.stats_xchg_send_slices += send_slices
    with _trace.span_of(getattr(mex, "tracer", None), "exchange",
                        "sort_fused", m_pad=M_pad, out_cap=out_cap,
                        send_slices=send_slices):
        out = fb(srow, scol, words_mat, gidx_s, *sorted_payload)
    tree = jax.tree.unflatten(treedef, list(out))
    return DeviceShards(mex, tree, new_counts)


def _lex_greater(words_mat: jnp.ndarray, gidx: jnp.ndarray,
                 splitter: jnp.ndarray) -> jnp.ndarray:
    """(words, gidx) > splitter lexicographically; [cap] bool."""
    nw = words_mat.shape[1]
    gt = jnp.zeros(words_mat.shape[0], dtype=bool)
    eq = jnp.ones(words_mat.shape[0], dtype=bool)
    for i in range(nw):
        w = words_mat[:, i]
        gt = gt | (eq & (w > splitter[i]))
        eq = eq & (w == splitter[i])
    gt = gt | (eq & (gidx.astype(jnp.uint64) > splitter[nw]))
    return gt


def Sort(dia: DIA, key_fn=None, compare_fn=None, stable=False) -> DIA:
    return DIA(SortNode(dia.context, dia._link(), key_fn, compare_fn,
                        stable))
