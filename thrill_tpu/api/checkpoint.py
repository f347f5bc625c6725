"""Checkpoint/resume: durable epoch snapshots of materialized DIAs.

The reference framework has NO fault tolerance — a lost worker kills
the whole SPMD job (reference: thrill/api/context.cpp:849-878 is
die-with-parent hygiene, nothing more). PR 1 made *transient* faults
survivable; this module makes **process loss** survivable, following
the RDD lineage+checkpoint model (Zaharia et al., NSDI'12): at stage
barriers (explicitly via ``dia.Checkpoint()``, or every barrier with
``THRILL_TPU_CKPT_AUTO=1``) a materialized DIA's per-worker shard state
is serialized through data/serializer.py and the vfs writers into an
epoch-stamped directory under ``THRILL_TPU_CKPT_DIR``::

    $THRILL_TPU_CKPT_DIR/
      epoch_000000/
        n<dia_id>.w<worker>.bin     per-worker shard payload
        MANIFEST.json               atomic commit record (tmp+rename)
      epoch_000001/ ...

An epoch is COMMITTED iff its manifest exists — the manifest is
written via ``vfs.write_file_atomic`` (write-temp + fsync + rename),
carries dtype/treedef/count metadata plus a CRC32 per shard file, and
is the unit of resume. A relaunched job (``Run(..., resume=True)`` or
``THRILL_TPU_RESUME=1``) loads the newest *complete* epoch, marks the
matching DIA node as already materialized (host Files rebuilt in
place, device shards re-uploaded through ``MeshExec``), and the pull
recursion then skips the node's entire upstream subgraph — only
post-checkpoint work replays, deterministically.

Node identity across runs is ``"<dia_id>:<label>"``: DIA ids are
assigned in construction order, so the same job code constructs the
same ids — the same determinism contract the fused plan cache and the
multi-controller SPMD model already rely on.

Multi-controller: every process writes shard files for its OWN workers
(the ckpt dir must be a shared filesystem across hosts), per-worker
CRCs are agreed over the host control plane, and rank 0 commits the
manifest after all hosts report their files written.

With ``THRILL_TPU_CKPT_DIR`` unset nothing here runs: ``Context``
leaves ``ctx.checkpoint`` as ``None`` and the stage driver's hooks are
a single attribute read (asserted by tests/api/test_checkpoint.py and
the dispatch-budget/fusion parity suites).
"""

from __future__ import annotations

import base64
import glob
import json
import os
import pickle
import shutil
import time
import zlib
from typing import Any, Dict, List, Optional

import numpy as np

from ..common import faults
from ..common.retry import default_policy
from ..data.serializer import deserialize_leaves, serialize_leaves
from ..data.shards import DeviceShards, HostShards
from ..vfs import file_io

MANIFEST = "MANIFEST.json"
_EPOCH_FMT = "epoch_{:06d}"
#: the commit record of an orchestrated process-level resize
#: (Context.resize_processes): written atomically AFTER the RESIZE
#: epoch seals and the net layer agreed to relaunch, consumed by the
#: supervisor (run-scripts/supervise.sh reads target_w) and cleared by
#: the relaunched run once it is actually running at the new W
RESIZE_MARKER = "RESIZE.json"

# checkpoint I/O is idempotent (files are rewritten whole, manifests
# commit atomically), so transient storage faults retry under the
# shared backoff policy before surfacing
_F_WRITE = faults.declare("ckpt.write")
_F_READ = faults.declare("ckpt.read")
_F_MANIFEST = faults.declare("ckpt.manifest")

# elastic re-partition (Context.resize): fired at STAGE time, before
# any shard or mesh state mutates — an injected failure aborts the
# resize with every old-W shard intact, so the generation heals and
# the next resize attempt runs from exactly the same state
_F_REPART = faults.declare("ckpt.repartition")

# process-level resize (Context.resize_processes): fired at RESIZE-
# epoch seal entry and again at marker commit, both BEFORE their
# writes — an injected failure leaves either nothing (seal) or a
# sealed-but-unannounced epoch an old-W resume rejects by the workers
# gate (marker), so the caller aborts with the old mesh fully intact
# and a clean retry runs the identical move
_F_RESIZE_MANIFEST = faults.declare("ckpt.resize_manifest")


def node_key(node) -> str:
    return f"{node.id}:{node.label}"


def resize_marker_path(directory: str) -> str:
    return os.path.join(directory, RESIZE_MARKER)


def pending_resize_target(directory: str) -> Optional[dict]:
    """The committed-but-unconsumed resize marker under ``directory``,
    or None. Module-level (no Context needed): the supervisor parses
    ``target_w`` from it before relaunching, and a relaunched child
    reads it to size its mesh before the Context even exists. A
    corrupt marker is LOUD and treated as absent — the relaunch then
    proceeds at the old W, whose epochs are still committed."""
    path = resize_marker_path(directory)
    try:
        if _is_remote(directory):
            with file_io.OpenReadStream(path) as f:
                raw = f.read()
        else:
            if not os.path.isfile(path):
                return None
            with open(path, "rb") as f:
                raw = f.read()
        m = json.loads(raw.decode())
        if int(m.get("target_w", 0)) < 1:
            raise ValueError(f"bad target_w {m.get('target_w')!r}")
        return m
    except FileNotFoundError:
        return None
    except (ValueError, KeyError, OSError) as e:
        import sys
        print(f"thrill_tpu.checkpoint: ignoring corrupt resize "
              f"marker {path}: {e}", file=sys.stderr)
        return None


def clear_resize_marker(directory: str) -> bool:
    """Consume the resize marker (the move completed: the relaunched
    run is up at the target W). Remote stores have no delete verb on
    the vfs seam — the relaunched run's workers gate makes a stale
    remote marker harmless, so this degrades to False."""
    path = resize_marker_path(directory)
    if _is_remote(directory):
        return False
    try:
        os.remove(path)
        return True
    except FileNotFoundError:
        return False
    except OSError:
        return False


def _epoch_num(path: str) -> Optional[int]:
    name = os.path.basename(path.rstrip("/"))
    if not name.startswith("epoch_"):
        return None
    try:
        return int(name[len("epoch_"):])
    except ValueError:
        return None


def _is_remote(path: str) -> bool:
    """Object-store (s3/hdfs/http) checkpoint directory: no mkdir, no
    rmtree, no posix stat — discovery goes through the vfs Glob and
    a missing manifest is detected by the read itself. Everything else
    (shard writes, manifest commit, restores) already rides the
    scheme-agnostic vfs seam."""
    return "://" in path and not path.startswith("file://")


class CheckpointManager:
    """Owned by :class:`api.context.Context`; saves materialized shard
    state at stage barriers and restores it on resume."""

    def __init__(self, ctx, directory: str, resume: bool = False,
                 auto: bool = False) -> None:
        self.ctx = ctx
        self.dir = directory
        self.auto = auto
        self.resume = resume
        # observability (surfaced by ctx.overall_stats())
        self.epochs_written = 0
        self.bytes_written = 0
        self.resume_skipped_ops = 0
        # EM-sort runs reloaded from the run store instead of re-formed
        # (core/em_runs.py bumps this on every successful try_load)
        self.resume_skipped_runs = 0
        self.restored_nodes = 0
        self.recovery_time_s = 0.0
        self.resume_epoch: Optional[int] = None
        self._inflight_dir: Optional[str] = None
        self._manifest: Optional[dict] = None
        if not _is_remote(self.dir):
            os.makedirs(self.dir, exist_ok=True)
        self._next_epoch = 1 + max(
            (e for e in (_epoch_num(p) for p in self._epoch_dirs())
             if e is not None), default=-1)
        if self._multihost():
            # controllers must agree on epoch numbering: a rank whose
            # directory scan raced another rank's incomplete-epoch
            # cleanup would otherwise write into a different epoch dir
            self._next_epoch = max(
                self.ctx.net.all_gather(self._next_epoch))
        if resume:
            if self._host_rank() == 0:
                self.cleanup_incomplete()
            self._manifest = self._load_newest_manifest()
            if self._multihost():
                # controllers must resume from ONE agreed epoch (or
                # none at all): a rank whose manifest scan raced, hit a
                # transient read error, or found nothing would
                # otherwise replay a different subgraph than its peers
                # — a silent deadlock or mixed-epoch corruption. Agree
                # on the MINIMUM visible epoch (every rank can load
                # it), -1 anywhere = nobody resumes; then agree that
                # every rank actually holds that manifest.
                mine = (int(self._manifest["epoch"])
                        if self._manifest is not None else -1)
                agreed = min(self.ctx.net.all_gather(mine))
                if agreed < 0:
                    self._manifest = None
                elif agreed != mine:
                    self._manifest = self._load_manifest_for(agreed)
                ok = self._manifest is not None
                if not all(self.ctx.net.all_gather(ok)):
                    self._manifest = None
            if self._manifest is not None:
                self.resume_epoch = int(self._manifest["epoch"])
                log = self.ctx.logger
                if log.enabled:
                    log.line(event="resume", epoch=self.resume_epoch,
                             node=self._manifest["node"]["key"])
            # consume a committed resize marker once the relaunch is
            # actually UP at the target W: from here the move is
            # complete and the supervisor must not relaunch again. A
            # marker for a DIFFERENT W stays (this run is not the
            # relaunch the move asked for — its epochs are still
            # gated per-W, so nothing wrong can restore).
            marker = pending_resize_target(self.dir)
            if marker is not None and self._host_rank() == 0 \
                    and int(marker["target_w"]) \
                    == self.ctx.mesh_exec.num_workers:
                clear_resize_marker(self.dir)
                faults.note("recovery", what="ckpt.resize_complete",
                            target_w=int(marker["target_w"]),
                            from_w=marker.get("from_w"),
                            epoch=marker.get("epoch"))

    # -- topology helpers ----------------------------------------------
    def _host_rank(self) -> int:
        return self.ctx.net.my_rank if self.ctx.net.num_workers > 1 else 0

    def _multihost(self) -> bool:
        return self.ctx.net.num_workers > 1

    def _local_workers(self) -> List[int]:
        mex = self.ctx.mesh_exec
        if getattr(mex, "num_processes", 1) > 1:
            return list(mex.local_workers)
        return list(range(mex.num_workers))

    def _epoch_dirs(self) -> List[str]:
        if _is_remote(self.dir):
            # object stores have no directories: list the epoch_*
            # object prefix and fold keys back into epoch "dirs"
            base = self.dir.rstrip("/")
            seen: Dict[str, None] = {}
            try:
                listing = file_io.Glob(base + "/epoch_*")
            except (OSError, NotImplementedError):
                return []
            for fi in listing:
                rest = fi.path[len(base) + 1:]
                if "/" in rest:
                    seen.setdefault(rest.split("/", 1)[0], None)
            return [f"{base}/{d}" for d in seen]
        return [p for p in glob.glob(os.path.join(self.dir, "epoch_*"))
                if os.path.isdir(p)]

    # ------------------------------------------------------------------
    # save
    # ------------------------------------------------------------------
    def maybe_autosave(self, node, shards) -> None:
        """Stage-barrier hook (``THRILL_TPU_CKPT_AUTO=1``): checkpoint
        every freshly materialized DOp result. Sources (no parents) and
        explicit Checkpoint nodes (they save themselves) are skipped."""
        if not self.auto or not node.parents:
            return
        if node.label.startswith("Checkpoint"):
            return
        if isinstance(shards, (DeviceShards, HostShards)):
            self.save(node, shards)

    def save(self, node, shards) -> int:
        """Write one epoch holding ``shards`` for ``node``; returns the
        epoch number. The epoch is durable once the manifest lands.

        Multihost: the whole body runs under the abort protocol
        (poison_on_error) — a rank whose shard write fails past the
        retry budget poisons its peers BEFORE they block in the
        file-table all_gather, so the group gets the root cause
        instead of stranding in a collective."""
        from ..net.group import poison_on_error
        grp = self.ctx.net.group if self._multihost() else None
        with poison_on_error(grp, "ckpt.save"):
            return self._save_guarded(node, shards)

    def _save_guarded(self, node, shards) -> int:
        t0 = time.perf_counter()
        epoch = self._next_epoch
        self._next_epoch += 1
        edir = os.path.join(self.dir, _EPOCH_FMT.format(epoch))
        if not _is_remote(self.dir):
            os.makedirs(edir, exist_ok=True)
        self._inflight_dir = edir
        if isinstance(shards, DeviceShards):
            rec, nbytes = self._save_device(node, shards, edir)
        elif isinstance(shards, HostShards):
            rec, nbytes = self._save_host(node, shards, edir)
        else:
            raise TypeError(f"cannot checkpoint {type(shards).__name__}")
        if self._multihost():
            # agree the full per-worker file table (names/CRCs/counts)
            # across controllers, then rank 0 commits for everyone
            tables = self.ctx.net.all_gather(
                (rec["files"], rec.get("counts"), nbytes))
            files: Dict[str, Any] = {}
            for tab, cnts, _ in tables:
                files.update(tab)
            rec["files"] = files
            if rec.get("counts") is None or rec["kind"] == "host":
                # host-storage counts are per-process partials: merge
                merged = [0] * self.ctx.mesh_exec.num_workers
                for tab, cnts, _ in tables:
                    for w, c in (cnts or {}).items():
                        merged[int(w)] = int(c)
                rec["counts"] = merged
        manifest = {"format": 1, "epoch": epoch,
                    "workers": self.ctx.mesh_exec.num_workers,
                    "node": rec}
        if self._host_rank() == 0:
            payload = json.dumps(manifest, sort_keys=True).encode()

            def commit():
                faults.check(_F_MANIFEST, epoch=epoch)
                file_io.write_file_atomic(
                    os.path.join(edir, MANIFEST), payload)

            default_policy().run(commit, what="ckpt.manifest")
        if self._multihost():
            # nobody proceeds past the barrier until the epoch is
            # committed — a straggler must not build on an epoch a
            # crashed rank 0 never sealed
            self.ctx.net.barrier()
        self._inflight_dir = None
        self.epochs_written += 1
        self.bytes_written += nbytes
        log = self.ctx.logger
        if log.enabled:
            log.line(event="checkpoint", epoch=epoch, node=node.label,
                     dia_id=node.id, bytes=nbytes,
                     seconds=round(time.perf_counter() - t0, 4))
        return epoch

    def _write_file(self, edir: str, name: str, payload: bytes) -> dict:
        path = os.path.join(edir, name)

        def write():
            faults.check(_F_WRITE, file=name)
            with file_io.OpenWriteStream(path) as f:
                f.write(payload)

        default_policy().run(write, what="ckpt.write")
        return {"name": name, "crc": zlib.crc32(payload),
                "bytes": len(payload)}

    def _save_device(self, node, shards: DeviceShards, edir: str):
        import jax
        # drains any deferred producer validation first (to_worker_
        # arrays calls validate_pending), so a hinted-join overflow can
        # never be sealed into an epoch
        per_worker = shards.to_worker_arrays(local_only=True)
        _, treedef = jax.tree.flatten(shards.tree)
        skeleton = jax.tree.unflatten(
            treedef, list(range(treedef.num_leaves)))
        files: Dict[str, Any] = {}
        nbytes = 0
        for w in self._local_workers():
            tree = per_worker[w]
            if tree is None:
                continue
            payload = serialize_leaves(
                [np.asarray(l) for l in jax.tree.leaves(tree)])
            files[str(w)] = self._write_file(
                edir, f"n{node.id}.w{w}.bin", payload)
            nbytes += len(payload)
        rec = {"key": node_key(node), "dia_id": node.id,
               "label": node.label, "kind": "device",
               "counts": [int(c) for c in shards.counts],
               "cap": int(shards.cap),
               "skeleton": base64.b64encode(
                   pickle.dumps(skeleton)).decode("ascii"),
               "files": files}
        return rec, nbytes

    def _save_host(self, node, shards: HostShards, edir: str):
        from ..data.serializer import serialize_batch
        files: Dict[str, Any] = {}
        counts: Dict[str, int] = {}
        nbytes = 0
        for w in self._local_workers():
            items = shards.lists[w]
            payload = serialize_batch(list(items))
            files[str(w)] = self._write_file(
                edir, f"n{node.id}.w{w}.bin", payload)
            counts[str(w)] = len(items)
            nbytes += len(payload)
        rec = {"key": node_key(node), "dia_id": node.id,
               "label": node.label, "kind": "host",
               "counts": counts, "files": files}
        return rec, nbytes

    # ------------------------------------------------------------------
    # orchestrated process-level resize (Context.resize_processes)
    # ------------------------------------------------------------------
    def seal_resize(self, node, shards, target_w: int) -> int:
        """Seal a RESIZE epoch: ``shards`` re-partitioned to
        ``target_w`` AT SEAL TIME and written as a ``target_w``-worker
        epoch. The relaunched W'-wide run then restores through the
        completely standard resume path — its workers gate
        (``_try_load_manifest``) matches, and the shard layout is the
        ``dense_range_bounds`` split a fixed-W' run of the same
        pipeline would have produced, so every post-resume result is
        bit-identical to a fixed-W' reference.

        Crash-safety: the ``ckpt.resize_manifest`` site fires at entry
        before any byte lands; an uncommitted epoch (SIGKILL mid-seal)
        is swept by ``cleanup_incomplete`` at the next resume; a
        COMMITTED W' epoch with no marker is rejected by an old-W
        resume's workers gate — in every case either the old state or
        the sealed move survives, never a mix."""
        from ..net.group import poison_on_error
        grp = self.ctx.net.group if self._multihost() else None
        with poison_on_error(grp, "ckpt.seal_resize"):
            return self._seal_resize_guarded(node, shards, target_w)

    def _seal_resize_guarded(self, node, shards, target_w: int) -> int:
        import jax
        from ..data.serializer import (deserialize_batch,
                                       serialize_batch)
        from ..data.shards import resplit_leaves
        t0 = time.perf_counter()
        target_w = int(target_w)
        old_w = self.ctx.mesh_exec.num_workers
        faults.check(_F_RESIZE_MANIFEST, stage="seal",
                     target=target_w, old=old_w)
        # gather the FULL per-worker view over the host control plane
        # (each process serializes only its local workers; rank 0 ends
        # up holding everything and writes every W' shard file — the
        # joiners of a grow do not exist yet, so nobody else can)
        if isinstance(shards, DeviceShards):
            per_worker = shards.to_worker_arrays(local_only=True)
            _, treedef = jax.tree.flatten(shards.tree)
            skeleton = jax.tree.unflatten(
                treedef, list(range(treedef.num_leaves)))
            local_tab = {
                w: serialize_leaves([np.asarray(l) for l in
                                     jax.tree.leaves(per_worker[w])])
                for w in self._local_workers()
                if per_worker[w] is not None}
            kind = "device"
        elif isinstance(shards, HostShards):
            skeleton = None
            local_tab = {w: serialize_batch(list(shards.lists[w]))
                         for w in self._local_workers()}
            kind = "host"
        else:
            raise TypeError(
                f"cannot seal {type(shards).__name__} for a resize")
        if self._multihost():
            full: Dict[int, bytes] = {}
            for tab in self.ctx.net.all_gather(local_tab):
                full.update({int(w): p for w, p in tab.items()})
        else:
            full = dict(local_tab)
        epoch = self._next_epoch
        self._next_epoch += 1
        edir = os.path.join(self.dir, _EPOCH_FMT.format(epoch))
        nbytes = 0
        if self._host_rank() == 0:
            if not _is_remote(self.dir):
                os.makedirs(edir, exist_ok=True)
            self._inflight_dir = edir
            if kind == "device":
                per_worker_leaves = [
                    deserialize_leaves(full[w]) for w in range(old_w)]
                new_leaves = resplit_leaves(per_worker_leaves,
                                            target_w)
                counts = [int(l[0].shape[0]) if l else 0
                          for l in new_leaves]
                payloads = [serialize_leaves(l) for l in new_leaves]
                rec: Dict[str, Any] = {
                    "key": node_key(node), "dia_id": node.id,
                    "label": node.label, "kind": "device",
                    "counts": counts, "cap": max([1] + counts),
                    "skeleton": base64.b64encode(
                        pickle.dumps(skeleton)).decode("ascii")}
            else:
                lists = [deserialize_batch(full[w])
                         for w in range(old_w)]
                new = HostShards(old_w, lists).repartition(target_w)
                counts = [len(l) for l in new.lists]
                payloads = [serialize_batch(l) for l in new.lists]
                rec = {"key": node_key(node), "dia_id": node.id,
                       "label": node.label, "kind": "host",
                       "counts": counts}
            files: Dict[str, Any] = {}
            for w in range(target_w):
                files[str(w)] = self._write_file(
                    edir, f"n{node.id}.w{w}.bin", payloads[w])
                nbytes += len(payloads[w])
            rec["files"] = files
            manifest = {"format": 1, "epoch": epoch,
                        "workers": target_w,
                        "resize": {"from": old_w, "to": target_w},
                        "node": rec}
            payload = json.dumps(manifest, sort_keys=True).encode()

            def commit():
                faults.check(_F_MANIFEST, epoch=epoch)
                file_io.write_file_atomic(
                    os.path.join(edir, MANIFEST), payload)

            default_policy().run(commit, what="ckpt.manifest")
            self._inflight_dir = None
        if self._multihost():
            self.ctx.net.barrier()
        self.epochs_written += 1
        self.bytes_written += nbytes
        log = self.ctx.logger
        if log.enabled:
            log.line(event="resize_seal", epoch=epoch,
                     node=node.label, dia_id=node.id,
                     workers_old=old_w, workers_new=target_w,
                     bytes=nbytes,
                     seconds=round(time.perf_counter() - t0, 4))
        return epoch

    def commit_resize_marker(self, target_w: int,
                             epoch: Optional[int] = None,
                             generation: Optional[int] = None,
                             procs: Optional[int] = None) -> str:
        """Commit the resize move: the marker's existence tells the
        supervisor (and any relaunch, however it died) that the move
        is ON and what W to relaunch at (``target_procs`` is the
        process count the supervisor's multi-worker mode spawns; the
        single-child mode re-sizes the one child's mesh to
        ``target_w`` instead). Atomic (tmp+rename); the fault site
        fires first, so an injected failure commits nothing and the
        caller aborts with the old W intact."""
        faults.check(_F_RESIZE_MANIFEST, stage="marker",
                     target=int(target_w))
        payload = json.dumps(
            {"format": 1, "target_w": int(target_w),
             "from_w": self.ctx.mesh_exec.num_workers,
             "target_procs": int(procs) if procs else 1,
             "epoch": epoch, "generation": generation},
            sort_keys=True).encode()
        path = resize_marker_path(self.dir)
        if self._host_rank() == 0:
            default_policy().run(
                lambda: file_io.write_file_atomic(path, payload),
                what="ckpt.resize_marker")
        if self._multihost():
            self.ctx.net.barrier()
        return path

    # ------------------------------------------------------------------
    # resume / restore
    # ------------------------------------------------------------------
    def _load_manifest_for(self, epoch: int) -> Optional[dict]:
        """Load one specific epoch's manifest (cross-rank agreement
        picked an epoch older than this rank's newest)."""
        edir = os.path.join(self.dir, _EPOCH_FMT.format(epoch))
        return self._try_load_manifest(edir)

    def _try_load_manifest(self, edir: str) -> Optional[dict]:
        mpath = os.path.join(edir, MANIFEST)
        if not _is_remote(self.dir) and not os.path.isfile(mpath):
            return None
        try:
            if _is_remote(self.dir):
                try:
                    with file_io.OpenReadStream(mpath) as f:
                        raw = f.read()
                except FileNotFoundError:
                    # no manifest object = uncommitted epoch, exactly
                    # the missing-file case the posix isfile probe hits
                    return None
            else:
                with open(mpath, "rb") as f:
                    raw = f.read()
            m = json.loads(raw.decode())
            if m.get("format") != 1:
                raise ValueError(f"unknown format {m.get('format')}")
            if m.get("workers") != self.ctx.mesh_exec.num_workers:
                raise ValueError(
                    f"epoch was written by a {m.get('workers')}-worker "
                    f"mesh; this run has "
                    f"{self.ctx.mesh_exec.num_workers}")
            m["_dir"] = edir
            return m
        except (ValueError, KeyError, OSError) as e:
            import sys
            print(f"thrill_tpu.checkpoint: skipping epoch "
                  f"{os.path.basename(edir)}: {e}", file=sys.stderr)
            return None

    def _load_newest_manifest(self) -> Optional[dict]:
        # foreign/renamed epoch_* dirs (non-numeric suffix) are not
        # resumable epochs — skip them instead of crashing the scan
        dirs = sorted((p for p in self._epoch_dirs()
                       if _epoch_num(p) is not None),
                      key=_epoch_num, reverse=True)
        for edir in dirs:
            m = self._try_load_manifest(edir)
            if m is not None:
                return m
        return None

    def restorable(self, node) -> bool:
        """Does the resume manifest hold this node's state? (Cheap:
        one dict probe; used by the stage driver to route a fused pull
        into the restore path instead of re-deferring upstream.)"""
        m = self._manifest
        return (m is not None and node._shards is None
                and m["node"]["key"] == node_key(node))

    def try_restore(self, node):
        """Rebuild the node's shards from the resume epoch, or None.

        A corrupt epoch (CRC mismatch, missing file) logs loudly and
        returns None — recomputing from lineage is always correct,
        dying on a half-written checkpoint never is."""
        if not self.restorable(node):
            return None
        m = self._manifest
        res = self._restore_agreed(node.label, "recomputing from "
                                               "lineage")
        if res is None:
            self._manifest = None        # every rank recomputes
            return None
        shards, dt = res
        skipped = _count_upstream_new(node)
        self.resume_skipped_ops += skipped
        # one restore per manifest: downstream re-executions of the
        # same key (a later Checkpoint call reusing the id after a
        # Dispose) must recompute, not replay a stale epoch
        self._manifest = None
        faults.note("recovery", what="ckpt.restore", node=node.label,
                    epoch=m["epoch"], skipped_ops=skipped,
                    seconds=round(dt, 4))
        return shards

    # ------------------------------------------------------------------
    # loop-carry epochs (api/loop.py Iterate(..., checkpoint_every=k))
    # ------------------------------------------------------------------
    def save_loop_state(self, name: str, iteration: int, shards) -> int:
        """Seal a loop-carried state into a durable epoch. The label
        encodes (loop name, iteration) so a resumed run can re-enter
        the loop mid-flight without rebuilding the body graph."""
        import types
        shim = types.SimpleNamespace(
            id=0, label=f"LoopState[{name}@{iteration}]", parents=())
        return self.save(shim, shards)

    def try_restore_loop(self, name: str):
        """(shards, iteration) from the resume manifest when it holds a
        loop epoch for ``name``, else None. Same all-or-nothing
        multihost agreement and corrupt-epoch degradation as
        :meth:`try_restore`."""
        m = self._manifest
        if m is None:
            return None
        rec = m["node"]
        label = rec["key"].split(":", 1)[1]
        prefix = f"LoopState[{name}@"
        if not label.startswith(prefix) or not label.endswith("]"):
            return None
        try:
            iteration = int(label[len(prefix):-1])
        except ValueError:
            return None
        res = self._restore_agreed(label, "re-running the loop from "
                                          "its start")
        self._manifest = None
        if res is None:
            return None
        shards, dt = res
        faults.note("recovery", what="ckpt.restore", node=label,
                    epoch=m["epoch"], loop=name, iteration=iteration,
                    seconds=round(dt, 4))
        return shards, iteration

    def _restore_agreed(self, label: str, fallback: str):
        """The shared restore core of :meth:`try_restore` /
        :meth:`try_restore_loop`: rebuild the manifest node's shards
        (corrupt epoch -> loud stderr + recovery note + None) and run
        the all-or-nothing cross-rank agreement. Restore is
        all-or-nothing ACROSS RANKS: one rank falling back to
        recompute while the others restore would re-enter upstream
        exchange collectives alone (deadlock) or finish on mixed-epoch
        data (wrong results). The agreement runs in lockstep:
        restorable() is deterministic after the startup epoch
        agreement, so every controller reaches this all_gather for the
        same node. Returns (shards, seconds) or None; the caller owns
        clearing ``_manifest``."""
        m = self._manifest
        rec = m["node"]
        t0 = time.perf_counter()
        try:
            if rec["kind"] == "device":
                shards = self._restore_device(rec, m["_dir"])
            else:
                shards = self._restore_host(rec, m["_dir"])
        except Exception as e:
            import sys
            print(f"thrill_tpu.checkpoint: restore of {rec['key']} "
                  f"from epoch {m['epoch']} failed ({e!r}); {fallback}",
                  file=sys.stderr)
            faults.note("recovery", what="ckpt.restore_failed",
                        node=label, epoch=m["epoch"], error=repr(e))
            shards = None
        if self._multihost():
            oks = self.ctx.net.all_gather(shards is not None)
            if not all(oks) and shards is not None:
                faults.note("recovery", what="ckpt.restore_abandoned",
                            node=label, epoch=m["epoch"],
                            peers_failed=oks.count(False))
                shards = None
        if shards is None:
            return None
        dt = time.perf_counter() - t0
        self.restored_nodes += 1
        self.recovery_time_s += dt
        return shards, dt

    def _read_file(self, edir: str, finfo: dict) -> bytes:
        path = os.path.join(edir, finfo["name"])

        def read():
            faults.check(_F_READ, file=finfo["name"])
            with file_io.OpenReadStream(path) as f:
                return f.read()

        data = default_policy().run(read, what="ckpt.read")
        if zlib.crc32(data) != finfo["crc"]:
            raise IOError(f"CRC mismatch in {finfo['name']}")
        return data

    def _overlapped_reads(self, edir: str, rec: dict, workers):
        """Yield ``(worker, shard file bytes)`` with the NEXT worker's
        file read already in flight behind the current worker's
        decode+upload — the checkpoint-restore face of the out-of-core
        overlap tier. Each read is the full retry+CRC path
        (:meth:`_read_file`, itself streaming through the prefetching
        vfs reader); a background failure degrades to the demand read
        on this thread, so corruption/fault semantics are unchanged.
        ``THRILL_TPU_PREFETCH=0`` restores strictly sequential reads."""
        from ..data.writeback import make_readahead, overlapped_fetch
        from ..vfs.file_io import prefetch_depth
        from ..common.decisions import record_of, resolve_io_prefetch
        from ..common.iostats import IO as _IOSTATS
        from .planner import planner_of
        workers = list(workers)
        mex = self.ctx.mesh_exec
        ra = None
        drec = None
        st: dict = {}
        io0 = _IOSTATS.snapshot()
        if len(workers) > 1:
            # planner consult + decision record only when a readahead
            # pool actually runs — a 1-file restore must not consume a
            # replan mark or ledger a re-optimization it never
            # exercised
            depth = prefetch_depth()
            pl = planner_of(mex)
            if pl is not None:
                # per-site learned depth (seeded from this site's
                # audited hit rate, not just the one env default)
                depth = pl.io_prefetch_depth("ckpt.restore", depth)
            ra = make_readahead(depth)
            if ra is not None:
                drec = record_of(
                    mex, "io_prefetch", "ckpt.restore",
                    f"depth={depth}", predicted=1.0,
                    reason="overlap next shard's read with the "
                           "current decode+upload",
                    files=len(workers), depth=depth)
        try:
            yield from overlapped_fetch(
                workers,
                lambda w: self._read_file(edir, rec["files"][str(w)]),
                "ckpt.restore", ra, stats=st)
            if st.get("prefetched"):
                _IOSTATS.add(restore_overlaps=1)
                log = self.ctx.logger
                if log.enabled:
                    log.line(event="restore_overlap", kind="ckpt",
                             files=len(workers),
                             prefetched=st["prefetched"])
        finally:
            if ra is not None:
                ra.shutdown(wait=True, cancel_futures=True)
            resolve_io_prefetch(
                mex, drec, _IOSTATS.delta(_IOSTATS.snapshot(), io0))

    def _restore_device(self, rec: dict, edir: str) -> DeviceShards:
        import jax
        mex = self.ctx.mesh_exec
        W = mex.num_workers
        counts = np.asarray([int(c) for c in rec["counts"]],
                            dtype=np.int64)
        cap = int(rec["cap"])
        skeleton = pickle.loads(base64.b64decode(rec["skeleton"]))
        treedef = jax.tree.structure(skeleton)
        local = self._local_workers()
        per_worker_leaves: Dict[int, List[np.ndarray]] = {}
        for w, data in self._overlapped_reads(edir, rec, local):
            leaves = deserialize_leaves(data)
            if len(leaves) != treedef.num_leaves:
                raise IOError(
                    f"worker {w}: {len(leaves)} leaves, treedef wants "
                    f"{treedef.num_leaves}")
            if leaves and leaves[0].shape[0] != counts[w]:
                raise IOError(
                    f"worker {w}: {leaves[0].shape[0]} rows, manifest "
                    f"says {counts[w]}")
            per_worker_leaves[w] = leaves
        out_leaves = []
        for i in range(treedef.num_leaves):
            singles = []
            tail = per_worker_leaves[local[0]][i].shape[1:]
            dtype = per_worker_leaves[local[0]][i].dtype
            for w in local:
                arr = per_worker_leaves[w][i]
                if arr.dtype != dtype or arr.shape[1:] != tail:
                    raise IOError(
                        f"worker {w} leaf {i}: {arr.dtype}{arr.shape} "
                        f"does not match worker {local[0]}'s "
                        f"{dtype}(*, {tail}) — corrupt epoch")
                pad = [(0, cap - arr.shape[0])] + [(0, 0)] * (arr.ndim - 1)
                padded = np.pad(arr, pad)[None]        # [1, cap, ...]
                singles.append(jax.device_put(padded, mex.devices[w]))
            out_leaves.append(jax.make_array_from_single_device_arrays(
                (W, cap) + tail, mex.sharded, singles))
        tree = jax.tree.unflatten(treedef, out_leaves)
        shards = DeviceShards(mex, tree, counts)
        log = self.ctx.logger
        if log.enabled:
            log.line(event="ckpt_restore", kind="device",
                     epoch=self._manifest["epoch"],
                     items=int(counts.sum()))
        return shards

    def _restore_host(self, rec: dict, edir: str) -> HostShards:
        from ..data.serializer import deserialize_batch
        mex = self.ctx.mesh_exec
        W = mex.num_workers
        lists: List[List[Any]] = [[] for _ in range(W)]
        local = self._local_workers()
        for w in local:
            if rec["files"].get(str(w)) is None:
                raise IOError(f"worker {w}: shard file missing from "
                              f"manifest")
        for w, data in self._overlapped_reads(edir, rec, local):
            lists[w] = deserialize_batch(data)
            want = int(rec["counts"].get(str(w), len(lists[w]))) \
                if isinstance(rec["counts"], dict) \
                else int(rec["counts"][w])
            if len(lists[w]) != want:
                raise IOError(f"worker {w}: {len(lists[w])} items, "
                              f"manifest says {want}")
        shards = HostShards(W, lists)
        log = self.ctx.logger
        if log.enabled:
            log.line(event="ckpt_restore", kind="host",
                     epoch=self._manifest["epoch"], items=shards.total)
        return shards

    # ------------------------------------------------------------------
    # hygiene
    # ------------------------------------------------------------------
    def cleanup_incomplete(self) -> int:
        """Remove epoch directories without a committed manifest (a
        crashed run's half-written epoch). Safe only when no live
        writer shares the directory: called at resume startup (the
        previous run is dead by definition) and from the abort path
        (only this run's own in-flight epoch is fresh)."""
        removed = 0
        if _is_remote(self.dir):
            # no delete verb on the vfs seam — harmless: an epoch
            # without a manifest is invisible to resume discovery
            return 0
        for edir in self._epoch_dirs():
            if os.path.isfile(os.path.join(edir, MANIFEST)):
                continue
            try:
                shutil.rmtree(edir)
                removed += 1
            except OSError:
                pass
        if removed:
            faults.note("recovery", what="ckpt.cleanup_incomplete",
                        removed=removed)
        return removed

    def abort_cleanup(self) -> None:
        """Drop this run's uncommitted in-flight epoch (if any)."""
        edir, self._inflight_dir = self._inflight_dir, None
        if edir and _is_remote(self.dir):
            return
        if edir and not os.path.isfile(os.path.join(edir, MANIFEST)):
            try:
                shutil.rmtree(edir)
            except OSError:
                pass

    def stats(self) -> dict:
        return {"checkpoint_epochs": self.epochs_written,
                "ckpt_bytes_written": self.bytes_written,
                "resume_skipped_ops": self.resume_skipped_ops,
                "resume_skipped_runs": self.resume_skipped_runs,
                "recovery_time_s": round(self.recovery_time_s, 4)}


# ----------------------------------------------------------------------
# elastic re-partition (api/context.py Context.resize)
# ----------------------------------------------------------------------
#
# Live shards move across a W change in two phases so a mid-resize
# failure can never strand half-moved data:
#
# * stage_repartition — runs BEFORE anything mutates: every live
#   shard's valid rows serialize through the checkpoint serializer
#   (the same columnar records an epoch file holds, data/serializer.py)
#   into an in-memory staging blob. Any failure here (including the
#   injected ``ckpt.repartition`` site) aborts the resize with the old
#   mesh, membership and shards untouched.
# * commit_repartition — runs AFTER ``MeshExec.resize``: the staged
#   records deserialize behind the PR-13/15 prefetching reader
#   (writeback.overlapped_fetch — the next worker's decode is in
#   flight behind the current upload), re-split across
#   ``dense_range_bounds(total, W')`` and upload to the new mesh.
#   The split is exactly the layout a fresh W'-wide run would build,
#   which is what keeps post-resize results bit-identical to a
#   fixed-W' run.


def stage_repartition(shards) -> dict:
    """Serialize one live shard store for a W change; returns the
    staging blob ``commit_repartition`` consumes. Pure read: the
    shards stay valid and untouched."""
    import jax as _jax
    faults.check(_F_REPART, kind=type(shards).__name__,
                 workers=shards.num_workers)
    if isinstance(shards, DeviceShards):
        per_worker = shards.to_worker_arrays()
        _, treedef = _jax.tree.flatten(shards.tree)
        skeleton = _jax.tree.unflatten(
            treedef, list(range(treedef.num_leaves)))
        payloads = [serialize_leaves(
            [np.asarray(l) for l in _jax.tree.leaves(t)])
            for t in per_worker]
        return {"kind": "device", "skeleton": skeleton,
                "payloads": payloads}
    if isinstance(shards, HostShards):
        from ..data.serializer import serialize_batch
        return {"kind": "host",
                "payloads": [serialize_batch(list(items))
                             for items in shards.lists]}
    raise TypeError(f"cannot repartition {type(shards).__name__}")


def _overlapped_staged(mex, payloads):
    """Yield ``(worker, payload)`` with the next worker's record fetch
    in flight behind the current decode — the same planner-consulted
    readahead the checkpoint restore path runs, at its own
    ``ckpt.repartition`` site."""
    from ..data.writeback import make_readahead, overlapped_fetch
    from ..vfs.file_io import prefetch_depth
    from .planner import planner_of
    workers = list(range(len(payloads)))
    ra = None
    if len(workers) > 1:
        depth = prefetch_depth()
        pl = planner_of(mex)
        if pl is not None:
            depth = pl.io_prefetch_depth("ckpt.repartition", depth)
        ra = make_readahead(depth)
    try:
        yield from overlapped_fetch(
            workers, lambda w: payloads[w], "ckpt.repartition", ra)
    finally:
        if ra is not None:
            ra.shutdown(wait=True, cancel_futures=True)


def commit_repartition(mex, staged: dict):
    """Rebuild one staged shard store against the RESIZED mesh (device
    kind) or the new worker count (host kind)."""
    import jax as _jax
    if staged["kind"] == "host":
        from ..data.serializer import deserialize_batch
        lists: List[List[Any]] = []
        for _, payload in _overlapped_staged(mex, staged["payloads"]):
            lists.append(deserialize_batch(payload))
        return HostShards(len(lists), lists).repartition(
            mex.num_workers)
    from ..data.shards import resplit_leaves
    treedef = _jax.tree.structure(staged["skeleton"])
    per_worker_leaves: List[List[np.ndarray]] = [
        deserialize_leaves(payload)
        for _, payload in _overlapped_staged(mex, staged["payloads"])]
    new_leaves = resplit_leaves(per_worker_leaves, mex.num_workers)
    per_worker = [_jax.tree.unflatten(treedef, leaves)
                  for leaves in new_leaves]
    return DeviceShards.from_worker_arrays(mex, per_worker)


def _count_upstream_new(node) -> int:
    """How many transitive ancestors the restore just short-circuited
    (they stay NEW: the pull recursion never reaches them)."""
    seen = set()
    stack = [p.node for p in node.parents]
    n = 0
    while stack:
        x = stack.pop()
        if x.id in seen:
            continue
        seen.add(x.id)
        if x.state == "NEW":
            n += 1
            stack.extend(p.node for p in x.parents)
    return n


# ----------------------------------------------------------------------
# the explicit barrier node (dia.Checkpoint())
# ----------------------------------------------------------------------

def make_checkpoint_node(dia, name: Optional[str] = None):
    from .dia import DIA
    from .dia_base import DIABase

    class CheckpointNode(DIABase):
        """Materializes its parent and seals the result into an epoch.
        A fusion/stage barrier by construction (no compute_plan): a
        downstream fused chain starts from the checkpointed shards."""

        def compute(self):
            shards = self.parents[0].pull()
            mgr = getattr(self.context, "checkpoint", None)
            if mgr is not None:
                mgr.save(self, shards)
            return shards

    label = "Checkpoint" if name is None else f"Checkpoint[{name}]"
    node = CheckpointNode(dia.context, label, [dia._link()])
    return DIA(node)
