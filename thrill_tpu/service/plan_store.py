"""Persistent compile/plan store: learned plan state across restarts.

A long-lived service amortizes its planning cost in-process — sticky
exchange capacities, narrow specs, plan kinds (data/exchange.py) and
pre-shuffle verdicts (core/preshuffle.py) are learned once per
``MeshExec.cached`` / ``FusionPlan`` composite identity and reused for
every later query. A process RESTART used to throw all of it away:
every exchange site paid the synced host plan step again (a blocking
device->host sync each — the class of cost the whole dispatch budget
fights), every auto-prune site re-ran its cost model. This store
persists that state through the vfs (file://, s3://, hdfs://) so a
warm restart re-runs a known pipeline with ``plan_builds == 0``.

Key/versioning rules:

* Keys are SHA-1 digests of the ``repr`` of the in-memory identity
  tuples (call-site ident + shapes + dtypes + treedefs) — stable for a
  fixed program across processes, and garbage for a changed one, which
  is exactly right: a changed pipeline simply misses and re-learns.
* Every on-disk key carries a ``w{W}:`` prefix (the mesh width the
  entry was learned at): capacities, narrow ranges and loop tapes are
  W-SHAPED vectors, and an elastic service that resizes W=2→3 must
  not install 2-wide caps into a 3-wide mesh. Loads filter to the
  CURRENT width and strip the prefix; entries of other widths stay on
  disk untouched, so a resize back to a previously-served W warm
  starts again. (This is the on-disk twin of MeshExec.resize's in-
  memory per-W archive, parallel/mesh.py.)
* Values are CORRECTNESS-NEUTRAL by construction: a lying capacity or
  narrow range is caught by the exchange's in-trace overflow/range
  flag and healed by the synced re-run; a wrong plan kind or prune
  verdict costs performance, never results. That is why a plan store
  may be trusted at all — and why corruption handling can afford to be
  simple: any parse/CRC/version failure degrades LOUDLY to an empty
  store (cold recompile), never to a partial read.
* The file carries ``version`` (STORE_VERSION — bump on any format
  change; skewed versions are refused wholesale) and a CRC-32 over the
  canonical entries JSON. Writes go through
  ``vfs.write_file_atomic`` — readers see the old store or the whole
  new one, never a torn prefix.

Compiled XLA executables are deliberately NOT stored here: jax's own
persistent compilation cache (JAX_COMPILATION_CACHE_DIR, or the
checkout's .jax_cache/ — api/context.py) already buries repeat compile
costs; this store covers the
DATA-DRIVEN half of planning that jax cannot know about.
"""

from __future__ import annotations

import contextlib
import json
import zlib
from typing import Optional

from ..common import faults

# v2: keys gained the w{W}: width prefix (elastic mesh) — v1 stores
# carry width-ambiguous keys and are refused wholesale by the version
# check (loud cold recompile), exactly the documented skew behavior
STORE_VERSION = 2
_FILE = "plans.json"
#: the decision ledger's audited-accuracy summary persists NEXT TO the
#: plan state it judges (common/decisions.py; Context.close writes it)
_LEDGER_FILE = "decisions.json"

# fired at load time: an armed fire makes THIS load read as corrupt —
# the store degrades to empty (cold recompile), results stay exact
_F_CORRUPT = faults.declare("service.plan_store.corrupt")

#: entry kinds and their owners (data/exchange.py, core/preshuffle.py,
#: parallel/mesh.py, api/loop.py)
_KINDS = ("caps", "plan", "ranges", "prune_decisions", "prune_history",
          "out_bytes", "loop_tape")


def _crc(entries: dict) -> int:
    return zlib.crc32(json.dumps(entries, sort_keys=True).encode())


def _for_width(entries: dict, w: int) -> dict:
    """The store slice learned at mesh width ``w``: keep only
    ``w{w}:``-prefixed keys, stripped. Entries of other widths (or
    unprefixed strays) are simply not installed — they are not wrong,
    they are for a differently-shaped mesh."""
    pre = f"w{w}:"
    return {kind: {k[len(pre):]: v for k, v in m.items()
                   if isinstance(k, str) and k.startswith(pre)}
            for kind, m in entries.items() if isinstance(m, dict)}


def install_entries(mex, entries: dict, *,
                    symmetric: bool = False) -> int:
    """Install loaded store entries into a MeshExec's lazy seed
    tables; returns how many arrived. Shared by :meth:`PlanStore.attach`
    (this process read the file) and the Context's multi-process path
    (rank 0 read it and BROADCAST the entries over the host control
    plane, so every rank installs the identical seeds —
    api/context.py; that caller passes ``symmetric=True``, the
    attestation that keeps the optimistic exchange gate open on
    multi-controller meshes — data/exchange.py install_plan_seeds).
    Filters to the mesh's CURRENT width (keys are ``w{W}:``-prefixed
    on disk — see the module docstring)."""
    from ..api import loop
    from ..core import preshuffle
    from ..data import exchange
    entries = _for_width(entries, mex.num_workers)
    n = exchange.import_plan_state(mex, entries, symmetric=symmetric)
    n += preshuffle.import_plan_state(mex, entries,
                                      symmetric=symmetric)
    n += loop.import_plan_state(mex, entries, symmetric=symmetric)
    ob = entries.get("out_bytes")
    if isinstance(ob, dict) and hasattr(mex, "import_learned_sizes"):
        n_ob = mex.import_learned_sizes(ob)
        if n_ob and not symmetric:
            # learned sizes ride the same provenance rule as the seed
            # table: a non-attested install closes the optimism gate
            mex._plan_seed_symmetric = False
        n += n_ob
    return n


class PlanStore:
    """One on-disk plan-state file under a vfs directory."""

    def __init__(self, path: str, logger=None) -> None:
        self.path = path
        self.file = path.rstrip("/") + "/" + _FILE
        self.logger = logger
        self._last_corrupt: Optional[str] = None

    # -- reading --------------------------------------------------------
    def load(self) -> dict:
        """Entries by kind; {} when cold. NEVER raises: any failure —
        missing file aside — is a loud degrade to empty (the service
        recompiles; a plan store must not be able to take it down)."""
        from ..vfs import file_io
        self._last_corrupt = None
        try:
            faults.check(_F_CORRUPT, path=self.file)
            with file_io.OpenReadStream(self.file) as f:
                raw = f.read()
        except FileNotFoundError:
            return {}
        except Exception as e:
            return self._corrupt(f"unreadable: {e!r}")
        try:
            payload = json.loads(raw.decode())
            if not isinstance(payload, dict):
                return self._corrupt("not a JSON object")
            if payload.get("version") != STORE_VERSION:
                return self._corrupt(
                    f"version skew: {payload.get('version')!r} != "
                    f"{STORE_VERSION}")
            entries = payload.get("entries")
            if not isinstance(entries, dict):
                return self._corrupt("entries missing")
            if _crc(entries) != payload.get("crc"):
                return self._corrupt("CRC mismatch")
        except Exception as e:
            return self._corrupt(f"parse failure: {e!r}")
        return {k: dict(v) for k, v in entries.items()
                if k in _KINDS and isinstance(v, dict)}

    def _corrupt(self, why: str) -> dict:
        self._last_corrupt = why
        faults.note("recovery", what="plan_store.corrupt",
                    path=self.file, why=why[:200])
        import sys
        print(f"thrill_tpu.service: plan store {self.file} ignored "
              f"({why}); recompiling cold", file=sys.stderr)
        return {}

    def attach(self, mex) -> int:
        """Seed a MeshExec's plan state from the store; returns the
        number of entries imported. The seeds are consumed lazily at
        each site's first lookup (data/exchange.py plan_seed), so an
        entry for a pipeline this process never runs costs nothing."""
        return install_entries(mex, self.load())

    # -- writing --------------------------------------------------------
    def save(self, mex) -> None:
        """Persist the MeshExec's current plan state, merged with what
        is already on disk (capacities elementwise-max; unknown
        digests are kept — another pipeline's state is not ours to
        drop). On posix paths the load-merge-write runs under an
        flock, so concurrent services sharing one store only ever
        ratchet; object-store schemes (s3://, hdfs://) have no lock
        primitive and keep last-writer-wins there. A corrupt on-disk
        store is replaced wholesale."""
        with self._save_lock():
            self._save_locked(mex)

    @contextlib.contextmanager
    def _save_lock(self):
        if "://" in self.path and not self.path.startswith("file://"):
            yield                        # no lock primitive: best effort
            return
        import os
        d = self.path[len("file://"):] if self.path.startswith(
            "file://") else self.path
        os.makedirs(d, exist_ok=True)
        import fcntl
        with open(d.rstrip("/") + "/.plans.lock", "w") as lk:
            fcntl.flock(lk, fcntl.LOCK_EX)
            try:
                yield
            finally:
                fcntl.flock(lk, fcntl.LOCK_UN)

    def _save_locked(self, mex) -> None:
        from ..api import loop
        from ..core import preshuffle
        from ..data import exchange
        from ..vfs import file_io
        entries = exchange.export_plan_state(mex)
        entries.update(preshuffle.export_plan_state(mex))
        entries.update(loop.export_plan_state(mex))
        if hasattr(mex, "export_learned_sizes"):
            entries["out_bytes"] = mex.export_learned_sizes()
        # stamp every exported key with the width it was learned at
        # (the in-memory tables are all CURRENT-W state: MeshExec.resize
        # parks other widths in its archive, never in these exports)
        pre = f"w{mex.num_workers}:"
        entries = {kind: {pre + dg: v for dg, v in m.items()}
                   for kind, m in entries.items()}
        prev = self.load()
        if self._last_corrupt is None:
            for kind, old in prev.items():
                new = entries.setdefault(kind, {})
                for dg, v in old.items():
                    if dg not in new:
                        new[dg] = v
                    elif kind == "caps":
                        try:
                            new[dg] = [max(int(a), int(b)) for a, b
                                       in zip(new[dg], v)] \
                                if len(new[dg]) == len(v) else new[dg]
                        except (TypeError, ValueError):
                            pass
        payload = {"version": STORE_VERSION, "crc": _crc(entries),
                   "entries": entries}
        file_io.write_file_atomic(
            self.file, json.dumps(payload, sort_keys=True).encode())
        if self.logger is not None and self.logger.enabled:
            self.logger.line(event="plan_store_save", path=self.file,
                             entries=sum(len(v)
                                         for v in entries.values()))

    def save_ledger(self, summary: dict) -> None:
        """Persist the decision ledger's accuracy summary beside
        plans.json: per-kind predicted-vs-actual MAE plus the
        worst-audited sites. Plain overwrite (no merge): the ledger is
        a per-run audit report, not ratcheting plan state — the newest
        run's verdict on the cost model is the one that matters."""
        from ..vfs import file_io
        path = self.path.rstrip("/") + "/" + _LEDGER_FILE
        file_io.write_file_atomic(
            path, json.dumps(summary, sort_keys=True).encode())
        if self.logger is not None and self.logger.enabled:
            self.logger.line(event="decision_ledger_save", path=path,
                             decisions=summary.get("decisions", 0))
