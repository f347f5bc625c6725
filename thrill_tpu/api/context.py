"""Context, runtime bootstrap and the local test harness.

Equivalent of the reference's Context/HostContext/Run machinery
(reference: thrill/api/context.hpp:90-448, context.cpp:336-341,947-1013):
``Run`` bootstraps a runtime and hands the user job a Context; the job
builds and executes DIA pipelines against it.

Single-controller translation: one Context drives all W logical workers
(one per mesh device). ``RunLocalTests`` replicates the reference's
in-process virtual-cluster sweep — the same job body runs on meshes of
several sizes over XLA host-platform devices, no cluster needed.

Multi-host: call ``thrill_tpu.api.Run`` after ``jax.distributed``
initialization and the mesh spans all hosts' devices; each host runs the
same single-controller program (standard JAX multi-controller SPMD).
"""

from __future__ import annotations

import contextlib
import os
import threading
import time
from typing import Any, Callable, List, Optional, Sequence

import numpy as np

import jax

from ..common.config import Config
from ..common.logger import JsonLogger, default_log_path
from ..mem.manager import MemoryManager
from ..net.flow import FlowControlChannel, LocalFlowControl
from ..parallel.mesh import MeshExec


def _wire_ratio(raw: int, actual: int) -> float:
    """bytes_on_wire_raw / bytes_on_wire, 1.0 when nothing shipped."""
    return round(raw / actual, 3) if actual else 1.0


def _em_adopted() -> int:
    """Process-wide count of EM runs adopted from departed ranks
    (core/em_runs.py). Adoption only ever happens in a rank that
    joined/relaunched into an elastic group, so this is exactly zero
    for every non-elastic workload — the perf sentinel pins it."""
    try:
        from ..core.em_runs import adopted_total
        return adopted_total()
    except Exception:
        return 0


class PipelineError(RuntimeError):
    """One pipeline run on a Context failed — and ONLY that pipeline:
    the Context healed (generation-scoped failure domain) and stays
    usable for the next run. Carries the ROOT CAUSE of the abort
    (``origin`` rank, ``cause`` text, ``generation`` of the failed
    run, ``root`` original exception). Deliberately NOT a
    ConnectionError/ClusterAbort subclass: retry policies classify it
    permanent, RunSupervised does not relaunch for it (the caller
    opted into handling scoped failures by using ``ctx.pipeline()``),
    and ``Context.close()`` runs the healthy collective shutdown."""

    def __init__(self, origin: int, cause: str, generation: int,
                 root: Optional[BaseException] = None) -> None:
        super().__init__(
            f"pipeline generation {generation} aborted "
            f"(origin rank {origin}): {cause}")
        self.origin = origin
        self.cause = cause
        self.generation = generation
        self.root = root


# process-level elasticity: the exit code a supervised worker exits
# with once a resize move is COMMITTED (marker on disk). EX_TEMPFAIL —
# "try again", which is literally the contract: the supervisor reads
# the RESIZE marker and relaunches at the target W with resume.
RESIZE_EXIT_CODE = 75


class ResizeRelaunch(SystemExit):
    """Raised by :meth:`Context.resize_processes` once the move is
    committed: this process must exit so the supervisor
    (run-scripts/supervise.sh) can relaunch the job at the target W
    with ``THRILL_TPU_RESUME=1``. A SystemExit subclass with code
    ``RESIZE_EXIT_CODE`` — left uncaught it exits the worker with
    exactly the code the supervisor's resize branch watches for, and
    no retry policy classifies it transient. Raise it only on the MAIN
    thread (a SystemExit in a helper thread kills just that thread);
    autoscaler deployments signal the main loop from ``apply_fn`` and
    let it call resize_processes."""

    def __init__(self, target_w: int, epoch: Optional[int] = None,
                 generation: Optional[int] = None) -> None:
        super().__init__(RESIZE_EXIT_CODE)
        self.target_w = int(target_w)
        self.epoch = epoch
        self.generation = generation

    def __str__(self) -> str:
        return (f"resize move to W={self.target_w} committed: exiting "
                f"{RESIZE_EXIT_CODE} for supervised relaunch")


class Context:
    """Runtime handle passed to user jobs; owns the mesh and services."""

    def __init__(self, mesh_exec: Optional[MeshExec] = None,
                 config: Optional[Config] = None, seed: int = 0,
                 host_rank: Optional[int] = None,
                 resume: bool = False) -> None:
        self.config = config or Config.from_env()
        # persistent compile cache: placed from outside by
        # JAX_COMPILATION_CACHE_DIR (jax reads it itself; nothing is
        # set here then). Unset, it goes to ONE fixed directory in the
        # checkout — the path is part of the cache key, so a directory
        # that moves never hits — and only off-CPU (XLA:CPU AOT cache
        # entries reload with machine-feature warning spam).
        if "JAX_COMPILATION_CACHE_DIR" not in os.environ \
                and jax.default_backend() != "cpu":
            from ..common.config import COMPILE_CACHE_DIR
            jax.config.update("jax_compilation_cache_dir",
                              COMPILE_CACHE_DIR)
        self.mesh_exec = mesh_exec or MeshExec(
            num_workers=self.config.num_workers)
        self.mesh_exec.exchange_mode = self.config.exchange
        if host_rank is None:
            host_rank = jax.process_index()
        self.host_rank = host_rank
        # worker-level collectives, single-controller flavor (host ops)
        self.flow = LocalFlowControl(self.num_workers)
        # host-level control plane: FlowControlChannel over a real group
        # (reference: ctx.net, api/context.hpp:446-448). Single-process
        # runs get a trivial 1-host group; multi-process deployments
        # bootstrap the authenticated TCP full mesh from THRILL_TPU_*
        # env so host-side scalar agreement crosses machines.
        self.net = FlowControlChannel(self._construct_host_group())
        # the host-storage data plane (data/multiplexer.py) reaches the
        # other controllers through the mesh handle every shard carries
        self.mesh_exec.host_net = self.net
        self.logger = JsonLogger(
            default_log_path(self.config.log_path, host_rank=host_rank),
            program="thrill_tpu", workers=self.num_workers,
            host=host_rank)
        # storage-layer events (device->host demotions) log through the
        # mesh the shards carry a reference to
        self.mesh_exec.logger = self.logger
        # tracing spine (common/trace.py): one Tracer per Context,
        # attached to the mesh (dispatch/fusion/exchange/mem/loop
        # spans) and the net group (collective/heal spans); spans are
        # tagged with the generation and tenant CURRENT at span start.
        # THRILL_TPU_TRACE=0 pins the disabled fast path (no span
        # objects anywhere); the ring doubles as the flight recorder.
        from ..common.trace import Tracer
        self.tracer = Tracer(rank=host_rank, logger=self.logger)
        # getattr, not plain attribute reads: generation/current_tenant
        # are assigned further down __init__, and a span started during
        # construction must not crash on the not-yet-bound names
        self.tracer.gen_fn = lambda: getattr(self, "generation", None)
        self.tracer.tenant_fn = \
            lambda: getattr(self, "current_tenant", None)
        self.mesh_exec.tracer = self.tracer
        self.net.group.tracer = self.tracer
        # performance doctor (common/doctor.py): per-peer collective
        # wait attribution + partition-skew detection + the critical-
        # path pass over the span ring. THRILL_TPU_DOCTOR=0 pins the
        # disabled fast path (no Doctor anywhere: every choke point
        # pays one attribute read, allocates nothing).
        from ..common.doctor import Doctor, doctor_enabled
        self.doctor = Doctor(rank=host_rank) if doctor_enabled() \
            else None
        self.mesh_exec.doctor = self.doctor
        self.net.group.doctor = self.doctor
        # plan observatory (common/decisions.py): one DecisionLedger
        # per Context, attached to the mesh so every plan-choice choke
        # point (fusion, exchange, preshuffle, admission, plan store)
        # reaches it in one attribute read. THRILL_TPU_DECISIONS=0
        # pins the disabled fast path (no record objects anywhere);
        # records ride the JSON log (event=decision) and the trace's
        # "plan" lane, and ctx.explain() renders them on the DIA tree.
        from ..common.decisions import DecisionLedger
        self.decisions = DecisionLedger(logger=self.logger,
                                        tracer=self.tracer)
        self.mesh_exec.decisions = self.decisions
        # adaptive cost-based planner (api/planner.py): one model over
        # the learned plan state that CHOOSES — exchange strategy and
        # chunk count, optimistic-vs-synced dispatch, pre-shuffle
        # prune verdicts, proactive fusion splits under the HBM
        # admission estimate — and RE-OPTIMIZES when the decision
        # ledger's audit joins reveal a learned stat lied.
        # THRILL_TPU_PLANNER=0 restores the per-site heuristics
        # exactly (no Planner constructed, every call site takes its
        # legacy branch).
        from .planner import Planner, planner_enabled
        self.planner = None
        if planner_enabled():
            self.planner = Planner(self.mesh_exec)
            self.mesh_exec.planner = self.planner
            self.decisions.audit_hook = self.planner.on_audit
        # live metrics endpoint (common/metrics.py): Prometheus text on
        # THRILL_TPU_METRICS_PORT from a daemon thread; unset = off
        from ..common.metrics import maybe_start as _metrics_start
        self._metrics = _metrics_start(self)
        # fault-injection / retry / abort events from every layer ride
        # the same JSON stream (tools/json2profile.py renders them);
        # counters are process-lifetime, so snapshot a baseline and
        # report per-job deltas (sequential Run()s must not inherit a
        # previous job's retries)
        from ..common import faults
        if self.logger.enabled:
            faults.REGISTRY.set_logger(self.logger.line)
        self._faults_base = faults.REGISTRY.stats()
        # out-of-core I/O overlap ledger (common/iostats.py): same
        # process-lifetime baseline pattern as the fault counters
        from ..common.iostats import IO as _iostats
        self._io_base = _iostats.snapshot()
        self.mem = MemoryManager(name="context")
        from ..mem.hbm import HbmGovernor
        self.hbm = HbmGovernor(self, limit=self.config.hbm_limit)
        # memory-pressure resilience (mem/pressure.py): HBM admission
        # control + the OOM escalation ladder. Enabled only when a
        # budget is known (device memory_stats or THRILL_TPU_HBM_LIMIT)
        # — otherwise every dispatch pays one attribute read.
        from ..mem.pressure import PressureMonitor
        self.pressure = PressureMonitor(self.mesh_exec,
                                        governor=self.hbm)
        self.mesh_exec.pressure = self.pressure
        # stage memory negotiation state: bytes currently reserved by
        # active grants (reference: per-stage RAM distribution among
        # max-RAM requesters, api/dia_base.cpp:121-270)
        self._mem_reserved = 0
        self._mem_lock = threading.Lock()
        self.rng = np.random.default_rng(seed)
        self._nodes: List[Any] = []
        # coordinated-abort latch: set by abort() (and by close() when
        # an abort-class exception is in flight) so cleanup never runs
        # collectives against dead peers and leaked run files get swept
        self._aborted = False
        # generation-scoped failure domains: every pipeline run carries
        # the CURRENT generation id; an abort tears down only that
        # generation (ctx.pipeline() heals and bumps it) instead of
        # poisoning the whole Context. The net group shares the id so
        # poison frames / barriers are tagged consistently. The
        # counter is MONOTONIC and never reused (nested/sequential
        # blocks each get a fresh id; clean exits restore the parent
        # domain without ever re-issuing an id a node is stamped with).
        self.generation = 1
        self._gen_counter = 1
        self.net.group.generation = self.generation
        self.stats_pipeline_aborts = 0
        self.stats_heal_time_s = 0.0
        # elastic mesh (Context.resize): resizes completed on this
        # Context and the wall seconds they cost — the serve lane
        # reports both (a resize-free run must show 0 / 0.0)
        self.stats_resizes = 0
        self.stats_resize_time_s = 0.0
        # process-level elasticity (resize_processes): moves this
        # Context committed, and the exiting-for-relaunch latch —
        # once the marker is on disk the shutdown is LOCAL (the group
        # membership already drained; a shrink's survivors and its
        # departing ranks no longer share collective membership)
        self.stats_resizes_proc = 0
        self._resize_exiting = False
        # service plane (thrill_tpu/service/): the scheduler is
        # constructed lazily by the first submit(); current_tenant is
        # the tenant nodes created right now are stamped with (the
        # scheduler sets it around each job, service/tenancy.py's
        # activate() is the direct-use form)
        self.service = None
        self._service_lock = threading.Lock()
        self._closed = False
        self.current_tenant: Optional[str] = None
        # network front door (service/front_door.py): set when a
        # FrontDoor binds to this Context — closed before the
        # scheduler so no reader thread submits into a draining queue.
        # THRILL_TPU_SERVE_PORT auto-starts one (mirror of the metrics
        # endpoint above); loud degrade on bind failure, never fatal.
        self.front_door = None
        from ..service.front_door import maybe_start as _fd_start
        _fd_start(self)
        # autoscaler (service/autoscale.py): the policy thread that
        # watches queue depth / rejects / serve p99 and drives resize.
        # Off (None, zero overhead) unless THRILL_TPU_AUTOSCALE_S > 0;
        # stopped in close() before the front door so no decision
        # fires into a draining service plane.
        self.autoscaler = None
        from ..service.autoscale import maybe_start as _as_start
        self.autoscaler = _as_start(self)
        # persistent plan store (service/plan_store.py): learned
        # exchange capacities / narrow specs / plan kinds / pre-shuffle
        # verdicts seed the fresh mesh, so a warm restart re-runs a
        # known pipeline with zero data-driven plan builds. Off (zero
        # overhead) unless THRILL_TPU_PLAN_STORE is set.
        self.plan_store = None
        if self.config.plan_store and self.mesh_exec.num_processes > 1:
            # multi-controller meshes: RANK 0 reads the store and
            # BROADCASTS the entries over the host control plane, so
            # every rank installs the IDENTICAL seeds — the
            # asymmetric-read hazard (one rank cold, one seeded; a
            # corrupt file on one host) that used to force the loud
            # skip cannot arise, because only one read ever happens.
            # Rank 0 keeps the store handle (it is the single writer
            # at close; the learned state derives from replicated plan
            # inputs, so one rank's copy is the cluster's copy).
            # Without a spanning host control plane there is still no
            # agreement channel — keep the loud skip.
            if self.net.num_workers == self.mesh_exec.num_processes:
                from ..service.plan_store import (PlanStore,
                                                  install_entries)
                entries = None
                if self.host_rank == 0:
                    self.plan_store = PlanStore(self.config.plan_store,
                                                logger=self.logger)
                    entries = self.plan_store.load()
                entries = self.net.broadcast(entries, origin=0)
                seeded = install_entries(self.mesh_exec, entries or {},
                                         symmetric=True)
                # every rank now provably holds identical seeds, and
                # state learned from here derives from the replicated
                # send matrix: the optimistic exchange path stays open
                # on this mesh (data/exchange.py _optimistic_ok —
                # symmetric=True is the attestation; a storeless mesh
                # is symmetric by default, planner edge (a))
                self.mesh_exec._plan_seed_symmetric = True
                if self.logger.enabled:
                    self.logger.line(event="plan_store_load",
                                     path=self.config.plan_store,
                                     entries=seeded, broadcast=True)
                if self.decisions.enabled:
                    # the store_skip decision of old is now a
                    # store_broadcast one: explain() shows the warm
                    # start happened and how it stayed symmetric
                    self.decisions.record(
                        "store_broadcast", "plan_store",
                        "warm-start" if seeded else "cold",
                        rejected=[("per-rank-read", None)],
                        reason="rank-0 load broadcast over ctx.net "
                               "keeps SPMD plan seeds symmetric",
                        entries=seeded, path=self.config.plan_store)
            else:
                import sys
                print("thrill_tpu.service: THRILL_TPU_PLAN_STORE "
                      "ignored on a multi-process mesh without a "
                      "spanning host control plane (no channel to "
                      "broadcast rank 0's entries); recompiling cold",
                      file=sys.stderr)
                if self.decisions.enabled:
                    self.decisions.record(
                        "store_skip", "plan_store", "cold",
                        rejected=[("warm-start", None)],
                        reason="multi-process mesh without a host "
                               "control plane: rank-0 entries cannot "
                               "be broadcast",
                        path=self.config.plan_store)
        elif self.config.plan_store:
            from ..service.plan_store import PlanStore
            self.plan_store = PlanStore(self.config.plan_store,
                                        logger=self.logger)
            seeded = self.plan_store.attach(self.mesh_exec)
            if self.logger.enabled:
                self.logger.line(event="plan_store_load",
                                 path=self.config.plan_store,
                                 entries=seeded)
            if self.decisions.enabled \
                    and self.plan_store._last_corrupt is not None:
                # the corrupt-degrade is a plan decision too: the
                # service chose cold recompile over a torn store
                self.decisions.record(
                    "store_skip", "plan_store", "cold",
                    rejected=[("warm-start", None)],
                    reason="store corrupt: "
                           + self.plan_store._last_corrupt[:120],
                    path=self.config.plan_store)
        # checkpoint/resume subsystem (api/checkpoint.py): fully off —
        # ctx.checkpoint stays None, the stage driver pays one
        # attribute read — unless THRILL_TPU_CKPT_DIR is set
        self.checkpoint = None
        if self.config.ckpt_dir:
            from .checkpoint import CheckpointManager
            self.checkpoint = CheckpointManager(
                self, self.config.ckpt_dir,
                resume=resume or self.config.resume,
                auto=self.config.ckpt_auto)
        self._profiler = None
        if self.config.profile and self.logger.enabled:
            from ..common.profile import ProfileThread
            self._profiler = ProfileThread(self.logger).start()

    def _construct_host_group(self):
        from ..net import tcp
        import os
        if jax.process_count() > 1:
            # THRILL_TPU_NET selects the control-plane transport like
            # the reference's THRILL_NET (api/context.cpp:822-847):
            # tcp (default, authenticated full mesh) or mpi (mpi4py,
            # tag-namespace groups over COMM_WORLD)
            if os.environ.get("THRILL_TPU_NET") == "mpi":
                from ..net import mpi as mpi_net
                grp = mpi_net.construct(1)[0]
                if grp.num_hosts != jax.process_count():
                    raise ValueError(
                        f"MPI world has {grp.num_hosts} ranks but "
                        f"jax.process_count() is {jax.process_count()}")
                if grp.my_rank != jax.process_index():
                    raise ValueError(
                        f"MPI rank {grp.my_rank} disagrees with "
                        f"jax.process_index()={jax.process_index()} — "
                        f"the host control plane and the device mesh "
                        f"must use the same rank order")
                return grp
            grp = tcp.construct_from_env()
            if grp is not None:
                if grp.num_hosts != jax.process_count():
                    raise ValueError(
                        f"THRILL_TPU_HOSTLIST has {grp.num_hosts} hosts "
                        f"but jax.process_count() is "
                        f"{jax.process_count()}")
                if grp.my_rank != jax.process_index():
                    raise ValueError(
                        f"THRILL_TPU_RANK={grp.my_rank} disagrees with "
                        f"jax.process_index()={jax.process_index()} — "
                        f"the host control plane and the device mesh "
                        f"must use the same rank order")
                return grp
            import sys
            print("thrill_tpu: multi-process run without "
                  "THRILL_TPU_HOSTLIST — host-side control plane is "
                  "process-local only (cross-host scalar agreement "
                  "rides device collectives exclusively)",
                  file=sys.stderr)
        return tcp.TcpGroup(0, 1, {})

    # -- identity -------------------------------------------------------
    @property
    def num_workers(self) -> int:
        return self.mesh_exec.num_workers

    def _register_node(self, node) -> int:
        # stamp the failure domain: a heal disposes exactly the nodes
        # of the aborted generation (their shards may be partial) and
        # leaves earlier generations' cached results untouched. The
        # tenant stamp routes the node's HBM bytes to the per-tenant
        # ledger (mem/hbm.py, service/tenancy.py).
        node._generation = self.generation
        node._tenant = self.current_tenant
        self._nodes.append(node)
        return len(self._nodes) - 1

    # -- service plane (thrill_tpu/service/) ----------------------------
    def submit(self, pipeline_fn: Callable[["Context"], Any],
               tenant: str = "default", name: str = "",
               weight: Optional[float] = None):
        """Queue ``pipeline_fn(ctx) -> result`` for execution on this
        Context and return a :class:`~thrill_tpu.service.JobFuture`.

        Thread-safe: any number of client threads may submit; jobs
        serialize onto the SPMD mesh in weighted-fair order across
        tenants (service/scheduler.py). Each job runs in its own
        ``ctx.pipeline()`` failure domain — a failing job raises its
        :class:`PipelineError` from ``future.result()`` while the
        Context heals and later jobs run normally. Once a Context
        serves, run ALL its pipelines through submit(): the Context is
        not re-entrant, and a main-thread pipeline racing the
        dispatcher would interleave device programs."""
        svc = self.service
        if svc is None:
            # first submit may race across client threads: exactly ONE
            # scheduler (and dispatcher thread) may ever own the mesh
            with self._service_lock:
                if self._closed:
                    # a first submit AFTER close() must not construct
                    # a live scheduler over the torn-down mesh — it
                    # resolves failed, like a submit on a closed
                    # scheduler does
                    from ..service.scheduler import JobFuture
                    return JobFuture.failed(
                        0, tenant, name or "job-0",
                        RuntimeError("Context is closed"))
                svc = self.service
                if svc is None:
                    from ..service.scheduler import Scheduler
                    svc = self.service = Scheduler(self)
        return svc.submit(pipeline_fn, tenant=tenant, name=name,
                          weight=weight)

    # -- elastic mesh: W is a per-generation property --------------------
    def resize(self, num_workers: int) -> float:
        """Resize the mesh to ``num_workers`` logical workers at a
        generation boundary; returns the wall seconds it took.

        Every LIVE cached result (node shards held by ``.Keep`` or a
        pending consumer) is re-partitioned across the new W by the
        checkpoint serializer — the same dense-range split a fresh
        ``W'``-wide run lays data out with, so post-resize pipelines
        compute bit-identical to a fixed-``W'`` Context. Learned plan
        state is W-SHAPED and swaps atomically: the old W's sticky
        exchange capacities, cached programs and loop tapes are parked
        in a per-W archive (a later resize BACK restores them warm),
        while the HBM governor's tenant ledger, the scheduler and its
        WFQ queue carry across unchanged.

        On a SERVING Context the swap runs fenced on the dispatcher
        thread at the next job boundary: the in-flight job finishes on
        the old mesh, the swap runs exclusively (ahead of the queue —
        under sustained traffic the queue may never drain), and every
        queued future then runs on the new mesh and resolves normally.
        A job observes exactly one W for its whole run, never a
        half-swapped mesh.

        Single-process only: a JAX device mesh cannot change its
        process set, so on multi-controller deployments membership
        changes happen in the host control plane instead
        (``net.Group.resize`` / ``net.tcp.join_tcp_group``) and each
        process keeps its local devices. ``THRILL_TPU_RESIZE=0`` pins
        W entirely (this method raises)."""
        from ..net.group import resize_enabled
        if self._closed:
            raise RuntimeError("Context is closed")
        if not resize_enabled():
            raise RuntimeError(
                "THRILL_TPU_RESIZE=0 pins the worker count for this "
                "process; unset it to allow Context.resize")
        new_w = int(num_workers)
        if new_w < 1:
            raise ValueError("cannot resize to an empty mesh")
        if self.mesh_exec.num_processes > 1 \
                or self.net.num_workers > 1 or jax.process_count() > 1:
            raise RuntimeError(
                "Context.resize is single-process only: a JAX device "
                "mesh cannot add or drop processes at runtime. On a "
                "multi-controller deployment, change membership in "
                "the host control plane (net.Group.resize for "
                "survivors/leavers, net.tcp.join_tcp_group for a "
                "joining rank) and relaunch the job at the new W — "
                "see ARCHITECTURE.md \"Elastic mesh\"")
        if new_w == self.num_workers:
            return 0.0
        svc = self.service
        if svc is not None and svc.alive:
            # fenced: the dispatcher runs the swap between jobs, so no
            # pipeline ever traces against a half-swapped mesh. The
            # front door's verdict gate closes FIRST: a socket submit
            # that reaches its admission verdict while this fence is
            # pending must not be told "accept" with the generation
            # (and W) the swap is about to invalidate — its verdict
            # waits out the swap and names the post-resize generation.
            fd = self.front_door
            if fd is not None:
                fd.begin_resize_fence()
            try:
                return svc.fence(lambda: self._resize_now(new_w))
            finally:
                if fd is not None:
                    fd.end_resize_fence()
        return self._resize_now(new_w)

    def _resize_now(self, new_w: int) -> float:
        from ..mem.hbm import SpilledShards
        from .checkpoint import (commit_repartition, stage_repartition)
        t0 = time.monotonic()
        mex = self.mesh_exec
        old_w = mex.num_workers
        plat = mex.devices[0].platform
        devs = [d for d in jax.devices() if d.platform == plat]
        if new_w > len(devs):
            raise ValueError(
                f"resize to {new_w} needs {new_w} {plat} devices, "
                f"have {len(devs)}; set XLA_FLAGS="
                f"--xla_force_host_platform_device_count={new_w} "
                f"for CPU meshes")
        # 1) STAGE: serialize every live result to host bytes through
        # the checkpoint serializer. Pure reads — the repartition
        # fault site fires here, BEFORE anything mutated, so an
        # injected failure leaves the Context exactly as it was and
        # the next resize attempt starts clean.
        live = []
        for node in self._nodes:
            if getattr(node, "_shards", None) is None:
                continue
            if isinstance(node._shards, SpilledShards):
                # re-split works on materialized shards; touch()
                # transparently restores the spilled result first
                self.hbm.touch(node)
            live.append((node, stage_repartition(node._shards)))
        # 2) SWAP: the mesh itself (per-W plan state parks in the
        # archive inside), then the worker-level flow channel, which
        # is W-wide by construction
        mex.resize(devs[:new_w])
        self.flow = LocalFlowControl(new_w)
        # 3) COMMIT: rebuild every staged result on the new mesh and
        # re-admit it to the HBM ledger at its new true size (tenant
        # budgets and spill counters carry across untouched)
        for node, blob in live:
            self.hbm.on_release(node, None)
            node._shards = commit_repartition(mex, blob)
            self.hbm.on_cache(node)
        # 4) a fresh generation: results computed from here belong to
        # the new W's failure domain (the host group is trivial in a
        # single-process Context, so the barrier is local bookkeeping)
        self._gen_counter += 1
        self.generation = self._gen_counter
        self.net.group.begin_generation(self.generation)
        dt = time.monotonic() - t0
        self.stats_resizes += 1
        self.stats_resize_time_s += dt
        if self.logger.enabled:
            self.logger.line(event="resize", workers_old=old_w,
                             workers_new=new_w, nodes_moved=len(live),
                             generation=self.generation,
                             resize_time_s=round(dt, 4))
        return dt

    # -- process-level elasticity: drain → seal → relaunch as one move --
    def resize_processes(self, num_workers: int, state=None,
                         drain_timeout_s: Optional[float] = None):
        """Orchestrated process-level resize: drain the service plane,
        seal a RESIZE checkpoint epoch re-partitioned to ``W'``,
        agree the relaunch over the host group, commit the RESIZE
        marker, and exit every process with :data:`RESIZE_EXIT_CODE`
        so the supervisor (run-scripts/supervise.sh) relaunches the
        job at ``W'`` with ``THRILL_TPU_RESUME=1``. Never returns:
        raises :class:`ResizeRelaunch` (a SystemExit) on success.

        ``state`` is the DIA whose materialized shards carry across
        the move (``Execute()``/``.Keep`` it first); ``None`` commits
        a data-free move — the relaunch starts the job body from
        scratch at ``W'``. Call it on the MAIN thread only; an
        autoscaler ``apply_fn`` should signal the main loop rather
        than call this from the policy thread (a SystemExit raised on
        a helper thread kills just that thread).

        Crash-safety, step by step (the fault-matrix contract):

        1. DRAIN — front door stops admitting (typed ``draining``
           rejects, clients redial post-relaunch), local queue runs
           dry. Nothing durable changed; failure aborts clean.
        2. SEAL (``ckpt.resize_manifest``) — the W'-worker epoch.
           SIGKILL mid-seal leaves an uncommitted dir swept at next
           resume; a COMMITTED epoch with no marker is inert (the
           old-W resume's workers gate rejects it).
        3. GATE (``net.group.relaunch``) — mutation-free agreement
           every rank reached the move (shrink settles through the
           lenient departing-peer barrier). Failure aborts clean.
        4. MARKER (``ckpt.resize_manifest``, stage=marker) — the
           point of no return. Before it lands: relaunch heals at the
           old W. After: any relaunch — including the supervisor's
           retry after a SIGKILL right here — reads the marker and
           completes the move at ``W'``.
        5. EXIT — every rank raises :class:`ResizeRelaunch`; close()
           runs collective-free (``_resize_exiting``) since ranks exit
           at their own pace from here.
        """
        from ..common import faults
        from ..net.group import resize_enabled, resize_timeout_s
        if self._closed:
            raise RuntimeError("Context is closed")
        if not resize_enabled():
            raise RuntimeError(
                "THRILL_TPU_RESIZE=0 pins the worker count for this "
                "job; unset it to allow Context.resize_processes")
        if self.checkpoint is None:
            raise ValueError(
                "resize_processes needs THRILL_TPU_CKPT_DIR: the "
                "RESIZE epoch and the relaunch marker live in the "
                "checkpoint directory")
        new_w = int(num_workers)
        if new_w < 1:
            raise ValueError("cannot resize to an empty mesh")
        old_w = self.num_workers
        if new_w == old_w:
            raise ValueError(
                f"already running W={old_w}: resize_processes is a "
                f"whole-process relaunch, a same-W move would restart "
                f"the job for nothing")
        procs = max(1, self.mesh_exec.num_processes)
        local = max(1, old_w // procs)
        if procs > 1 and new_w % local:
            raise ValueError(
                f"W'={new_w} is not a multiple of the {local} "
                f"workers each process contributes; the supervisor "
                f"relaunches whole processes")
        target_procs = (new_w // local) if procs > 1 else 1
        timeout = (drain_timeout_s if drain_timeout_s is not None
                   else resize_timeout_s())
        t0 = time.monotonic()
        # 1) DRAIN
        if self.front_door is not None:
            self.front_door.drain()
        self._quiesce_service(timeout)
        # 2) SEAL
        epoch = None
        if state is not None:
            node = getattr(state, "node", state)
            shards = getattr(node, "_shards", None)
            if shards is None:
                raise ValueError(
                    f"resize_processes state {node.label!r} has no "
                    f"materialized shards; Execute()/Keep() it before "
                    f"the move")
            epoch = self.checkpoint.seal_resize(node, shards, new_w)
        # 3) GATE — settle the move's generation over the old group
        gen = self._gen_counter + 1
        self.net.group.prepare_relaunch(target_procs, gen)
        self._gen_counter = gen
        self.generation = gen
        # 4) MARKER — the point of no return
        self.checkpoint.commit_resize_marker(
            new_w, epoch=epoch, generation=gen, procs=target_procs)
        # 5) EXIT
        self._resize_exiting = True
        self.stats_resizes_proc += 1
        dt = time.monotonic() - t0
        self.stats_resize_time_s += dt
        faults.note("recovery", what="ctx.resize_processes",
                    old_w=old_w, new_w=new_w, epoch=epoch,
                    generation=gen, _quiet=True)
        if self.logger.enabled:
            self.logger.line(event="resize_processes",
                             workers_old=old_w, workers_new=new_w,
                             procs_old=procs, procs_new=target_procs,
                             epoch=epoch, generation=gen,
                             seconds=round(dt, 4))
        raise ResizeRelaunch(new_w, epoch=epoch, generation=gen)

    def _quiesce_service(self, timeout: float) -> None:
        """Wait until the local scheduler has no queued or in-flight
        job (``jobs_done`` catches up to ``jobs_submitted``). The
        front door is already draining, so no NEW work arrives over
        the socket edge; direct ``ctx.submit`` callers are expected to
        stop submitting around a resize — under sustained direct
        traffic this times out and the move aborts clean."""
        svc = self.service
        if svc is None or not svc.alive:
            return
        deadline = time.monotonic() + max(0.1, float(timeout))
        while True:
            with svc._cv:
                idle = (svc.queue.depth == 0
                        and svc.jobs_done >= svc.jobs_submitted)
            if idle:
                break
            if time.monotonic() > deadline:
                raise TimeoutError(
                    f"resize_processes: service did not drain within "
                    f"{timeout:.1f}s (queued={svc.queue.depth}, "
                    f"in_flight="
                    f"{svc.jobs_submitted - svc.jobs_done}); the move "
                    f"aborted with nothing mutated")
            time.sleep(0.02)
        if self.net.num_workers > 1:
            # multi-controller: the follower dispatchers park in a net
            # recv waiting for rank 0's next ordering frame, so the
            # move's seal/gate collectives below would race that recv
            # for frames. Stop the scheduler collectively instead —
            # rank 0's close broadcasts the drain sentinel and every
            # rank's dispatcher exits at the same control-plane point
            # (TCP ordering puts the sentinel after the last job's
            # frames). Every drained future has already resolved; a
            # submit after an aborted move lazily builds a fresh
            # scheduler, so the abort still leaves a serving Context.
            svc.close(timeout=timeout)
            self.service = None

    # -- stage memory negotiation ---------------------------------------
    # Reference: the StageBuilder distributes worker RAM per stage —
    # fixed DIAMemUse requests are subtracted, the remainder is split
    # evenly among ops requesting DIAMemUse::Max
    # (api/dia_base.cpp:121-270). Pull-model translation: requesters
    # negotiate on entry to compute() and RESERVE their grant until
    # release; a "max" requester gets half of the remaining pool, so
    # nested concurrent requesters (recursive Sorts) get geometrically
    # smaller shares and the pool is never over-committed (the
    # reference can split exactly because a stage's requesters are
    # known up front; here they arrive dynamically).
    @property
    def ram_workers(self) -> int:
        """Host-RAM pool for operator workspace (one third of the
        configured or detected RAM, reference MemoryConfig split,
        api/context.cpp:1082-1093)."""
        ram = getattr(self, "_ram_workers", None)
        if ram is None:
            total = self.config.ram or self.config.host_ram
            if not total:
                try:
                    total = (os.sysconf("SC_PAGE_SIZE")
                             * os.sysconf("SC_PHYS_PAGES"))
                except (ValueError, OSError):
                    total = 8 << 30
            from ..mem.manager import MemoryConfig
            ram = self._ram_workers = MemoryConfig.split(total).ram_workers
        return ram

    def negotiate_mem(self, node) -> bool:
        """Grant ``node.mem_limit`` per its ``mem_use()`` request.
        Returns True when something was granted (caller must
        release_mem after compute)."""
        req = node.mem_use()
        if req is None:
            node.mem_limit = None
            return False
        with self._mem_lock:   # net layer is multi-threaded; stay safe
            remaining = max(self.ram_workers - self._mem_reserved, 4096)
            if req == "max":
                grant = max(remaining // 2, 4096)
            else:
                grant = min(int(req), remaining)
            self._mem_reserved += grant
            reserved = self._mem_reserved
        node.mem_limit = grant
        node._mem_grant = grant
        short = req != "max" and grant < int(req)
        if self.logger.enabled:
            self.logger.line(event="mem_negotiate", node=node.label,
                             dia_id=node.id, grant=grant,
                             reserved=reserved,
                             short=short or None)
        if short:
            # fixed-size requesters must see they got less than asked —
            # they read node.mem_limit (the granted amount) to adapt
            import sys
            print(f"thrill_tpu: mem_negotiate short grant for "
                  f"{node.label}: requested {req}, granted {grant}",
                  file=sys.stderr)
        return True

    def release_mem(self, node) -> None:
        grant = getattr(node, "_mem_grant", 0)
        if grant:
            with self._mem_lock:
                self._mem_reserved -= grant
        node._mem_grant = 0

    # -- sources (created lazily like every DIA op) ---------------------
    def Generate(self, size: int, fn: Optional[Callable] = None,
                 storage: Optional[str] = None):
        from .ops import sources
        return sources.Generate(self, size, fn, storage)

    def Distribute(self, items, storage: Optional[str] = None):
        from .ops import sources
        return sources.Distribute(self, items, storage)

    def EqualToDIA(self, items, storage: Optional[str] = None):
        """Every-worker-identical local data -> DIA (reference:
        api/equal_to_dia.hpp:30; here identical by construction)."""
        from .ops import sources
        return sources.Distribute(self, items, storage)

    def ConcatToDIA(self, per_worker_items, storage: Optional[str] = None):
        from .ops import sources
        return sources.ConcatToDIA(self, per_worker_items, storage)

    def ReadLines(self, path_or_glob: str):
        from .ops import read_write
        return read_write.ReadLines(self, path_or_glob)

    def ReadWordsPacked(self, path_or_glob: str, max_word: int = 16):
        """Text -> device DIA of {"w": [max_word] uint8} packed words
        (vectorized tokenization; device-native WordCount input)."""
        from .ops import read_write
        return read_write.ReadWordsPacked(self, path_or_glob, max_word)

    def ReadBinary(self, path_or_glob: str, dtype, record_shape=()):
        from .ops import read_write
        return read_write.ReadBinary(self, path_or_glob, dtype, record_shape)

    # -- plan observatory (common/decisions.py) -------------------------
    def explain(self, pipeline_fn: Optional[Callable] = None,
                name: str = "") -> str:
        """Render the physical plan as an annotated tree: ops, fused
        segments, the exchange strategy per shuffle edge, and every
        recorded decision with its reason and (post-run) its audit
        verdict.

        ``ctx.explain(pipeline_fn)`` runs ``pipeline_fn(ctx)`` and
        renders exactly the nodes that run created; ``ctx.explain()``
        renders everything this Context has built so far. Purely
        observational: reads the decision ledger, changes no plan."""
        from ..common.decisions import render_plan
        lo = 0
        if pipeline_fn is not None:
            lo = len(self._nodes)
            pipeline_fn(self)
        nodes = self._nodes[lo:]
        return render_plan(
            [{"id": n.id, "label": n.label, "state": n.state,
              "parents": [p.node.id for p in n.parents]}
             for n in nodes],
            self.decisions.snapshot(), W=self.num_workers,
            title=name or (getattr(pipeline_fn, "__name__", "")
                           if pipeline_fn is not None else ""))

    def doctor_report(self, k: int = 5) -> dict:
        """The performance doctor's full diagnosis for this Context:
        wait attribution + straggler scores, per-site skew table, and
        the critical path computed over the tracer's span ring (the
        post-run pass; tools/doctor_report.py is the offline twin over
        merged logs). Returns {} with THRILL_TPU_DOCTOR=0. Purely
        observational — local state only, never a collective."""
        if self.doctor is None:
            return {}
        ring = self.tracer.ring if self.tracer.enabled else None
        return self.doctor.report(ring=ring or (), k=k)

    def overall_stats(self, local_only: bool = False) -> dict:
        """End-of-job summary (reference: OverallStats AllReduce,
        api/context.cpp:1235-1341). In multi-process runs the per-host
        stats are aggregated over the host control plane (``ctx.net``):
        counters sum, peaks take the max.

        ``local_only=True`` NEVER enters the cross-host collective —
        the metrics endpoint's scrape thread (common/metrics.py) uses
        it so a scrape can run while the service dispatcher owns the
        control plane (the PR-9 local-view stats rule)."""
        mex = self.mesh_exec
        # fold real process RSS into the reported peak (reference:
        # malloc_tracker feeds OverallStats the true allocation peak)
        self.mem.sample_rss()
        stats = {
            "workers": self.num_workers,
            "nodes_created": len(self._nodes),
            "nodes_executed": sum(1 for n in self._nodes
                                  if n.state != "NEW"),
            "exchanges": mex.stats_exchanges,
            "items_moved": mex.stats_items_moved,
            "bytes_moved": mex.stats_bytes_moved,
            # overlapped exchange data plane (data/exchange.py):
            # exchanges dispatched with NO mid-shuffle host sync, the
            # capacity-plan cache's hit/miss record, and the bytes that
            # actually cross the fabric (padded device rows) / the TCP
            # wire (serialized host frames) — bytes_on_wire is the
            # pinned baseline for ROADMAP's shrink-the-wire item
            "exchanges_overlapped": mex.stats_exchanges_overlapped,
            "cap_cache_hits": mex.stats_cap_cache_hits,
            "cap_cache_misses": mex.stats_cap_cache_misses,
            # send blocks cut as slices of dest-sorted rows by the
            # dispatched exchange programs (data/exchange.py)
            "xchg_send_slices": mex.stats_xchg_send_slices,
            # rows into the exchanges, those kept on their own worker,
            # and the exchanges that ran duplicate detection's presence
            # registers (data/exchange.py, api/ops/reduce.py)
            "xchg_rows_in": mex.stats_xchg_rows_in,
            "xchg_rows_local": mex.stats_xchg_rows_local,
            "dup_detect_exchanges": mex.stats_dup_detect_exchanges,
            "bytes_wire_device": mex.stats_bytes_wire_device,
            "bytes_wire_host": mex.stats_bytes_wire_host,
            "bytes_on_wire": (mex.stats_bytes_wire_device
                              + mex.stats_bytes_wire_host),
            # shrink-the-wire layer (ISSUE 7): the raw-equivalent
            # volume (full-width device rows + host frame bytes before
            # the column codec) and the resulting compression ratio —
            # >= 1.0, exactly 1.0 with THRILL_TPU_WIRE_COMPRESS=0
            "bytes_wire_device_raw": mex.stats_bytes_wire_device_raw,
            "bytes_wire_host_saved": mex.stats_bytes_wire_host_saved,
            "bytes_on_wire_raw": (mex.stats_bytes_wire_device_raw
                                  + mex.stats_bytes_wire_host
                                  + mex.stats_bytes_wire_host_saved),
            "wire_compress_ratio": _wire_ratio(
                mex.stats_bytes_wire_device_raw
                + mex.stats_bytes_wire_host
                + mex.stats_bytes_wire_host_saved,
                mex.stats_bytes_wire_device
                + mex.stats_bytes_wire_host),
            # dispatch / upload / fetch counts: the budgets pinned by
            # tests/api/test_dispatch_budget.py
            "device_dispatches": mex.stats_dispatches,
            "device_uploads": mex.stats_uploads,
            "device_fetches": mex.stats_fetches,
            # host-phase seconds and bytes, added where each phase's
            # span ends (parallel/mesh.py): an upload ends when
            # device_put returns; sync_wait is the thread blocked on
            # the device before a fetch's copy; compiles are backend
            # compiles or compile-cache loads under a dispatch
            "upload_s": mex.stats_upload_s,
            "upload_bytes": mex.stats_upload_bytes,
            "stage_copy_bytes": mex.stats_stage_copy_bytes,
            "fetch_s": mex.stats_fetch_s,
            "fetch_bytes": mex.stats_fetch_bytes,
            "sync_wait_s": mex.stats_sync_wait_s,
            "compiles": mex.stats_compiles,
            "compile_s": mex.stats_compile_s,
            # program stitching (api/fusion.py): how many dispatches
            # the fused runner launched, how many DOp segments they
            # carried (ops/dispatch > 1 means chains actually fused),
            # and the per-stage composition table
            "fused_dispatches": mex.stats_fused_dispatches,
            "fused_ops": mex.stats_fused_ops,
            # dict() snapshot: the metrics scrape thread calls this
            # with local_only=True while the dispatcher inserts new
            # stage compositions — iterating the live dict would die
            # mid-scrape on "changed size during iteration"
            "fused_stages": {" + ".join(ops): n for ops, n in
                             dict(mex.fused_stage_counts).items()},
            # iteration execution layer (api/loop.py): captures vs
            # replayed iterations (zero graph build / planning), whole-
            # loop fori_loop iterations, loud replay fallbacks, and
            # HBM bytes donated back to XLA on replayed dispatches
            "loop_plan_builds": mex.stats_loop_plan_builds,
            "loop_plan_rebinds": mex.stats_loop_plan_rebinds,
            "r2i_index_plans": mex.stats_r2i_index_plans,
            "r2i_dense_plans": mex.stats_r2i_dense_plans,
            "sort_keys_reused": mex.stats_sort_keys_reused,
            # send histograms the dispatched programs counted by
            # comparison (data/exchange.py send_counts)
            "send_hists_by_compare": mex.stats_send_hists_by_compare,
            "pulls": mex.stats_pulls,
            "loop_replays": mex.stats_loop_replays,
            "loop_fori_iters": mex.stats_loop_fori_iters,
            "loop_replay_fallbacks": mex.stats_loop_fallbacks,
            "loop_donated_bytes": mex.stats_loop_donated_bytes,
            "host_mem_peak": self.mem.peak,
            "hbm_peak": self.hbm.mem.peak,
            "hbm_spills": self.hbm.spill_count,
            "hbm_restores": self.hbm.restore_count,
            # memory-pressure ladder (mem/pressure.py): the admission
            # cost model's high watermark, OOM-retry dispatches,
            # segment splits and bytes spilled under pressure
            **self.pressure.stats(),
            # robustness layer: lineage retries of hinted joins plus
            # the process-wide fault/retry/abort counters
            # (common/faults.py)
            "join_overflow_retries": mex.stats_join_overflow_retries,
            # generation-scoped failure domains: pipelines aborted on
            # this Context (each healed, not fatal), time spent
            # healing, links repaired by the tcp reconnect, and stale
            # prior-generation frames the filter dropped — the seed
            # metrics for the sustained-traffic harness
            "generation": self.generation,
            "pipeline_aborts": self.stats_pipeline_aborts,
            "heal_time_s": round(self.stats_heal_time_s, 4),
            # elastic mesh: W changes this Context performed and their
            # wall cost (0 / 0.0 proves the machinery idle when unused)
            "resizes": self.stats_resizes,
            "resize_time_s": round(self.stats_resize_time_s, 4),
            # process-level elasticity (resize_processes) and the
            # autoscaler that drives it: orchestrated moves committed
            # by this Context, policy decisions/ticks, and EM runs
            # adopted from departed ranks — all pinned EXACTLY zero on
            # non-elastic workloads by the perf sentinel
            "resizes_proc": self.stats_resizes_proc,
            **(self.autoscaler.stats()
               if getattr(self, "autoscaler", None) is not None
               else {"autoscale_decisions": 0, "autoscale_ticks": 0}),
            "runs_adopted": _em_adopted(),
            "conn_reconnects": getattr(self.net.group,
                                       "stats_reconnects", 0),
            "stale_frames_dropped": getattr(self.net.group,
                                            "stats_stale_dropped", 0),
            # service plane (thrill_tpu/service/): admission counters
            # from the scheduler, per-tenant HBM peaks from the
            # governor ledger, and the plan-store counters — a warm
            # restart of a known pipeline reports plan_builds == 0
            **(self.service.stats() if self.service is not None else
               {"jobs_submitted": 0, "jobs_failed": 0,
                "jobs_rejected": 0, "jobs_rate_limited": 0,
                "queue_depth_peak": 0}),
            # front door (service/front_door.py): socket-edge counters
            # when this Context serves external clients — all zero (and
            # absent machinery) otherwise
            **(self.front_door.stats()
               if getattr(self, "front_door", None) is not None
               else {"fd_conns_accepted": 0, "fd_conns_dropped": 0,
                     "fd_jobs_submitted": 0, "fd_jobs_rejected": 0,
                     "fd_chunks_sent": 0, "fd_slow_clients": 0,
                     "fd_deadline_expired": 0}),
            "tenant_hbm_peaks": dict(self.hbm.tenant_peaks),
            "tenant_spills": self.hbm.tenant_spill_count,
            "plan_builds": mex.stats_plan_builds,
            "plan_store_hits": mex.stats_plan_store_hits,
            # adaptive planner (api/planner.py): sites whose learned
            # plan was invalidated and re-chosen after an audit/
            # deferred-check lie, and re-choices that actually changed
            # the plan — 0/0 on a run whose learned stats held
            **(self.planner.stats() if self.planner is not None else
               {"planner_replans": 0, "planner_switches": 0}),
            # plan observatory (common/decisions.py): how many plan
            # choices were recorded, how many have joined actuals, and
            # the per-kind accuracy ledger (mean |log2 pred/actual|) —
            # the number the ROADMAP adaptive planner will be judged by
            "decisions_recorded": sum(
                self.decisions.kind_counts.values()),
            "decisions_joined": sum(
                self.decisions.joined_counts.values()),
            "decision_accuracy": {
                k: v["mae_log2"]
                for k, v in self.decisions.accuracy().items()
                if v.get("mae_log2") is not None},
            # performance doctor (common/doctor.py): seconds blocked
            # at collectives/exchange barriers with the per-peer
            # arrival deltas and the net/exchange/io/skew
            # decomposition, plus the worst partition-skew ratio any
            # exchange site observed
            **(self.doctor.stats() if self.doctor is not None else
               {"collective_wait_s": 0.0, "wait_net_s": 0.0,
                "wait_exchange_s": 0.0, "wait_io_s": 0.0,
                "wait_skew_s": 0.0, "straggler_waits": {},
                "skew_ratio": 0.0}),
            # service-plane latency histograms (service/scheduler.py):
            # deterministic log2-bucket accept-to-result quantiles per
            # tenant, {} until a job completed
            **({"serve_p50_ms": {}, "serve_p99_ms": {}}
               if self.service is None
               else self.service.latency_quantiles()),
        }
        # durability layer (api/checkpoint.py): epochs committed, bytes
        # sealed, ops skipped by resume, time spent restoring
        if self.checkpoint is not None:
            stats.update(self.checkpoint.stats())
        from ..common import faults
        stats.update({k: v - self._faults_base.get(k, 0)
                      for k, v in faults.REGISTRY.stats().items()})
        # out-of-core storage tier (vfs prefetch readers, write-behind
        # spill, double-buffered restore): hit/miss record, foreground
        # seconds lost to I/O, background busy seconds, write-behind
        # volume and queue high-water mark, restores that overlapped
        from ..common.iostats import IO as _iostats
        stats.update(_iostats.delta(_iostats.snapshot(),
                                    self._io_base))
        if self.net.num_workers > 1 and not local_only \
                and not self._aborted and self.service is None \
                and not self._resize_exiting:
            # once a rank has EVER served, degrade to the local view
            # permanently: while dispatchers live, the non-root ranks'
            # park in a recv on this same untagged control plane
            # waiting for ordering frames — an application-thread
            # all_gather here would race them for frames — and the
            # skip decision must be CROSS-RANK DETERMINISTIC, which
            # `service.alive` is not (a one-rank poison kills one
            # dispatcher while its peers' survive; scheduler
            # CONSTRUCTION is lockstep under the submission contract,
            # so gating on it keeps every rank on the same branch).
            per_host = self.net.all_gather(stats)
            # almost every counter is a per-controller view of one
            # global value (exchange stats derive from the replicated
            # send matrix, the mesh spans all hosts, the DAG is one
            # logical graph) — take host 0's copy, don't sum. Only the
            # host-process-local peaks (and the per-process fault/
            # retry/abort counters) genuinely differ across hosts.
            local_peaks = {"host_mem_peak", "recovery_time_s",
                           "hbm_high_watermark", "heal_time_s"}
            local_peaks |= {"writeback_queue_peak"}
            # the worst skew any rank observed is the cluster's skew
            local_peaks |= {"skew_ratio"}
            local_sums = {"faults_injected", "faults_delayed",
                          "retries", "recoveries",
                          "aborts", "ckpt_bytes_written", "oom_retries",
                          "segment_splits", "host_fallbacks",
                          "admission_spills", "pressure_spilled_bytes",
                          # out-of-core tier: per-process background
                          # I/O flows sum; the queue peak maxes
                          "prefetch_hits", "prefetch_misses",
                          "io_wait_s", "io_busy_s", "writeback_bytes",
                          "restore_overlaps", "spill_runs",
                          "prefetch_submits", "records_blocks",
                          # link repairs and stale-frame drops are
                          # per-process transport events; the abort/
                          # generation counters are coordinated (host
                          # 0's copy, the default, is the global view)
                          "conn_reconnects", "stale_frames_dropped",
                          # adopted EM runs are per-process transport-
                          # local events too (each adopting rank
                          # rewrote its own OWNER records)
                          "runs_adopted",
                          # host frames (and their codec savings) are
                          # per-process partials; the device wire
                          # bytes — actual and raw — derive from the
                          # replicated send matrix (host 0's copy)
                          "bytes_wire_host", "bytes_wire_host_saved",
                          # per-process tenant spills sum; the service
                          # admission counters and plan-build/store
                          # counters are coordinated (lockstep
                          # submission / replicated plan decisions —
                          # host 0's copy, the default). The
                          # tenant_hbm_peaks DICT also stays host 0's
                          # view: per-process governor ledgers.
                          "tenant_spills",
                          # doctor wait ledgers are per-process blocked
                          # seconds: cluster view sums them (the
                          # straggler_waits DICT merges per-key below)
                          "collective_wait_s", "wait_net_s",
                          "wait_exchange_s", "wait_io_s",
                          "wait_skew_s"}
            stats = {
                k: (max(h[k] for h in per_host) if k in local_peaks
                    else sum(h.get(k, 0) for h in per_host)
                    if k in local_sums else per_host[0][k])
                for k in stats}
            stats["bytes_on_wire"] = (stats["bytes_wire_device"]
                                      + stats["bytes_wire_host"])
            stats["bytes_on_wire_raw"] = (
                stats["bytes_wire_device_raw"]
                + stats["bytes_wire_host"]
                + stats["bytes_wire_host_saved"])
            stats["wire_compress_ratio"] = _wire_ratio(
                stats["bytes_on_wire_raw"], stats["bytes_on_wire"])
            # global straggler blame: rank r's score is the sum over
            # EVERY rank of the seconds that rank spent waiting on r
            merged_waits: dict = {}
            for h in per_host:
                for p, w in (h.get("straggler_waits") or {}).items():
                    merged_waits[p] = merged_waits.get(p, 0.0) + w
            stats["straggler_waits"] = {
                p: round(w, 4) for p, w in sorted(merged_waits.items())}
            stats["hosts"] = len(per_host)
        return stats

    # -- generation-scoped failure domains ------------------------------

    @contextlib.contextmanager
    def pipeline(self, name: str = ""):
        """Scoped failure domain for one pipeline run.

        Any error escaping the block aborts ONLY this pipeline: the
        Context heals (stale in-flight frames drained by generation
        tag, the failed run's HBM reservations and cached-shard pins
        released, deferred checks cancelled, dropped TCP links
        reconnected, watchdog + heartbeat re-armed) and surfaces a
        catchable :class:`PipelineError` carrying the root cause and
        generation — the next pipeline on this same Context runs
        bit-identical to a fresh-Context run.

        Unrecoverable verdicts (heartbeat-confirmed dead peer, or a
        heal that itself fails) re-raise the ORIGINAL abort so the
        supervised relaunch + resume path still engages. Yields the
        generation id of this run.

        Entering the block starts a FRESH generation (a never-reused
        id off a monotonic counter), so nodes cached by earlier
        successful pipelines (or created between blocks) belong to
        other generations and survive this block's abort — only THIS
        run's nodes are disposed by the heal. A nested block's clean
        exit restores the ENCLOSING failure domain, so an outer abort
        heals the outer run's nodes, not the nested survivor's. In
        multi-controller runs every controller must enter/exit
        pipeline() at the same program points (the same lockstep
        contract every collective already has)."""
        parent = self.generation
        self._gen_counter += 1
        self.generation = self._gen_counter
        self.net.group.generation = self.generation
        gen = self.generation
        try:
            yield gen
            # a deferred check crossing the boundary belongs to THIS
            # pipeline: surface it here, inside the failure domain
            self.mesh_exec.drain_checks()
        except PipelineError:
            # a nested pipeline() already aborted, healed and wrapped
            # this failure — pass it through, never double-heal (a
            # second barrier would waste a collective round and the
            # re-wrap would misreport the failed generation). Node
            # stamping resumes in the enclosing domain.
            self.generation = parent
            raise
        except Exception as e:
            replacement = self._pipeline_failed(e, name)
            if replacement is e:
                raise
            # healed: execution resumes in the ENCLOSING domain — a
            # caller catching this PipelineError continues the outer
            # block with its own generation, so the outer run's nodes
            # (stamped before AND after this failed block) share one
            # id and a later outer abort heals all of them. The WIRE
            # epoch (group.generation) stays at the heal's advanced
            # value so the failed generation's frames read as stale.
            self.generation = parent
            raise replacement from e
        else:
            # clean exit: pop back to the enclosing failure domain
            # (frames tagged with this block's id stay >= the restored
            # group generation, so nothing of a LIVE outer run ever
            # reads as stale)
            self.generation = parent
            self.net.group.generation = parent

    def _pipeline_failed(self, exc: BaseException,
                         name: str = "") -> BaseException:
        """Abort bookkeeping + heal; returns the exception the caller
        should raise (a PipelineError after a successful heal, the
        original otherwise)."""
        from ..common import faults
        from ..net.group import ClusterAbort
        failed_gen = self.generation
        unrecoverable = (isinstance(exc, ClusterAbort)
                         and not getattr(exc, "recoverable", True))
        origin = int(getattr(exc, "origin", self.host_rank))
        cause = str(getattr(exc, "cause", "") or
                    f"{type(exc).__name__}: {exc}")
        self.stats_pipeline_aborts += 1
        if self.logger.enabled:
            self.logger.line(event="pipeline_abort", origin=origin,
                             generation=failed_gen,
                             pipeline=name or None,
                             recoverable=not unrecoverable,
                             cause=cause[:300])
        # flight recorder: every abort leaves a self-contained
        # post-mortem — the ring's final spans name the failing site
        # (error attrs) and the generation; the decision ledger lands
        # beside it (the chaos sweep archives both: what the planner
        # chose on the road to this abort). Best-effort by contract.
        try:
            self.decisions.dump_beside(
                self.tracer.dump_flight(cause, generation=failed_gen))
        except Exception:
            pass
        if (self.net.num_workers > 1
                and not isinstance(exc, ClusterAbort)):
            # a RANK-LOCAL failure (user logic, per-rank I/O): the
            # peers never saw it and would not enter their own heal —
            # the generation barrier would then wait on ranks that
            # never aborted. Poison them first so every controller
            # aborts this generation and meets us at the barrier.
            try:
                self.net.group.poison_peers(cause)
            except Exception:
                pass
        if not unrecoverable:
            try:
                self._heal(failed_gen)
            except Exception as he:
                unrecoverable = True
                faults.note("recovery", what="heal_failed",
                            gen=failed_gen, error=repr(he))
        if unrecoverable:
            self._aborted = True
            return exc
        return PipelineError(origin, cause, failed_gen, root=exc)

    def _heal(self, failed_gen: int) -> None:
        """Tear down generation ``failed_gen`` and make the Context as
        good as fresh: dispose the failed run's nodes (releasing the
        HbmGovernor ledger entries, cached-shard pins, spilled blocks
        and host-RAM grants), cancel its deferred checks and any live
        loop capture, then run the fresh-generation barrier over the
        host group (reconnecting dropped TCP links, draining stale
        in-flight frames by generation tag) and re-arm the heartbeat
        monitor. Raises when the mesh cannot be healed (dead peer,
        reconnect failure, barrier timeout)."""
        from .dia_base import DISPOSED
        t0 = time.monotonic()
        mex = self.mesh_exec
        # the healed domain gets a FRESH never-reused id: past
        # failed_gen (stale-frame ordering) AND past every id nested
        # blocks already consumed (never collide with a surviving
        # node's stamp)
        self._gen_counter = max(self._gen_counter, failed_gen) + 1
        self.generation = self._gen_counter
        checks_dropped = mex.reset_run_state()
        released = 0
        for node in self._nodes:
            if getattr(node, "_generation", 0) != failed_gen:
                continue
            self.release_mem(node)
            if node.state == DISPOSED:
                continue
            try:
                node.dispose()
                released += 1
            except Exception:
                pass           # best effort: the ledger entry is gone
        # the transport heal + barrier is the COLLECTIVE part: every
        # controller that aborted this generation enters it. A rank
        # that MISSED the cluster's abort adopts the newer generation
        # its peers' barrier markers announced — re-sync local ids to
        # whatever the barrier settled on.
        stale = self.net.group.begin_generation(self.generation)
        self.generation = max(self.generation,
                              self.net.group.generation)
        self._gen_counter = max(self._gen_counter, self.generation)
        # re-arm liveness probing if the monitor thread has exited
        # (it stops itself only on a dead-peer verdict, which is
        # unrecoverable — this covers monitors stopped by tests or a
        # future recoverable-stop path)
        hb = getattr(self.net.group, "_heartbeat", None)
        if hb is not None and (hb._thread is None
                               or not hb._thread.is_alive()):
            from ..net import heartbeat
            self.net.group._heartbeat = heartbeat.maybe_start(
                self.net.group)
        self._aborted = False
        dt = time.monotonic() - t0
        self.stats_heal_time_s += dt
        if self.logger.enabled:
            self.logger.line(event="heal", generation=self.generation,
                             heal_time_s=round(dt, 4),
                             nodes_released=released,
                             checks_dropped=checks_dropped,
                             stale_frames=stale)

    def abort(self, cause: Any) -> None:
        """Coordinated abort: broadcast ``cause`` as a poison control
        frame to every controller (each peer surfaces it as a
        ClusterAbort carrying this ROOT CAUSE within its own recv
        deadline — no cascade of secondary timeouts), then raise it
        locally. The ``event=abort`` line is emitted BEFORE the raise
        (with origin + generation), so single-rank aborts — where no
        poison frame is ever sent — are visible in json2profile
        exactly like poisoned ones."""
        from ..net.group import ClusterAbort
        self._aborted = True
        if self.logger.enabled:
            cause_s = (f"{type(cause).__name__}: {cause}"
                       if isinstance(cause, BaseException)
                       else str(cause))
            self.logger.line(event="abort", origin=self.host_rank,
                             generation=self.generation,
                             cause=cause_s[:300])
        try:
            self.decisions.dump_beside(self.tracer.dump_flight(
                cause, generation=self.generation))
        except Exception:
            pass
        if self.net.num_workers > 1:
            self.net.group.poison_peers(cause)
        if isinstance(cause, BaseException):
            raise cause
        raise ClusterAbort(self.host_rank, str(cause),
                           generation=self.generation)

    def collective_mean_stdev(self, value: float):
        """(mean, stdev) of a per-controller scalar across the cluster
        — a COLLECTIVE; every controller must call it (reference:
        PrintCollectiveMeanStdev, api/context.hpp:352-375)."""
        vals = [float(v) for v in self.net.all_gather(float(value))]
        mean = sum(vals) / len(vals)
        var = sum((v - mean) ** 2 for v in vals) / len(vals)
        return mean, var ** 0.5

    def print_collective_mean_stdev(self, label: str,
                                    value: float) -> None:
        """Rank-0 prints mean/stdev of a per-controller scalar."""
        mean, stdev = self.collective_mean_stdev(value)
        if self.host_rank == 0:
            print(f"{label}: mean {mean:.6g} stdev {stdev:.6g} over "
                  f"{self.net.num_workers} hosts", flush=True)

    def note_failure(self, exc: BaseException) -> None:
        """Called by the run wrappers with an exception PROPAGATING out
        of the job (not sniffed from sys.exc_info(), which would also
        see exceptions merely being handled further up the stack — a
        successful nested retry Run inside an ``except ClusterAbort``
        must not shut down as aborted). A framework-owned abort
        (poisoned group, hung collective) switches close() to the
        aborted shutdown: no collectives against dead peers, sweep the
        run's leaked artifacts. Deliberately narrow: a user job's own
        ConnectionError/TimeoutError must NOT skip the collective
        shutdown the other ranks are entering (the detectors —
        watchdog, heartbeat, poison frames — convert real worker loss
        into ClusterAbort)."""
        from ..net.group import ClusterAbort, CollectiveHangTimeout
        if isinstance(exc, (ClusterAbort, CollectiveHangTimeout)):
            self._aborted = True
            # an abort escaping the whole job (no ctx.pipeline() heal
            # caught it) still leaves its post-mortem
            try:
                self.decisions.dump_beside(self.tracer.dump_flight(
                    exc, generation=getattr(exc, "generation",
                                            self.generation)))
            except Exception:
                pass

    def close(self) -> None:
        from ..net.group import ClusterAbort
        # an abort DISCOVERED during close itself (heartbeat latch, or
        # a peer's poison frame surfacing in the stats collective) must
        # complete the cleanup AND still surface: a surviving rank
        # whose job body already finished would otherwise exit 0 and a
        # supervisor would relaunch only the dead rank — stranding it
        # in bootstrap against a rank that never comes back
        discovered: Optional[BaseException] = None
        # metrics endpoint first: no scrape may observe (or race) the
        # teardown below
        if getattr(self, "_metrics", None) is not None:
            self._metrics.close()
            self._metrics = None
        # service plane first: drain queued jobs and stop the
        # dispatcher BEFORE the stats collective (the dispatcher owns
        # the mesh while serving), then persist the learned plan state
        # (rank 0 writes; all ranks read — the state derives from
        # replicated plan inputs, so one copy is the cluster's copy)
        with self._service_lock:
            self._closed = True
        # autoscaler before everything in the service plane: no policy
        # decision may fire a resize into the teardown below
        if getattr(self, "autoscaler", None) is not None:
            try:
                self.autoscaler.stop()
            except Exception as e:
                from ..common import faults as _faults
                _faults.note("recovery", what="autoscale.stop_failed",
                             error=repr(e)[:200])
            self.autoscaler = None
        # front door before the scheduler: stop accepting sockets and
        # flush streamed results while the dispatcher can still run
        # the in-flight jobs those streams are waiting on
        if self.front_door is not None:
            try:
                self.front_door.close()
            except Exception as e:
                from ..common import faults as _faults
                _faults.note("recovery",
                             what="front_door.close_failed",
                             error=repr(e)[:200])
            self.front_door = None
        if self.service is not None:
            try:
                self.service.close()
            except Exception as e:
                from ..common import faults as _faults
                _faults.note("recovery", what="service.close_failed",
                             error=repr(e)[:200])
        # single-writer by construction: on multi-process meshes only
        # rank 0 holds a store handle (it loaded and broadcast the
        # entries at __init__), so this save needs no rank guard —
        # and rank 0's learned state derives from replicated plan
        # inputs, so its copy is the cluster's copy
        if self.plan_store is not None:
            try:
                self.plan_store.save(self.mesh_exec)
            except Exception as e:
                # a failing store must never take down a clean close
                from ..common import faults as _faults
                _faults.note("recovery", what="plan_store.save_failed",
                             error=repr(e)[:200])
            # the audited accuracy ledger persists NEXT TO the plan
            # state it judges: plans.json says what the model learned,
            # decisions.json says how right it was (best-effort too)
            try:
                if self.decisions.enabled \
                        and self.decisions.kind_counts:
                    self.plan_store.save_ledger(
                        self.decisions.summary())
            except Exception as e:
                from ..common import faults as _faults
                _faults.note("recovery",
                             what="decision_ledger.save_failed",
                             error=repr(e)[:200])
        # a dead-peer verdict latched by the background heartbeat
        # monitor (net/heartbeat.py mark_dead) may arrive with NO
        # exception in flight (the job finished between collectives):
        # entering the stats all_gather would raise it mid-close and
        # skip all cleanup — honor the latch up front instead
        pending = getattr(self.net.group, "_pending_abort", None)
        if pending is not None:
            if not self._aborted:
                discovered = pending
            self._aborted = True
        if self._profiler is not None:
            self._profiler.stop()
        # overall_stats() is a COLLECTIVE in multi-host runs: every host
        # must enter it regardless of its local logger setting, or
        # all_gather and barrier traffic would interleave across hosts
        # (after an abort it degrades to the local view — see the
        # _aborted guard inside). A PEER's abort can surface right
        # here (its poison frame arrives in our stats all_gather even
        # though our own job succeeded) — degrade to the local view
        # instead of letting the abort skip the rest of the cleanup.
        try:
            stats = self.overall_stats()
        except (ClusterAbort, ConnectionError, TimeoutError) as e:
            if not self._aborted:
                discovered = e
            self._aborted = True
            stats = self.overall_stats()      # local, collective-free
        if self.logger.enabled:
            self.logger.line(event="overall_stats", **stats)
        from ..common import faults
        if faults.REGISTRY._log == self.logger.line:
            faults.REGISTRY.set_logger(None)
        # the records of what is still in flight, before the logger goes
        self.mesh_exec.close_watcher()
        self.logger.close()
        self.hbm.close()
        if self._aborted:
            # leaked-artifact hygiene: uncommitted epoch of THIS run,
            # plus spill files whose owning process is gone (a
            # kill -9'd worker cannot clean up after itself)
            if self.checkpoint is not None:
                self.checkpoint.abort_cleanup()
            from ..data.block_pool import purge_stale_spills
            purge_stale_spills(self.config.spill_dir)
        if self.net.num_workers > 1:
            # an exiting-for-relaunch rank closes collective-free too:
            # after the marker barrier every rank exits at its own
            # pace (the supervisor is the next synchronization point)
            if not self._aborted and not self._resize_exiting:
                try:
                    self.net.barrier()
                except (ClusterAbort, ConnectionError,
                        TimeoutError) as e:
                    # a dying peer must not block shutdown, but the
                    # loss must still surface (see ``discovered``)
                    if discovered is None:
                        discovered = e
            self.net.group.close()
        if discovered is not None:
            # re-raise ONLY when no other exception is propagating
            # (close() runs in a finally: raising over an in-flight
            # error would mask the real root cause)
            import sys
            if sys.exc_info()[1] is None:
                raise discovered


# ----------------------------------------------------------------------
# runtime bootstrap
# ----------------------------------------------------------------------

def Run(job: Callable[[Context], Any], config: Optional[Config] = None,
        devices: Optional[Sequence[Any]] = None, seed: int = 0,
        resume: bool = False) -> Any:
    """Run a job on all (or the configured number of) local devices.

    ``resume=True`` (or ``THRILL_TPU_RESUME=1``) restores the newest
    complete checkpoint epoch from ``THRILL_TPU_CKPT_DIR`` and replays
    only post-checkpoint work (api/checkpoint.py)."""
    mex = MeshExec(devices=devices,
                   num_workers=(config or Config.from_env()).num_workers)
    ctx = Context(mex, config, seed, resume=resume)
    try:
        return job(ctx)
    except BaseException as e:
        ctx.note_failure(e)
        raise
    finally:
        ctx.close()


def RunSupervised(job: Callable[[Context], Any],
                  config: Optional[Config] = None,
                  devices: Optional[Sequence[Any]] = None, seed: int = 0,
                  max_restarts: int = 2) -> Any:
    """Run with supervised re-execution: an abort-class failure
    (ClusterAbort from a poisoned/hung group, transport loss, timeout)
    tears the run down and relaunches the SAME job with resume enabled,
    so a committed checkpoint epoch bounds the recomputation. The
    multi-process analog lives in run-scripts/supervise.sh (process
    relaunch); this is the in-process form for single-controller jobs
    and tests."""
    from ..common import faults
    from ..net.group import ClusterAbort
    attempt = 0
    while True:
        try:
            return Run(job, config, devices, seed,
                       resume=attempt > 0)
        except (ClusterAbort, ConnectionError, TimeoutError) as e:
            if attempt >= max_restarts:
                raise
            attempt += 1
            faults.note("recovery", what="supervised_restart",
                        attempt=attempt, error=repr(e))
            import sys
            print(f"thrill_tpu: supervised restart {attempt}/"
                  f"{max_restarts} after {e!r} (resume=True)",
                  file=sys.stderr)


def RunLocalMock(job: Callable[[Context], Any], workers: int,
                 config: Optional[Config] = None, seed: int = 0) -> Any:
    """Run on a fixed-size virtual CPU mesh (reference: RunLocalMock)."""
    cpus = jax.devices("cpu")
    if workers > len(cpus):
        raise ValueError(
            f"need {workers} CPU devices; set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={workers}")
    mex = MeshExec(devices=cpus[:workers])
    ctx = Context(mex, config, seed)
    try:
        return job(ctx)
    except BaseException as e:
        ctx.note_failure(e)
        raise
    finally:
        ctx.close()


def RunDistributed(job: Callable[[Context], Any],
                   coordinator_address: Optional[str] = None,
                   num_processes: Optional[int] = None,
                   process_id: Optional[int] = None,
                   config: Optional[Config] = None,
                   resume: bool = False) -> Any:
    """Multi-host entry point: the mesh spans every host's devices.

    The reference reaches multiple hosts through its tcp/mpi backends
    (api/context.cpp:496,651); here the data plane rides
    ``jax.distributed`` — XLA routes collectives over ICI within a
    slice and DCN across slices, and the jitted operator programs are
    unchanged. Each host runs this same function (standard JAX
    multi-controller SPMD). Sources that take global host data
    (Distribute) expect identical input on every host; per-host data
    should enter via ConcatToDIA of the local portion.

    Host fetches of device results are multi-controller safe: plan
    matrices and samples are replicated inside the jitted programs, and
    every remaining device->host read goes through ``MeshExec.fetch``,
    which process-allgathers arrays spanning non-addressable devices.
    Host-side scalar agreement between controllers rides ``ctx.net``
    (FlowControlChannel over the authenticated TCP group from
    THRILL_TPU_HOSTLIST/RANK/SECRET). Validated by the 2-process
    WordCount test (tests/net/test_distributed.py).
    """
    if num_processes is not None and num_processes > 1:
        # the coordinator handshake is a distress deadline like the
        # net bootstraps: on a contended host a peer controller can
        # take minutes of imports/compiles to reach it (see
        # common/timeouts.py)
        from ..common.platform import enable_cpu_multiprocess_collectives
        from ..common.timeouts import scaled
        # a CPU mesh spanning processes needs an explicit collectives
        # backend (gloo) or every cross-process program fails at runtime
        enable_cpu_multiprocess_collectives()
        jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=num_processes, process_id=process_id,
            initialization_timeout=int(scaled(300.0)))
    mex = MeshExec(devices=jax.devices())
    ctx = Context(mex, config, host_rank=process_id or 0,
                  resume=resume)
    try:
        return job(ctx)
    except BaseException as e:
        ctx.note_failure(e)
        raise
    finally:
        ctx.close()


def RunLocalTests(job: Callable[[Context], Any],
                  worker_counts: Sequence[int] = (1, 2, 5, 8),
                  config: Optional[Config] = None) -> List[Any]:
    """Sweep the job over several virtual cluster sizes in-process.

    The single most valuable testing harness of the reference
    (api::RunLocalTests, thrill/api/context.cpp:336-341, sweeping mock
    clusters of {1,2,5,8} hosts x {1,3} workers).
    """
    cpus = jax.devices("cpu")
    max_w = int(os.environ.get("THRILL_TPU_MAX_MOCK_WORKERS", "64"))
    results = []
    for w in worker_counts:
        if w > len(cpus) or w > max_w:
            continue
        results.append(RunLocalMock(job, w, config))
    return results
