"""Deterministic perf-contract sentinel.

Wall-clock bench ratios swing 2-7x on shared rigs, so perf regressions
hide in the noise — but the counters the framework already maintains
are DETERMINISTIC for a fixed program: device dispatches (fusion
breaking shows up as a dispatch-count jump), data-driven plan builds
(plan-store/optimism regressions), exchange counts and overlap,
tracked fetches, and the bytes-on-wire totals (the wire codec
silently disabling doubles them). This tool snapshots those counters
per bench-shaped workload into ``PERF_CONTRACT.json`` and diffs a
fresh run against the snapshot:

* **counters** compare EXACTLY — any drift is a contract violation;
* **byte totals** compare ratio-banded (``THRILL_TPU_SENTINEL_BAND``,
  default 0.25): padded capacities may legally wiggle with pow2
  ratcheting, silent 2x regressions may not.

Usage::

    python -m thrill_tpu.tools.perf_sentinel --snapshot [PATH]
    python -m thrill_tpu.tools.perf_sentinel --check    [PATH]

(``run-scripts/perf_sentinel.sh`` wraps both with the env pinned.)
``--check`` exits 1 with a loud per-field diff on any violation. The
contract assumes default knobs: warm plan stores / armed faults are
scrubbed around the measurement (never a legitimate sentinel state),
while counter-relevant knobs like THRILL_TPU_FUSE are deliberately
honored — a knob-skewed run failing on its counters is exactly the
silent-regression class this tool exists to catch (the snapshot's
``env`` note tells the human what the contract ran under).
"""

from __future__ import annotations

import json
import os
import sys
import time
from typing import Callable, Dict, List

import numpy as np

#: counters that must match EXACTLY between contract and fresh run.
#: The out-of-core row (ISSUE 15): spilled runs, write-behind bytes,
#: readahead submissions and native-record blocks are deterministic
#: for a fixed program — em_sort settles its spill store at the
#: pre-merge barrier, so residency (and therefore every prefetch
#: submission) is a pure function of the program. A silent fallback
#: from the columnar record format to the pickle spill path moves
#: records_blocks AND writeback_bytes, failing this contract instead
#: of hiding in wall-clock noise. (The em workload assumes the baked
#: toolchain: a compiler-less host runs the python block store, whose
#: eviction order differs.)
COUNTERS = (
    "device_dispatches", "device_uploads", "device_fetches",
    "fused_dispatches", "fused_ops",
    "exchanges", "exchanges_overlapped",
    "cap_cache_hits", "cap_cache_misses",
    "plan_builds", "items_moved",
    "spill_runs", "records_blocks", "prefetch_submits",
    "writeback_bytes",
    # elastic-mesh / service-plane row (ISSUE 16): a resize-free run
    # must report EXACTLY zero resizes and zero admission rejections —
    # the elastic machinery and the bounded submit queue cost nothing
    # when unused. resize_time_ms is derived from resize_time_s in
    # _run_workload; it is only contract-deterministic BECAUSE it must
    # be zero here (wall time appears the moment a resize does, which
    # is itself the violation being caught). jobs_submitted pins the
    # serve workload's job count; the batch workloads report 0.
    "jobs_submitted", "jobs_failed", "jobs_rejected",
    "resizes", "resize_time_ms",
    # remote object store + resumable runs (ISSUE 17): the batch and
    # em workloads above must report EXACTLY zero — the HTTP transport
    # and the run store cost nothing when unused. The em_remote
    # workload pins the transport's request economy (a lost Range
    # header or a dropped reader reopen moves remote_gets; a per-part
    # PUT regression moves remote_puts); em_resume pins the
    # merge-only-restart contract (every committed run reused, zero
    # new spills on the resume leg).
    "remote_gets", "remote_puts", "runs_reused",
    # network front door (ISSUE 18): the batch and serve workloads
    # must report EXACTLY zero on every fd_* counter — a Context that
    # never binds a FrontDoor pays nothing for the socket edge. The
    # front_door workload pins the admission + streaming economy of
    # one real loopback client: conns, submits, chunks, and the clean
    # zero row for sheds/slow-client drops on an unloaded lane.
    "fd_conns_accepted", "fd_conns_dropped", "fd_jobs_submitted",
    "fd_jobs_rejected", "fd_chunks_sent", "fd_slow_clients",
    "fd_deadline_expired",
    # supervised process elasticity (ISSUE 20): every workload here is
    # a fixed-W run that never moves processes, so the process-move
    # counter, the autoscaler's decision count and the orphan-run
    # adoption count must be EXACTLY zero — the drain/seal/relaunch
    # machinery, the scaling policy and the join-time run-store scan
    # cost nothing on a run that never resizes.
    "resizes_proc", "autoscale_decisions", "runs_adopted",
)

#: byte totals compared ratio-banded (pow2 capacity ratchets may move
#: padded volume without a real regression)
BYTE_FIELDS = ("bytes_on_wire", "bytes_on_wire_raw", "bytes_moved")

#: knobs that change the counters — recorded INFORMATIONALLY into the
#: contract (a human diffing a failure sees what the snapshot ran
#: under). Deliberately NOT a comparison guard: "someone ran with
#: THRILL_TPU_FUSE=0" is exactly the silent-regression class the
#: sentinel exists to catch, so a knob-skewed check must fail on the
#: COUNTERS, loudly, not be excused by an env note.
ENV_NOTE = (
    "THRILL_TPU_FUSE", "THRILL_TPU_OVERLAP", "THRILL_TPU_XCHG_CHUNKS",
    "THRILL_TPU_XCHG_CAP_CACHE", "THRILL_TPU_XCHG_NARROW",
    "THRILL_TPU_WIRE_COMPRESS", "THRILL_TPU_PLANNER",
    "THRILL_TPU_EXCHANGE",
    "THRILL_TPU_LOCATION_DETECT", "THRILL_TPU_DUP_DETECT",
    "THRILL_TPU_LOOP_REPLAY",
    "THRILL_TPU_NATIVE_RECORDS", "THRILL_TPU_PREFETCH",
    "THRILL_TPU_WRITEBACK",
    "THRILL_TPU_PALLAS", "THRILL_TPU_SORT_IMPL",
    "THRILL_TPU_XCHG_BYTES_EQ", "THRILL_TPU_XCHG_BYTES_EQ_CAL",
)

#: state that is NEVER legitimate during a sentinel measurement — a
#: warm plan store zeroes plan_builds by design and armed faults
#: change retry paths: both are scrubbed around the runs (and
#: restored), so the contract always measures the cold default
#: THRILL_TPU_SERVE_QUEUE is scrubbed too: admission rejections depend
#: on submit-vs-drain TIMING under a finite cap, so a capped serve run
#: can never honor an exact jobs_rejected contract — unlike FUSE-style
#: knobs, whose counter effects are deterministic and therefore
#: deliberately honored
_SCRUB = ("THRILL_TPU_PLAN_STORE", "THRILL_TPU_FAULTS",
          "THRILL_TPU_CKPT_DIR", "THRILL_TPU_RESUME",
          "THRILL_TPU_SERVE_QUEUE",
          # same timing-dependence argument for the edge knobs: rate
          # limits and tenant caps shed by wall clock, and a set
          # SERVE_PORT would auto-bind a front door into EVERY
          # workload's Context, polluting their all-zero fd_* rows
          "THRILL_TPU_SERVE_RATE", "THRILL_TPU_SERVE_TENANT_QUEUE",
          "THRILL_TPU_SERVE_PORT",
          # a set autoscale tick would thread a live policy into every
          # workload's Context; its decisions are wall-clock-timed, so
          # the all-zero autoscale_decisions row is only contract-
          # deterministic with the knob scrubbed
          "THRILL_TPU_AUTOSCALE_S")

VERSION = 1


def _band() -> float:
    try:
        v = float(os.environ.get("THRILL_TPU_SENTINEL_BAND", "0.25"))
    except ValueError:
        return 0.25
    return v if v > 0 else 0.25


# ----------------------------------------------------------------------
# workloads: small, fixed-seed, W=2 — each is a fresh Context so the
# counters depend only on the program, never on a previous workload's
# learned state
# ----------------------------------------------------------------------

def _wc_scale(x):
    return x * 3 + 1


def _wc_odd(x):
    return x % 2 == 1


def _wc_kv(x):
    return (x % 13, x)


def _wc_add(a, b):
    return a + b


def _wordcount(ctx):
    """ReduceByKey-shaped with an LOp stack on top: fusion (the stack
    collapses into the reduce's pre-phase — FUSE=0 moves
    device_dispatches, not just fused_*), hash exchange, preshuffle."""
    return sorted(
        (int(k), int(v)) for k, v in ctx.Distribute(
            np.arange(384, dtype=np.int64)).Map(_wc_scale).Filter(
                _wc_odd).Map(_wc_kv).ReducePair(_wc_add).AllGather())


def _sort(ctx):
    """Sample-sort shaped: splitter agreement + range exchange."""
    rng = np.random.default_rng(11)
    data = rng.integers(0, 1 << 30, size=512).astype(np.int64)
    return ctx.Distribute(data).Sort().AllGather()


def _kv_mod(x):
    return (x % 24, x)


def _kv_ident(x):
    return (x, x * 3)


def _key0(kv):
    return kv[0]


def _join_vals(left, right):
    return (left[1], right[1])


def _joinish(ctx):
    """Hash-join shaped: two shuffles + the pre-shuffle location
    filter's cost-model path — the wire-heaviest contract workload."""
    from ..api.dia import InnerJoin
    left = ctx.Distribute(np.arange(240, dtype=np.int64)).Map(_kv_mod)
    right = ctx.Distribute(np.arange(24, dtype=np.int64)).Map(
        _kv_ident)
    j = InnerJoin(left, right, _key0, _key0, _join_vals)
    return sorted((int(a), int(b)) for a, b in j.AllGather())


def _chain_inc(x):
    return x + 1


def _chain(ctx):
    """Fully-fusible row-local DOp chain: ONE stitched dispatch when
    fusion is healthy, one per DOp when it breaks —
    ``device_dispatches`` is the contract that catches it."""
    return ctx.Distribute(np.arange(256, dtype=np.int64)).PrefixSum() \
        .Map(_chain_inc).ZipWithIndex().AllGather()


def _radix_sort(ctx):
    """Radix-engine sort lane (ISSUE 19): the sample-sort shape forced
    through the LSD radix engine (Pallas stable-partition kernel on
    TPU, the lax.scan partition fallback here). The dispatch/exchange
    counters pin the engine's program economy — a silent fallback to
    another engine (or a dead-pass skip regression) moves them."""
    rng = np.random.default_rng(17)
    data = rng.integers(0, 1 << 30, size=512).astype(np.int64)
    got = [int(x) for x in ctx.Distribute(data).Sort().AllGather()]
    assert got == sorted(int(x) for x in data), "radix_sort diverged"


def _ss_key(t):
    return t["k"]


def _segsum(ctx):
    """Additive FieldReduce lane (ISSUE 19): an f32 'sum' fold, the
    shape the segment-sum kernel serves on TPU (scatter-add fallback
    here — counters are engine-independent). ReduceByKey's shuffle +
    fold economy is this workload's contract."""
    from ..api.functors import FieldReduce
    rng = np.random.default_rng(19)
    n = 768
    ks = rng.integers(0, 48, size=n).astype(np.int64)
    vs = (rng.random(n) * 4).astype(np.float32)
    out = ctx.Distribute({"k": ks, "v": vs}).ReduceByKey(
        _ss_key, FieldReduce({"k": "first", "v": "sum"})).AllGather()
    assert len(out) == len(set(int(k) for k in ks)), "segsum diverged"


def _em_sort(ctx):
    """Host EM sort (ISSUE 15): fixed-seed string items spilled as
    sorted runs through the native columnar record format in a pinned
    disk-resident regime, then k-way merged with readahead. The
    out-of-core counter row (spill_runs / records_blocks /
    prefetch_submits / writeback_bytes) is this workload's contract."""
    rng = np.random.default_rng(23)
    # ~170 KiB spilled: comfortably past the 64 KiB residency floor,
    # so the merge genuinely faults blocks from disk and its readahead
    # submissions are a nonzero, deterministic part of the contract
    items = [f"k-{int(v):09d}" for v in
             rng.integers(0, 1 << 30, size=4096)]
    node = ctx.Distribute(items, storage="host").Sort().node
    hs = node.materialize()
    assert sum(len(lst) for lst in hs.lists) == len(items)


def _em_remote(ctx):
    """Remote storage lane (ISSUE 17): ReadLines -> Sort ->
    WriteLinesOne entirely against the in-repo object server at ZERO
    latency and ZERO failure rate — retries and reopens would make the
    request counts timing-dependent, so the sentinel measures the
    fault-free request economy (the chaos sweep owns the faulted
    paths). remote_gets / remote_puts are this workload's contract: a
    transport that silently stops ranging, re-lists, or splits PUTs
    moves them."""
    from .object_server import ObjectServer
    rng = np.random.default_rng(29)
    lines = sorted(f"r-{int(v):09d}" for v in
                   rng.integers(0, 1 << 30, size=512))
    with ObjectServer() as srv:
        srv.put("b/in-00.txt",
                "\n".join(lines[0::2]).encode() + b"\n")
        srv.put("b/in-01.txt",
                "\n".join(lines[1::2]).encode() + b"\n")
        d = ctx.ReadLines(f"{srv.url}/b/in-*").Sort()
        d.WriteLinesOne(f"{srv.url}/b/out.txt")
        got = ctx.ReadLines(f"{srv.url}/b/out.txt").AllGather()
    assert got == lines, "em_remote: remote roundtrip diverged"


def _er_key(t):
    return t[0]


def _em_resume(ctx):
    """Resumable external runs (ISSUE 17): an EM sort with
    checkpointing on forms + commits its spilled runs, then the SAME
    program relaunches with resume — the second leg must reuse every
    committed run (runs_reused == the first leg's spill count) and
    form ZERO new ones. Both legs run as nested local mocks inside
    the sentinel's outer context: iostats is process-global and the
    outer context reports the delta, so the pair lands in one row."""
    import tempfile
    from ..api.context import RunLocalMock
    from ..common.config import Config
    n = 1600
    data = [(f"k{(i * 7919) % n:05d}", float(i)) for i in range(n)]

    def job(c):
        return c.Distribute(data, storage="host").Sort(
            key_fn=_er_key).AllGather()

    with tempfile.TemporaryDirectory() as td:
        ck = os.path.join(td, "ck")
        first = RunLocalMock(job, 2, config=Config(ckpt_dir=ck))
        again = RunLocalMock(job, 2,
                             config=Config(ckpt_dir=ck, resume=True))
    assert first == again == sorted(data, key=_er_key), \
        "em_resume: resumed sort diverged"


def _serve_wc(ctx):
    return sorted(
        (int(k), int(v)) for k, v in ctx.Distribute(
            np.arange(128, dtype=np.int64)).Map(_wc_kv).ReducePair(
                _wc_add).AllGather())


def _serve_chain(ctx):
    return [int(v) for v in ctx.Distribute(
        np.arange(96, dtype=np.int64)).Map(_chain_inc).PrefixSum()
        .AllGather()]


def _fd_stream(ctx, args):
    for i in range(int(args["k"])):
        yield i * i


def _fd_wc(ctx, args):
    return _serve_wc(ctx)


def _front_door(ctx):
    """Network-edge workload (ISSUE 18): ONE real loopback client
    through a FrontDoor bound to the Context — the full admission
    protocol (auth flag, hello/welcome, framing) plus both result
    modes. Sequential deterministic submits pin the edge's counter
    economy: 1 conn, 3 submits, 1 blob chunk per wc + 4 item chunks,
    zero sheds / slow-client drops / deadline expiries on an unloaded
    loopback lane. The FrontDoor is left attached so the stats capture
    (and the Prometheus surface it feeds) sees the live counters;
    Context.close tears it down like any serving process would."""
    from ..service.client import FrontDoorClient
    from ..service.front_door import FrontDoor
    fd = FrontDoor(ctx, port=0)
    fd.register("wc", _fd_wc)
    fd.register("stream", _fd_stream)
    with FrontDoorClient("127.0.0.1", fd.port, tenant="a") as cli:
        r1 = cli.submit("wc", None).result(120)
        r2 = cli.submit("wc", None).result(120)
        assert r1 == r2, "front_door: repeated job diverged"
        items = list(cli.submit("stream", {"k": 4}).chunks(timeout=120))
        assert items == [0, 1, 4, 9], "front_door: stream diverged"
    # the client's bye lands asynchronously: wait for the drop so
    # fd_conns_dropped is contract-deterministic, bounded not flaky
    deadline = time.monotonic() + 30.0
    while fd.conns_dropped < 1 and time.monotonic() < deadline:
        time.sleep(0.01)
    assert fd.conns_dropped == 1, "front_door: bye never landed"


def _serve(ctx):
    """Resize-free serving lane (ISSUE 16): tenant-tagged jobs through
    ``ctx.submit`` on a W=2 mesh that never changes width. The elastic
    row (resizes / resize_time_ms) and the admission counter
    (jobs_rejected) must be EXACTLY zero — the elastic mesh and the
    bounded submit queue cost nothing when a Context never uses them —
    while jobs_submitted pins the lane's job count. Jobs serialize on
    the dispatcher, so the dispatch/exchange counters stay a pure
    function of the program just like the batch workloads."""
    futs = [ctx.submit(_serve_wc, tenant="a", name="wc0"),
            ctx.submit(_serve_chain, tenant="b", name="chain0"),
            ctx.submit(_serve_wc, tenant="a", name="wc1")]
    got = [f.result(timeout=120) for f in futs]
    assert got[0] == got[2], "serve lane: repeated job diverged"


WORKLOADS: Dict[str, Callable] = {
    "wordcount": _wordcount,
    "sort": _sort,
    "radix_sort": _radix_sort,
    "segsum": _segsum,
    "join": _joinish,
    "chain": _chain,
    "em_sort": _em_sort,
    "em_remote": _em_remote,
    "em_resume": _em_resume,
    "serve": _serve,
    "front_door": _front_door,
}

#: per-workload env pins (set around the run, restored after): the em
#: workload needs a deterministic spill regime — a forced run size and
#: a floor-pinned resident budget — regardless of the rig's RAM
ENV_PINS: Dict[str, Dict[str, str]] = {
    # the radix lane forces its engine; both new ISSUE-19 lanes pin
    # the bytes_eq calibration off so the dense/1-factor choice never
    # depends on this rig's measured launch overhead
    "radix_sort": {"THRILL_TPU_SORT_IMPL": "radix",
                   "THRILL_TPU_XCHG_BYTES_EQ_CAL": "0"},
    "segsum": {"THRILL_TPU_XCHG_BYTES_EQ_CAL": "0"},
    "em_sort": {"THRILL_TPU_HOST_SORT_RUN": "256",
                "THRILL_TPU_SPILL_RESIDENT": "64K"},
    # the resume pair needs the SAME forced run size on both legs so
    # run identities match; a fast retry base keeps the (fault-free)
    # remote lane from sleeping if the rig's loopback hiccups
    "em_resume": {"THRILL_TPU_HOST_SORT_RUN": "200",
                  "THRILL_TPU_SPILL_RESIDENT": "64K"},
    "em_remote": {"THRILL_TPU_RETRY_BASE_S": "0.01"},
}


def _run_workload(fn, workers: int = 2, pins=None) -> dict:
    from ..api.context import RunLocalMock
    stats_box = {}

    def job(ctx):
        fn(ctx)
        stats_box.update(ctx.overall_stats())

    saved = {k: os.environ.get(k) for k in (pins or {})}
    os.environ.update(pins or {})
    try:
        RunLocalMock(job, workers)
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    out = {k: int(stats_box.get(k, 0)) for k in COUNTERS}
    # derived: resize wall time in whole ms — int() on the raw seconds
    # would truncate a 0.9 s resize to 0 and hide exactly the
    # machinery-engaged-when-unused violation this field exists for
    out["resize_time_ms"] = int(round(
        float(stats_box.get("resize_time_s", 0.0)) * 1000))
    out.update({k: int(stats_box.get(k, 0)) for k in BYTE_FIELDS})
    return out


def snapshot(workloads=None, workers: int = 2) -> dict:
    """Run each workload on a fresh W=``workers`` mesh and collect its
    counter contract."""
    # unknown names (a contract from a newer checkout) simply don't
    # run — diff() then reports them missing, loudly
    names = [n for n in (workloads or WORKLOADS) if n in WORKLOADS]
    saved = {k: os.environ.pop(k) for k in _SCRUB if k in os.environ}
    try:
        runs = {name: _run_workload(WORKLOADS[name], workers,
                                    pins=ENV_PINS.get(name))
                for name in names}
    finally:
        os.environ.update(saved)
    return {
        "version": VERSION,
        "workers": workers,
        "env": {k: os.environ.get(k) for k in ENV_NOTE
                if os.environ.get(k) is not None},
        "workloads": runs,
    }


def diff(contract: dict, fresh: dict) -> List[str]:
    """Violations of ``fresh`` against ``contract`` (empty = clean).
    The env note is NOT compared — a knob-skewed run must fail on the
    counters themselves (that is the regression class being hunted),
    with the recorded env available for the human reading the diff."""
    problems: List[str] = []
    if contract.get("version") != fresh.get("version"):
        problems.append(
            f"contract version {contract.get('version')} != "
            f"{fresh.get('version')} (re-snapshot)")
        return problems
    band = _band()
    for name, want in contract.get("workloads", {}).items():
        got = fresh.get("workloads", {}).get(name)
        if got is None:
            problems.append(f"{name}: workload missing from fresh run")
            continue
        for k in COUNTERS:
            if int(got.get(k, 0)) != int(want.get(k, 0)):
                problems.append(
                    f"{name}.{k}: {want.get(k, 0)} -> {got.get(k, 0)} "
                    f"(exact counter contract)")
        for k in BYTE_FIELDS:
            w, g = int(want.get(k, 0)), int(got.get(k, 0))
            if w == 0 and g == 0:
                continue
            lo, hi = w * (1 - band), w * (1 + band)
            if not (lo <= g <= hi):
                problems.append(
                    f"{name}.{k}: {w} -> {g} "
                    f"(outside the +/-{band:.0%} byte band)")
    for name in fresh.get("workloads", {}):
        if name not in contract.get("workloads", {}):
            problems.append(
                f"{name}: not in the contract (re-snapshot to adopt)")
    return problems


def default_path() -> str:
    """PERF_CONTRACT.json at the repo root when run from a checkout,
    else the current directory. The checkout test is the contract file
    itself — the package grandparent always EXISTS (the module was
    imported from it), so a mere isdir check would route a
    pip-installed run's contract next to site-packages."""
    here = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    path = os.path.join(here, "PERF_CONTRACT.json")
    if os.path.isfile(path):
        return path
    return os.path.abspath("PERF_CONTRACT.json")


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    mode = None
    if argv and argv[0] in ("--snapshot", "--check"):
        mode = argv.pop(0)
    if mode is None:
        print("usage: perf_sentinel --snapshot|--check "
              "[PERF_CONTRACT.json]", file=sys.stderr)
        return 2
    path = argv.pop(0) if argv else default_path()
    # the virtual W=2 CPU mesh needs the device-count flag BEFORE jax
    # initializes (no-op when the harness already set it)
    os.environ.setdefault(
        "XLA_FLAGS", "--xla_force_host_platform_device_count=8")
    from ..common.platform import force_cpu_platform
    force_cpu_platform()
    if mode == "--snapshot":
        snap = snapshot()
        with open(path, "w") as f:
            json.dump(snap, f, indent=1, sort_keys=True)
            f.write("\n")
        print(f"perf_sentinel: contract written to {path} "
              f"({len(snap['workloads'])} workloads)")
        return 0
    try:
        with open(path) as f:
            contract = json.load(f)
    except (OSError, json.JSONDecodeError) as e:
        print(f"perf_sentinel: cannot read contract {path}: {e}",
              file=sys.stderr)
        return 2
    fresh = snapshot(workloads=contract.get("workloads"))
    problems = diff(contract, fresh)
    if problems:
        print(f"perf_sentinel: {len(problems)} contract violation(s) "
              f"vs {path}:", file=sys.stderr)
        for p in problems:
            print(f"  REGRESSION {p}", file=sys.stderr)
        return 1
    print(f"perf_sentinel: clean — "
          f"{len(contract.get('workloads', {}))} workloads match "
          f"{path}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
