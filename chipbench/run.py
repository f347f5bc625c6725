#!/usr/bin/env python3
"""The chip benchmark's one command.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, no child: set-up (import, input from the seed, one ``Run()``
that stays open, one warm-up job), then a closed loop of back-to-back
jobs for ``--seconds`` seconds, then the comparison with the plain
reference, then ONE JSON line as the last line of standard output.
Without a TPU it says why and exits non-zero; ``--rehearse`` is the only
way onto a CPU, at a tiny size, for the tests, and never prints that line.

This file knows no cell, configuration, job kind or metric by name: it
finds each through ``BENCHMARK.json`` (see README.md beside it).
"""

from __future__ import annotations

import time

_PROCESS_START = time.perf_counter()

import argparse
import importlib.util
import json
import numbers
import os
import shutil
import sys
import traceback

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
# the counters a run PRINTS (standard error, a rehearsal's ``counts``);
# readers get every counter the program has, by the program's own names
STAT_KEYS = ("device_dispatches", "device_uploads", "device_fetches",
             "exchanges", "bytes_moved", "oom_retries", "segment_splits",
             "host_fallbacks", "admission_spills", "hbm_spills")
_MODULES = {}


def say(msg: str) -> None:
    """Earlier lines go to standard error; standard output carries the
    result line alone."""
    print(msg, file=sys.stderr, flush=True)


def load_json(*parts: str):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def load_module(folder: str, name: str):
    """``chipbench/<folder>/<name>.py``, loaded once: a job kind or a
    per-layer reader is found by its name alone."""
    key = (folder, name)
    if key not in _MODULES:
        path = os.path.join(HERE, folder, name + ".py")
        spec = importlib.util.spec_from_file_location(
            f"chipbench_{folder}_{name}".replace(".", "_").replace("-", "_"),
            path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _MODULES[key] = mod
    return _MODULES[key]


def load_cell(bench: dict, name: str) -> dict:
    """The cell with its configuration's and its traffic mix's files."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"chipbench: no workload {name!r} in BENCHMARK.json "
                         f"(have: {', '.join(sorted(cells))})")
    cell = dict(cells[name])
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    cell["config_file"] = load_json(entry["file"])
    cell["traffic_file"] = load_json("chipbench", "traffic",
                                     cell["traffic"] + ".json")
    return cell


def metrics_of(bench: dict, group: str, cell: str) -> list:
    return [m for m in bench[group]
            if cell in m.get("workloads", [cell])]


def stat_deltas(before: dict, after: dict) -> dict:
    """What every numeric key of ``ctx.overall_stats()`` gained over the
    window. A key that one end lacks (a counter this commit's program
    does not have) is left out: its reader finds nothing to read."""
    def number(v):
        return isinstance(v, numbers.Real) and not isinstance(v, bool)
    return {k: after[k] - before[k] for k in after
            if k in before and number(after[k]) and number(before[k])}


def sampled_jobs(seed: int, traffic: dict) -> set:
    """Which jobs of a window are compared besides its last: all of
    them, or one of its first ``among_first``, drawn from the seed."""
    import numpy as np
    check = traffic["check"]
    if check["jobs"] == "all":
        return None
    rng = np.random.default_rng([seed, 0x5A17])
    return {int(rng.integers(0, int(check["among_first"])))}


class Window:
    """A closed loop of back-to-back jobs by one client."""

    def __init__(self, ctx, job, inp, keep):
        self.ctx, self.job, self.inp, self.keep = ctx, job, inp, keep
        self.spans = []         # each job's own clock: (start, end)
        self.kept = {}          # job index -> what the job returned
        self.attempted = self.failed = 0
        self.elapsed = 0.0

    @property
    def seconds(self) -> list:
        return [end - start for start, end in self.spans]

    def one(self):
        """One job on its own clock: from just before the input is
        handed over to the result ready. Every job gets new ndarray
        objects over the same bytes, so nothing keyed on an object's
        identity can serve a job from the one before."""
        fresh = {k: v.view() for k, v in self.inp.items()}
        t0 = time.perf_counter()
        handle = self.job.pipeline(self.ctx, fresh)
        return handle, (t0, time.perf_counter())

    def drive(self, seconds=None, jobs=None) -> None:
        """Until the first job that ends after ``seconds`` (no job is
        cut), or for ``jobs`` jobs. A job that raises counts as failed
        and ends the window."""
        opened = time.perf_counter()
        while True:
            index = self.attempted
            self.attempted += 1
            try:
                handle, span = self.one()
            except Exception:
                say(traceback.format_exc())
                self.failed += 1
                self.elapsed = time.perf_counter() - opened
                break
            self.spans.append(span)
            self.elapsed = span[1] - opened
            last = self.elapsed >= seconds if jobs is None \
                else self.attempted >= jobs
            if last or self.keep is None or index in self.keep:
                self.kept[index] = handle
            else:
                self.job.dispose(handle)
            del handle
            if last:
                break


def device_block(devices, peak: int) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


def memory_stats(devices) -> list:
    return [d.memory_stats() or {} for d in devices]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None,
                    help="default: BENCHMARK.json's run_seconds")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU, tiny size, for the tests; never prints the "
                         "result line")
    ap.add_argument("--keep-trace", action="store_true",
                    help="leave the trace under chipbench/out/trace/")
    ap.add_argument("--control", action="store_true",
                    help="put the job kind's control (the reference with "
                         "one guarantee broken) in the program's place "
                         "where results are compared: correct must read "
                         "false")
    args = ap.parse_args(argv)

    bench = load_json("BENCHMARK.json")
    cell = load_cell(bench, args.workload)
    config, traffic = cell["config_file"], dict(cell["traffic_file"])
    chips = int(cell["chips"])
    seconds = bench["run_seconds"] if args.seconds is None else args.seconds
    if args.rehearse:
        traffic.update(traffic.get("rehearse", {}))
        # rehearse what the chip runs: the jitted device programs with
        # the accelerator's key and row layouts, not the native host path
        os.environ["THRILL_TPU_HOST_RADIX"] = "0"
        os.environ["THRILL_TPU_SORT_U32"] = "1"
        os.environ["THRILL_TPU_PACK_MOVE"] = "1"
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    try:
        import jax
        import thrill_tpu  # noqa: F401  (the way a user does; turns x64 on)
        from thrill_tpu.api import Run
    except ImportError as e:
        say(f"chipbench: the system under test is not here: {e}")
        return 2

    imported = time.perf_counter() - _PROCESS_START
    devices = jax.devices()
    platform = devices[0].platform
    if args.rehearse:
        if platform != "cpu":
            say(f"chipbench: --rehearse is for the CPU; JAX found {platform}")
            return 2
    elif platform != "tpu":
        say(f"chipbench: JAX found no TPU (platform={platform!r}, "
            f"{len(devices)} device(s)); a number from a CPU is never a "
            f"device number. --rehearse is the CPU way in, for the tests.")
        return 2
    if len(devices) < chips:
        say(f"chipbench: {cell['name']} asks for {chips} chip(s), JAX found "
            f"{len(devices)}")
        return 2
    devices = devices[:chips]
    peaks = load_json("chipbench", "peaks.json")["device_kinds"]
    if not args.rehearse and devices[0].device_kind not in peaks:
        say(f"chipbench: device kind {devices[0].device_kind!r} is not in "
            f"chipbench/peaks.json; a peak is never a default")
        return 2
    say(f"device: platform={platform} kind={devices[0].device_kind} "
        f"count={len(devices)} jax={jax.__version__}")

    compiles = [0]

    def on_duration(event, duration, **kw):
        if event == COMPILE_EVENT:
            compiles[0] += 1
    jax.monitoring.register_event_duration_secs_listener(on_duration)

    job = load_module("jobs", config["job"])
    t0 = time.perf_counter()
    inp = job.generate(args.seed, traffic, config)
    n_records = job.records(traffic)
    say(f"input: {n_records} records from seed {args.seed} in "
        f"{time.perf_counter() - t0:.2f}s (imports took {imported:.2f}s)")
    keep = sampled_jobs(args.seed, traffic)
    traced_jobs = int(traffic["traced_jobs"])
    if keep is not None and args.trace:
        # never the window's last job, which is compared anyway: the
        # sampled result stays in HBM and is part of the peak
        keep = {k % max(1, traced_jobs - 1) for k in keep}
    trace_dir = os.path.join(HERE, "out", "trace", cell["name"])
    out = {}

    def body(ctx):
        mex = ctx.mesh_exec
        if mex.num_workers != chips:
            raise RuntimeError(
                f"Run() took {mex.num_workers} devices, wanted {chips}")
        say(f"compile cache: dir={jax.config.jax_compilation_cache_dir!r} "
            + ("(from JAX_COMPILATION_CACHE_DIR)"
               if os.environ.get("JAX_COMPILATION_CACHE_DIR")
               else "(set by thrill_tpu)"))
        window = Window(ctx, job, inp, keep)
        handle, (t0, t1) = window.one()
        job.dispose(handle)
        del handle
        say(f"first_job_s: {t1 - t0:.3f} (upload + compile or cache load + "
            f"run; {compiles[0]} backend compiles or cache loads)")
        for rec in ctx.decisions.snapshot():
            if rec.get("kind") in ("sort_engine", "xchg_strategy",
                                   "xchg_chunks"):
                say(f"decision {rec['kind']} site={rec.get('site')} "
                    f"chosen={rec.get('chosen')} reason={rec.get('reason')!r}")
        say("HBM bytes in use after the warm-up job: " + ", ".join(
            str(m.get("bytes_in_use", "not reported"))
            for m in memory_stats(mex.devices)))

        stats0 = ctx.overall_stats()
        compiles0 = compiles[0]
        out["setup_s"] = time.perf_counter() - _PROCESS_START
        if args.trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
            options = jax.profiler.ProfileOptions()
            # the device alone: the host's events (millions of Transpose
            # chunks per upload) made a 223 MB trace and slowed every job
            # by 1.4 s, whatever the level above 0
            options.python_tracer_level = 0
            options.host_tracer_level = 0
            with jax.profiler.trace(trace_dir, profiler_options=options):
                window.drive(jobs=traced_jobs)
        else:
            window.drive(seconds=seconds)
        stats1 = ctx.overall_stats()
        out["compiles"] = compiles[0] - compiles0
        out["memory"] = memory_stats(mex.devices)
        out["stats"] = stat_deltas(stats0, stats1)
        out["counts"] = {k: out["stats"][k] for k in STAT_KEYS}
        out["window"] = window
        say("stats over the window: " + " ".join(
            f"{k}={v}" for k, v in out["counts"].items()))
        say("job seconds: " + " ".join(f"{s:.3f}" for s in window.seconds))
        # the window has closed and the peak has been read: only now is a
        # result brought to the host
        out["got"] = {}
        for index in sorted(window.kept):
            handle = window.kept.pop(index)
            out["got"][index] = None if args.control else job.fetch(handle)
            job.dispose(handle)
            del handle

    try:
        Run(body, devices=None if len(jax.devices()) == chips else devices,
            seed=args.seed)
    except Exception:
        say(traceback.format_exc())
        if "window" not in out:
            return 1
    finally:
        jax.monitoring.unregister_event_duration_listener(on_duration)

    window = out["window"]
    t0 = time.perf_counter()
    want = job.reference(inp, traffic)
    numbers = {}
    compared = sorted(out.get("got", {}))
    control = job.control(inp, traffic) if args.control else None
    for index in compared:
        got = control if args.control else out["got"].pop(index)
        for name, (value, limit) in job.compare(got, want).items():
            if name not in numbers or value > numbers[name][0]:
                numbers[name] = (value, limit)
    check_s = time.perf_counter() - t0
    correct = bool(compared) and window.failed == 0 and all(
        value <= limit for value, limit in numbers.values())
    check = {"jobs_compared": len(compared),
             **{name: {"value": value, "limit": limit}
                for name, (value, limit) in numbers.items()}}
    say(f"reference and comparison in {check_s:.2f}s; jobs compared: "
        f"{len(compared)} of {window.attempted}"
        + (f" (jobs {compared})" if len(compared) <= 4 else ""))

    memory = out["memory"]
    peak = max((m.get("peak_bytes_in_use", 0) for m in memory), default=0)
    done = len(window.seconds)
    result = {"correct": correct, "attempted": window.attempted,
              "failed": window.failed, "metrics": {},
              "device": device_block(devices, peak)}
    if args.trace:
        trace_reduce = load_module("", "trace_reduce")
        summary = trace_reduce.summarize(
            trace_reduce.load_xplane(trace_reduce.find_xplane(trace_dir)),
            window.spans)
        if not args.keep_trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
        run = {"trace": summary, "stats": out["stats"], "memory": memory,
               "jobs": done, "job_seconds": window.seconds,
               "compiles": out["compiles"], "cell": cell,
               "traffic": traffic, "config": config,
               "peaks": peaks.get(devices[0].device_kind),
               "min_bytes": job.min_bytes(traffic, config, want)}
        for m in metrics_of(bench, "per_layer", cell["name"]):
            value = load_module("layer_metrics", m["name"]).read(run)
            if value is not None:
                result["metrics"][m["name"]] = {"value": value,
                                                "unit": m["unit"]}
        if summary:
            result["device"]["busy_s"] = summary["busy_s"]
            result["device"]["window_s"] = summary["window_s"]
            result["breakdown"] = {"device_ops": summary["device_ops"],
                                   "idle_gaps": summary["idle_gaps"]}
            say("idle seconds by place: " + json.dumps(
                summary["idle_s_by_label"]))
    else:
        values = {
            "records_per_s": done * n_records / window.elapsed
            if done else None,
            "setup_s": out["setup_s"],
        }
        for m in metrics_of(bench, "end_to_end", cell["name"]):
            if values.get(m["name"]) is not None:
                result["metrics"][m["name"]] = {"value": values[m["name"]],
                                                "unit": m["unit"]}
        say(f"compiles or cache loads inside the window: {out['compiles']}")
    result["check"] = check

    say("check: " + " ".join(
        f"{k}={v['value']} (limit {v['limit']})" if isinstance(v, dict)
        else f"{k}={v}" for k, v in check.items())
        + f" -> correct={correct}")
    if args.rehearse:
        print(json.dumps({"rehearsal": True, "workload": cell["name"],
                          "correct": correct, "attempted": window.attempted,
                          "failed": window.failed, "check": check,
                          "counts": out["counts"],
                          "reported": sorted(result["metrics"])}),
              flush=True)
        return 0
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
