"""From a profiler trace (``.xplane.pb``) to what the per-layer readers
read: busy seconds per device, seconds per XLA operation name, idle gaps.

Two steps, so that the arithmetic is testable without a trace:
``load_xplane`` turns the file into plain tuples with nothing but JAX,
``summarize`` reduces them. Times are seconds unless a name says ``_ns``.

The trace is taken with the host's tracer off (with it on, every upload
writes millions of events, 223 MB for three jobs, and each job takes
1.4 s longer; PERF.md, PR 25), so the jobs' spans come from the harness's
own clock and are laid on the trace's by one anchor: the last job's end is
the end of the last device operation. That is right to the few
milliseconds between the device finishing and ``block_until_ready``
returning, which is enough to say where a long idle gap lies.
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE_PREFIX = "/device:TPU:"
OP_LINE = "XLA Ops"
# how far the one anchor may lay a job's edge off the device's clock: the
# last job's block_until_ready has returned up to some milliseconds late
ANCHOR_SLACK_NS = 20e6
COLLECTIVE_MARKS = ("all-to-all", "all-gather", "all-reduce",
                    "collective-permute", "reduce-scatter",
                    "ragged-all-to-all")


_HLO = re.compile(r"^%?(?P<name>\S+) = \(?(?P<shape>[a-z0-9]+\[[^\]]*\])?")
_KIND = re.compile(r"kind=(\w+)")


def short_name(op: str) -> str:
    """The trace names a device operation by its whole HLO line, which
    can run to kilobytes: keep the name, the first output shape and the
    fusion kind (``fusion.44 u32[8388608] kLoop``)."""
    m = _HLO.match(op)
    if not m:
        return op[:96]
    kind = _KIND.search(op)
    parts = [m["name"], m["shape"], kind and kind[1]]
    return " ".join(p for p in parts if p)[:96]


def is_collective(name: str) -> bool:
    """The chip's trace names the exchange ``all_to_all.37`` (the name
    JAX gave the operation) and a ``psum`` ``all-reduce.11`` (XLA's)."""
    name = name.replace("_", "-")
    return any(mark in name for mark in COLLECTIVE_MARKS)


def find_xplane(trace_dir: str) -> str:
    hits = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not hits:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return hits[-1]


def load_xplane(path: str, text_proto: bool = False) -> dict:
    """{plane: {line: [(name, start_ns, duration_ns), ...]}} of the
    device planes."""
    from jax.profiler import ProfileData
    if text_proto:
        with open(path) as f:
            data = ProfileData.from_serialized_xspace(
                ProfileData.text_proto_to_serialized_xspace(f.read()))
    else:
        data = ProfileData.from_file(path)
    planes = {}
    for plane in data.planes:
        if not plane.name.startswith(DEVICE_PLANE_PREFIX):
            continue
        lines = {}
        for line in plane.lines:
            events = [(short_name(e.name), float(e.start_ns),
                       float(e.duration_ns)) for e in line.events]
            if events:
                lines.setdefault(line.name, []).extend(events)
        if lines:
            planes[plane.name] = lines
    return planes


def union(intervals) -> list:
    """Overlapping or touching [start, end) intervals merged, in order."""
    merged = []
    for start, end in sorted(intervals):
        if end <= start:
            continue
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return merged


def self_time_by_name(events) -> dict:
    """Nanoseconds per operation name, each instant given to the
    innermost event that covers it: a ``while`` that spans its body's
    operations keeps only what they leave."""
    out = {}
    stack = []      # [name, end, self_ns, cursor]

    def close(upto):
        while stack and stack[-1][1] <= upto:
            name, end, acc, cursor = stack.pop()
            acc += max(0.0, end - cursor)
            out[name] = out.get(name, 0.0) + acc
            if stack:
                stack[-1][3] = max(stack[-1][3], end)

    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        close(start)
        end = start + dur
        if stack:
            # a child may not outlast its parent: clip it
            end = min(end, stack[-1][1])
            stack[-1][2] += max(0.0, start - stack[-1][3])
            stack[-1][3] = max(stack[-1][3], start)
        stack.append([name, end, 0.0, start])
    close(float("inf"))
    return out


def _clip(events, lo, hi):
    out = []
    for name, start, dur in events:
        s, e = max(start, lo), min(start + dur, hi)
        if e > s:
            out.append((name, s, e - s))
    return out


def _pieces(gap, jobs) -> list:
    """An idle gap cut at the job annotations' edges, each piece with
    where it lies: before a job's first device operation (the upload),
    after its last (the wait for the result, a fetch), between two
    operations, or between jobs (dispose)."""
    s, e = gap
    cuts = sorted({s, e} | {x for j in jobs for x in j if s < x < e})
    out = []
    for a, b in zip(cuts, cuts[1:]):
        label = "between_jobs"
        for js, je in jobs:
            if a >= js and b <= je:
                label = "in_job.head" if a - js <= ANCHOR_SLACK_NS else \
                    "in_job.tail" if je - b <= ANCHOR_SLACK_NS \
                    else "in_job.mid"
                break
        out.append((b - a, label))
    return out


def summarize(planes: dict, job_spans) -> dict | None:
    """``job_spans``: each traced job's (start, end) in seconds on the
    harness's clock. The traced window is from the first job's start to
    the last one's end. None where no device plane has an operation."""
    devices = {p: lines[OP_LINE] for p, lines in planes.items()
               if p.startswith(DEVICE_PLANE_PREFIX) and lines.get(OP_LINE)}
    if not devices or not job_spans:
        return None
    last_op_end = max(s + d for ev in devices.values() for _, s, d in ev)
    shift = last_op_end - job_spans[-1][1] * 1e9
    jobs = [(s * 1e9 + shift, e * 1e9 + shift) for s, e in job_spans]
    lo, hi = jobs[0][0], jobs[-1][1]
    busy, by_name, gaps = [], {}, []    # gaps: one list per device
    for plane in sorted(devices):
        events = _clip(devices[plane], lo, hi)
        merged = union((s, s + d) for _, s, d in events)
        busy.append(sum(e - s for s, e in merged))
        for name, ns in self_time_by_name(events).items():
            by_name[name] = by_name.get(name, 0.0) + ns
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        gaps.append([piece for s, e in zip(edges[0::2], edges[1::2])
                     if e > s for piece in _pieces((s, e), jobs)])
    n = len(devices)
    ops = sorted(((ns / n / 1e9, name) for name, ns in by_name.items()),
                 reverse=True)
    by_label = {}
    for ns, label in (piece for device in gaps for piece in device):
        by_label[label] = by_label.get(label, 0.0) + ns / n / 1e9
    return {
        "devices": n,
        "jobs": len(jobs),
        "window_s": (hi - lo) / 1e9,
        # the mean over the devices, like every per-op time below
        "busy_s": sum(busy) / n / 1e9,
        "busy_s_per_device": [b / 1e9 for b in busy],
        "collective_s": sum(s for s, name in ops if is_collective(name)),
        "device_ops": [[name, s] for s, name in ops[:10]],
        # the chips of one SPMD program wait together: the first device's
        # gaps, or every gap would fill the list once per chip
        "idle_gaps": [[label, ns / 1e9]
                      for ns, label in sorted(gaps[0], reverse=True)[:10]],
        "idle_s_by_label": by_label,
    }

