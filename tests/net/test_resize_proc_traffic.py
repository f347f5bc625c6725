"""Supervised elasticity on REAL processes under LIVE traffic
(ISSUE 20 acceptance): a 2-process run under live lockstep submits
scales to 3 processes via an autoscale decision, resumes from the
RESIZE epoch, shrinks back to 2 on sustained idle — and every
JobFuture ever returned resolves BIT-IDENTICAL to fixed-W reference
runs (the drain inside ``resize_processes`` finishes in-flight work
before the move seals; nothing is lost, nothing is wrong).

~3 supervised rounds x up to 3 JAX processes plus two fixed-W
reference launches: slow lane, like the other real-process launches.
"""

import json
import os
import subprocess
import sys
import tempfile

import pytest

from portalloc import free_ports, load_scaled

pytestmark = pytest.mark.slow

CHILD = os.path.join(os.path.dirname(__file__),
                     "resize_traffic_child.py")
SUPERVISE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), "run-scripts", "supervise.sh")

_COMPILE_CACHE_DIR = os.path.join(
    tempfile.gettempdir(), "thrill-tpu-test-xla-cache")


def _env(ck, ports):
    repo_root = os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env.pop("THRILL_TPU_RESUME", None)
    env.pop("THRILL_TPU_RESIZE_W", None)
    env.update({
        "PYTHONPATH": repo_root + os.pathsep
        + env.get("PYTHONPATH", ""),
        "THRILL_TPU_CKPT_DIR": ck,
        "TEST_PORTS": " ".join(str(p) for p in ports),
        "THRILL_TPU_SECRET": "resize-traffic-secret",
        "JAX_COMPILATION_CACHE_DIR": _COMPILE_CACHE_DIR,
        "THRILL_TPU_HANG_TIMEOUT_S": "60",
        # drain budget for the in-flight a2/b2 jobs: at W=3 they miss
        # the W=2 XLA compile cache, and three ranks compiling
        # concurrently on a loaded rig can blow the 30s default —
        # a timing abort here would mask the round, not find a bug
        "THRILL_TPU_RESIZE_TIMEOUT_S": "180",
    })
    return env


def _reference_run(ck, nproc):
    """One fixed-W run of the same job: the bit-identical baseline."""
    ports = free_ports(4)
    env = _env(ck, ports)
    env.update({"TEST_FIXED_W": "1", "THRILL_TPU_NPROC": str(nproc),
                "THRILL_TPU_SUPERVISE_ROUND": "0"})
    procs = []
    for rank in range(nproc):
        e = dict(env, THRILL_TPU_RANK=str(rank))
        procs.append(subprocess.Popen(
            [sys.executable, CHILD], stdout=subprocess.PIPE,
            stderr=subprocess.PIPE, text=True, env=e))
    results = []
    for p in procs:
        out, err = p.communicate(timeout=load_scaled(420))
        assert p.returncode == 0, f"reference failed:\n{err[-3000:]}"
        lines = [l for l in out.splitlines()
                 if l.startswith("RESULT ")]
        assert lines, f"no RESULT:\n{out}\n{err[-2000:]}"
        results.append(json.loads(lines[-1][len("RESULT "):]))
    assert all(r == results[0] for r in results[1:])
    return results[0]


def test_supervised_2_3_2_under_live_traffic_bit_identical(tmp_path):
    # fixed-W references first (separate stores: no cross-resume)
    ref2 = _reference_run(str(tmp_path / "ref2"), 2)
    ref3 = _reference_run(str(tmp_path / "ref3"), 3)
    assert ref2["w"] == 2 and ref3["w"] == 3

    # the elastic run: supervise.sh -w 2, three rounds (up, down, out)
    ck = str(tmp_path / "ck")
    ports = free_ports(12)            # 3 rounds x (coordinator + 3)
    p = subprocess.run(
        ["bash", SUPERVISE, "-n", "2", "-w", "2", "--",
         sys.executable, CHILD],
        env=_env(ck, ports), capture_output=True, text=True,
        timeout=load_scaled(900))
    assert p.returncode == 0, (
        f"supervisor failed:\n{p.stdout[-3000:]}\n{p.stderr[-3000:]}")
    results = [json.loads(l[len("RESULT "):])
               for l in p.stdout.splitlines()
               if l.startswith("RESULT ")]
    by_round = {}
    for r in results:
        by_round.setdefault(r["round"], []).append(r)
    assert sorted(by_round) == [0, 1, 2], sorted(by_round)
    # every rank of a round agrees exactly
    for rnd, rs in by_round.items():
        assert all(r == rs[0] for r in rs[1:]), f"round {rnd} diverged"
    r0, r1, r2 = (by_round[i][0] for i in (0, 1, 2))

    # the width walked 2 -> 3 -> 2, driven by the policy
    assert (r0["w"], r1["w"], r2["w"]) == (2, 3, 2)
    assert r0["autoscale_target"] == 3 and r1["autoscale_target"] == 2
    assert not r0["resumed"] and r1["resumed"] and r2["resumed"]
    # the relaunches restored the sealed RESIZE epoch
    assert r1["resume_skipped_ops"] >= 1
    assert r2["resume_skipped_ops"] >= 1
    # in-flight futures were drained to completion BEFORE each move
    assert r0["inflight_resolved_by_drain"]
    assert r1["inflight_resolved_by_drain"]

    # every JobFuture bit-identical to the fixed-W references
    for r, ref in ((r0, ref2), (r1, ref3), (r2, ref2)):
        assert r["base"] == ref["base"]
        assert r["early"] == ref["early"]
        assert r["late"] == ref["late"]
    assert "resize move committed; relaunching at W=3" in p.stderr
    assert "resize move committed; relaunching at W=2" in p.stderr
