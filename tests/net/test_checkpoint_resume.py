"""Worker loss + supervised relaunch with resume (real processes).

The acceptance shape of the checkpoint/resume subsystem
(api/checkpoint.py): SIGKILL one worker mid-PageRank, relaunch the
whole group with ``resume=True``, and the job completes with results
BIT-IDENTICAL to an uninterrupted run — resuming from the last
committed epoch instead of recomputing from scratch. The pipeline uses
host storage so every exchange and collective rides this framework's
own TCP control plane (the layer whose failure semantics are under
test), and the collective watchdog (THRILL_TPU_HANG_TIMEOUT_S)
converts the survivor's wait on the killed peer into a fast
ClusterAbort instead of a hang.
"""

import json
import os
import subprocess
import sys
import tempfile

import pytest

from portalloc import free_ports, load_scaled

# ~2 minutes of real process launches (3 runs x 2 ranks): excluded
# from the tier-1 wall-clock budget like the other long-running
# launches; the fast in-process kill-and-resume coverage rides tier-1
# in tests/api/test_checkpoint.py (chaos-marked seeds included)
pytestmark = pytest.mark.slow

CHILD = os.path.join(os.path.dirname(__file__), "checkpoint_child.py")

_COMPILE_CACHE_DIR = os.path.join(
    tempfile.gettempdir(), "thrill-tpu-test-xla-cache")


def _launch(nproc, ckpt_dir, extra_env=None):
    ports = free_ports(1 + nproc)
    coordinator = f"127.0.0.1:{ports[0]}"
    hostlist = " ".join(f"127.0.0.1:{p}" for p in ports[1:])
    procs = []
    for rank in range(nproc):
        env = dict(os.environ)
        env.pop("XLA_FLAGS", None)
        repo_root = os.path.dirname(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))))
        env.update({
            "PYTHONPATH": repo_root + os.pathsep
            + env.get("PYTHONPATH", ""),
            "THRILL_TPU_SECRET": "test-cluster-secret",
            "JAX_COMPILATION_CACHE_DIR": _COMPILE_CACHE_DIR,
            "THRILL_TPU_HOSTLIST": hostlist,
            "THRILL_TPU_RANK": str(rank),
            "THRILL_TPU_CKPT_DIR": ckpt_dir,
            # the watchdog is what turns the killed peer into a clean
            # abort on the survivor (fixed, not load-scaled: the test
            # owns the whole group, nothing else legitimately blocks)
            "THRILL_TPU_HANG_TIMEOUT_S": "20",
        })
        env.update(extra_env or {})
        procs.append(subprocess.Popen(
            [sys.executable, CHILD, coordinator, str(rank), str(nproc)],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True, env=env))
    return procs


def _drain(procs, timeout_s, expect_ok=True):
    import concurrent.futures as cf
    timeout_s = load_scaled(timeout_s)
    with cf.ThreadPoolExecutor(len(procs)) as ex:
        futs = [ex.submit(p.communicate, None, timeout_s)
                for p in procs]
        try:
            drained = [f.result(timeout=timeout_s + 20) for f in futs]
        except (cf.TimeoutError, subprocess.TimeoutExpired):
            for q in procs:
                q.kill()
            raise AssertionError(
                f"child timed out ({timeout_s:.0f}s) — a worker HUNG "
                f"instead of aborting/resuming")
    results = []
    for p, (out, err) in zip(procs, drained):
        if expect_ok:
            assert p.returncode == 0, f"child failed:\n{err[-3000:]}"
            lines = [l for l in out.splitlines()
                     if l.startswith("RESULT ")]
            assert lines, f"no RESULT line:\n{out}\n{err[-2000:]}"
            results.append(json.loads(lines[-1][len("RESULT "):]))
        else:
            results.append((p.returncode, out, err))
    return results


def test_sigkill_one_worker_resume_bit_identical(tmp_path):
    nproc = 2
    # 1) golden: uninterrupted run
    golden_dir = str(tmp_path / "golden")
    golden = _drain(_launch(nproc, golden_dir), 420)
    assert golden[0]["ranks"] == golden[1]["ranks"]
    assert golden[0]["epochs"] == 5
    assert golden[0]["hosts"] == nproc

    # 2) crash run: rank 1 SIGKILLs itself entering epoch 3's save —
    # epochs 0..2 are committed, 3 is at most half-written. The
    # survivor must ABORT (watchdog/poison), not hang.
    crash_dir = str(tmp_path / "crash")
    outcomes = _drain(
        _launch(nproc, crash_dir,
                extra_env={"TEST_KILL_RANK": "1",
                           "TEST_KILL_AT_EPOCH": "3"}),
        420, expect_ok=False)
    assert outcomes[1][0] == -9, "rank 1 was not SIGKILLed"
    assert outcomes[0][0] != 0, \
        "survivor exited 0 despite losing its peer"
    committed = sorted(
        d for d in os.listdir(crash_dir)
        if os.path.isfile(os.path.join(crash_dir, d, "MANIFEST.json")))
    assert committed == ["epoch_000000", "epoch_000001",
                         "epoch_000002"], committed

    # 3) supervised relaunch with resume: bit-identical final ranks,
    # and the first two iterations were SKIPPED, not recomputed
    resumed = _drain(
        _launch(nproc, crash_dir,
                extra_env={"THRILL_TPU_RESUME": "1"}), 420)
    assert resumed[0]["ranks"] == golden[0]["ranks"], \
        "resumed run diverged from the uninterrupted run"
    assert resumed[1]["ranks"] == golden[0]["ranks"]
    assert resumed[0]["resume_skipped_ops"] >= 1, \
        "resume recomputed from scratch"
    # the incomplete epoch_000003 from the crash was cleaned up
    assert not os.path.isdir(os.path.join(crash_dir, "epoch_000003")) \
        or os.path.isfile(os.path.join(
            crash_dir, "epoch_000003", "MANIFEST.json"))
