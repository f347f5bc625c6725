"""Supervised process-level elasticity (ISSUE 20): drain -> resize ->
relaunch-with-resume as ONE move, on real processes under the real
supervisor (run-scripts/supervise.sh).

Pinned acceptance:

* a W=2 run scales to 3 VIA AN AUTOSCALE DECISION (the real policy
  fed an injected hot metric sequence), exits 75 with a committed
  RESIZE marker, and the supervisor relaunches it at W'=3 with
  resume — the relaunch restores the RESIZE epoch through the
  standard resume path, bit-identical, and consumes the marker;
* a sustained-idle sequence then shrinks it back to 2 the same way;
* a SIGKILL between the marker commit and the relaunch exit — the
  nastiest window — is completed by the supervisor on its crash-retry
  path: the restart budget is charged but the move lands at W'=3
  with no wrong data and no revival of the old W;
* the slow lane runs the full 2->3->2 under LIVE front-door traffic
  (test_resize_proc_traffic.py's lane in the bench covers timings).
"""

import json
import os
import subprocess
import sys
import tempfile

import pytest

from portalloc import load_scaled

CHILD = os.path.join(os.path.dirname(__file__), "resize_proc_child.py")
SUPERVISE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), "run-scripts", "supervise.sh")

_COMPILE_CACHE_DIR = os.path.join(
    tempfile.gettempdir(), "thrill-tpu-test-xla-cache")


def _run_supervised(tmp_path, extra_env=None, timeout_s=420):
    state = str(tmp_path / "state")
    ck = str(tmp_path / "ck")
    os.makedirs(state, exist_ok=True)
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
        "TEST_STATE_DIR": state,
        "JAX_COMPILATION_CACHE_DIR": _COMPILE_CACHE_DIR,
    })
    env.update(extra_env or {})
    p = subprocess.run(
        ["bash", SUPERVISE, "-n", "2", "--", sys.executable, CHILD],
        env=env, capture_output=True, text=True,
        timeout=load_scaled(timeout_s))
    phases = [json.loads(l[len("PHASE "):])
              for l in p.stdout.splitlines() if l.startswith("PHASE ")]
    return p, phases


def test_supervised_autoscale_resize_up_then_down_bit_identical(
        tmp_path):
    p, phases = _run_supervised(tmp_path)
    assert p.returncode == 0, (
        f"supervisor failed:\n{p.stdout[-2000:]}\n{p.stderr[-3000:]}")
    assert [ph["phase"] for ph in phases] == [0, 1, 2], phases
    # the width walked 2 -> 3 -> 2, each step a supervised relaunch
    assert [ph["w"] for ph in phases] == [2, 3, 2]
    assert [ph["resumed"] for ph in phases] == [False, True, True]
    # every relaunch restored the sealed RESIZE epoch (bit-identical
    # to the fixed-W reference the first phase computed) and the
    # resumed run itself consumed the marker before the job body ran
    want = sorted(i * 3 + 1 for i in range(96))
    assert all(ph["result"] == want for ph in phases)
    assert all(ph["resume_skipped_ops"] >= 1 for ph in phases[1:])
    assert not any(ph["marker_pending"] for ph in phases)
    # clean-75 relaunches are FREE: no restart budget burned, and the
    # supervisor said exactly what it did
    assert "resize move committed; relaunching at W=3" in p.stderr
    assert "resize move committed; relaunching at W=2" in p.stderr
    assert "restart" not in p.stdout


def test_sigkill_between_marker_and_relaunch_completed_by_supervisor(
        tmp_path):
    p, phases = _run_supervised(
        tmp_path, extra_env={"TEST_KILL_AFTER_MARKER": "1"})
    assert p.returncode == 0, (
        f"supervisor failed:\n{p.stdout[-2000:]}\n{p.stderr[-3000:]}")
    # phase 0 died by SIGKILL after the marker landed; the supervisor
    # charged its restart budget but COMPLETED the move at W'=3
    assert [ph["phase"] for ph in phases] == [0, 1], phases
    assert phases[1]["w"] == 3 and phases[1]["resumed"]
    want = sorted(i * 3 + 1 for i in range(96))
    assert phases[1]["result"] == want       # no wrong data
    assert phases[1]["resume_skipped_ops"] >= 1
    assert not phases[1]["marker_pending"]
    assert "completing move to W=3 on restart 1/2" in p.stderr


# -- seeded chaos over the new move sites (CHAOS_ELASTIC=1) ---------------

N_ELASTIC_SEEDS = int(os.environ.get("THRILL_TPU_ELASTIC_SEEDS", "2"))


@pytest.mark.chaos
@pytest.mark.parametrize("seed", range(N_ELASTIC_SEEDS))
def test_chaos_process_move_sites_nothing_mutated_then_commit(
        seed, tmp_path, monkeypatch):
    """Seeded chaos over the three process-move sites (armed at full
    seed count by ``run-scripts/chaos_sweep.sh`` CHAOS_ELASTIC=1):
    whichever site fires, the failed attempt leaves W, generation and
    the marker EXACTLY as before — then the clean retry commits the
    whole move (seal + marker) in one shot."""
    import numpy as np

    from thrill_tpu.api import Context
    from thrill_tpu.api.checkpoint import pending_resize_target
    from thrill_tpu.api.context import ResizeRelaunch
    from thrill_tpu.common import faults
    from thrill_tpu.common.config import Config
    from thrill_tpu.parallel.mesh import MeshExec
    from thrill_tpu.service.autoscale import (AutoscalePolicy,
                                              Autoscaler)

    monkeypatch.delenv(faults.ENV_VAR, raising=False)
    faults.REGISTRY.reset()
    site = ["ckpt.resize_manifest", "net.group.relaunch",
            "svc.autoscale.decide"][seed % 3]
    ck = str(tmp_path / "ck")
    ctx = Context(MeshExec(num_workers=2), config=Config(ckpt_dir=ck))
    try:
        d = ctx.Distribute(np.arange(48, dtype=np.int64)).Map(
            lambda x: x * 5 + seed)
        d.Keep(4)
        want = sorted(int(x) for x in d.AllGather())
        gen0, w0 = ctx.generation, ctx.num_workers

        a = Autoscaler(ctx, policy=AutoscalePolicy(
            min_w=2, max_w=3, up_queue=8, confirm_ticks=1,
            idle_ticks=9, cooldown_ticks=0))
        hot = {"queue_depth": 99, "jobs_rejected": 0,
               "jobs_in_flight": 2, "serve_p99_ms": 0.0}
        with faults.inject(site, n=1, seed=seed):
            if site == "svc.autoscale.decide":
                with pytest.raises(faults.InjectedFault):
                    a.tick()
                target = a.observe(hot, ctx.num_workers)  # clean retry
            else:
                target = a.observe(hot, ctx.num_workers)
                with pytest.raises(faults.InjectedFault):
                    ctx.resize_processes(target, state=d)
        assert target == 3
        # nothing mutated by the armed failure
        assert ctx.num_workers == w0 and ctx.generation == gen0
        assert pending_resize_target(ck) is None
        assert ctx.stats_resizes_proc == 0
        assert sorted(int(x) for x in d.AllGather()) == want
        # the clean retry commits the whole move
        with pytest.raises(ResizeRelaunch):
            ctx.resize_processes(target, state=d)
        mark = pending_resize_target(ck)
        assert mark["target_w"] == 3
        assert ctx.stats_resizes_proc == 1
        assert faults.REGISTRY.injected >= 1
    finally:
        ctx.close()
