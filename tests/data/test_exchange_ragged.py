"""Ragged exchange path: trace/shape validation (XLA:CPU cannot execute
ragged_all_to_all, so execution runs only on real TPU pods)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp


def test_ragged_path_traces_and_lowers(monkeypatch):
    from thrill_tpu.parallel.mesh import MeshExec
    from thrill_tpu.data import exchange

    # the env override is captured at mesh construction (resolve_mode
    # no longer reads os.environ per call) — set it FIRST
    monkeypatch.setenv("THRILL_TPU_EXCHANGE", "ragged")
    cpus = jax.devices("cpu")[:4]
    mex = MeshExec(devices=cpus)
    W, cap = 4, 8
    S = np.array([[1, 2, 0, 1], [0, 1, 1, 2], [2, 0, 1, 0],
                  [1, 1, 1, 1]], dtype=np.int64)
    leaves = [jnp.zeros((W, cap), jnp.int64)]
    treedef = jax.tree.structure(0)

    # tracing + abstract shapes must succeed; only backend compile
    # of the ragged op is TPU-only
    with pytest.raises(Exception) as ei:
        exchange._exchange_planned(mex, treedef, None, leaves, S)
    assert "ragged-all-to-all" in str(ei.value) or \
        "UNIMPLEMENTED" in str(ei.value), str(ei.value)[:200]


def test_lower_ragged_exchange_plan():
    """The dryrun's plan validation (lower WITHOUT compiling): the
    lowered module must contain the ragged collective, for multiple
    leaf schemas and skewed send matrices."""
    from thrill_tpu.parallel.mesh import MeshExec
    from thrill_tpu.data.exchange import lower_ragged_exchange

    mex = MeshExec(devices=jax.devices("cpu")[:4])
    S = np.array([[5, 0, 0, 1], [0, 1, 1, 2], [2, 0, 1, 0],
                  [1, 7, 1, 1]], dtype=np.int64)
    hlo = lower_ragged_exchange(
        mex, [(np.uint64, ()), (np.uint8, (10,)), (np.float32, (2, 2))],
        S)
    assert "ragged" in hlo.lower()


def test_ragged_off_tpu_warns_loudly(capsys, monkeypatch):
    """Forcing ragged on a CPU backend prints the untested-path gate
    before the compile error surfaces."""
    from thrill_tpu.parallel.mesh import MeshExec
    from thrill_tpu.data import exchange

    monkeypatch.setenv("THRILL_TPU_EXCHANGE", "ragged")
    mex = MeshExec(devices=jax.devices("cpu")[:2])
    S = np.array([[1, 1], [1, 1]], dtype=np.int64)
    leaves = [jnp.zeros((2, 4), jnp.int64)]
    treedef = jax.tree.structure(0)
    with pytest.raises(Exception):
        exchange._exchange_planned(mex, treedef, None, leaves, S)
    err = capsys.readouterr().err
    assert "UNIMPLEMENTED" in err and "ragged" in err


def test_landing_offsets_math():
    S = np.array([[3, 1], [2, 4]], dtype=np.int64)
    landing = np.cumsum(S, axis=0) - S
    # worker 1's chunk to dest 0 lands after worker 0's 3 items
    assert landing[1, 0] == 3 and landing[0, 0] == 0
    assert landing[1, 1] == 1
