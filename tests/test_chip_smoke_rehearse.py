"""chip_smoke.py on the CPU: the rehearsal passes end to end in this
process, and nothing but a TPU ever gets the contract line."""

import importlib.util
import json
import os
import sys

import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def smoke(monkeypatch):
    # what the script sets for a rehearsal is restored afterwards: the
    # worker goes on to other tests
    for var in ("THRILL_TPU_HOST_RADIX", "THRILL_TPU_SORT_U32",
                "THRILL_TPU_PACK_MOVE"):
        monkeypatch.setenv(var, "")     # registers the restore...
        monkeypatch.delenv(var)         # ...and leaves the script a clean slate
    monkeypatch.setattr(sys, "path", list(sys.path))
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(_ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _contract_lines(out: str):
    hits = []
    for line in out.splitlines():
        try:
            obj = json.loads(line)
        except ValueError:
            continue
        if isinstance(obj, dict) and obj.get("ok") is True:
            hits.append(line)
    return hits


def test_rehearsal_passes_and_never_prints_the_contract_line(smoke, capsys):
    assert smoke.main(["--rehearse", "--records", "4096"]) == 0
    out = capsys.readouterr().out
    last = json.loads(out.strip().splitlines()[-1])
    assert last["ok"] is False and last["rehearsal"] == "passed"
    assert last["device"]["platform"] == "cpu"
    assert not _contract_lines(out)
    for phase in ("native", "terasort", "wordcount", "kernels"):
        assert f"== phase {phase} passed" in out
    assert "equal the numpy reference, byte for byte" in out


def test_without_a_tpu_it_fails_and_prints_no_result(smoke, capsys):
    assert smoke.main([]) != 0
    out = capsys.readouterr().out
    assert "no TPU" in out
    assert not _contract_lines(out)
    assert "== phase" not in out
