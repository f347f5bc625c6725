"""What the benchmark's data files have to satisfy, as functions of a
ROOT directory (one that holds ``BENCHMARK.json`` and ``chipbench/``):
the same code checks the repository's tree (``test_harness.py``) and a
copy with entries added (``test_append_only.py``). Each raises
``AssertionError`` where a rule is broken. Nothing here names a cell, a
configuration, a traffic mix or a metric."""

import glob
import hashlib
import importlib.util
import json
import os
import re

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
LISTS = ("configs", "workloads", "end_to_end", "per_layer")
FIXED = ("command", "paths", "run_seconds")
APPEND_ONLY = ("entries go at the END of each list; an entry the benchmark "
               "had is not edited or moved: that takes a `benchmark` PR")


def load_bench(root):
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def load_run_py(root):
    """``<root>/chipbench/run.py``, which finds its files beside itself."""
    spec = importlib.util.spec_from_file_location(
        "chipbench_run", os.path.join(root, "chipbench", "run.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ------------------------------------------------------------ data files

def check_data_files(root, bench):
    """Every configuration and traffic file loads, is well formed and is
    used by an entry; every entry's file is there."""
    folder = os.path.join(root, "chipbench")
    configs = {os.path.join(root, c["file"]) for c in bench["configs"]}
    assert configs == set(glob.glob(os.path.join(folder, "configs",
                                                 "*.json")))
    for path in configs:
        with open(path) as f:
            c = json.load(f)
        assert os.path.exists(os.path.join(folder, "jobs", c["job"] + ".py"))
        assert c["guarantees"] and c["shapes"] and c["source"]
    used = {w["traffic"] for w in bench["workloads"]}
    have = {os.path.basename(p)[:-5] for p in
            glob.glob(os.path.join(folder, "traffic", "*.json"))}
    assert used == have
    for name in have:
        with open(os.path.join(folder, "traffic", name + ".json")) as f:
            t = json.load(f)
        assert t["loop"] == "closed" and t["clients"] == 1
    with open(os.path.join(folder, "peaks.json")) as f:
        assert "TPU v5 lite" in json.load(f)["device_kinds"]


def check_names_units_and_keys(bench):
    """The contract's rules on ``BENCHMARK.json`` itself."""
    assert set(bench) == set(FIXED) | set(LISTS)
    assert 1 <= bench["run_seconds"] <= 51
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and all(map(NAME.match, c["reduced"]))
        assert any(c["file"].startswith(p + "/") for p in bench["paths"])
        assert 1 <= len(c["why"]) <= 200 and 1 <= len(c["source"]) <= 200
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
    four = sum(w["chips"] == 4 for w in bench["workloads"])
    assert four <= max(1, len(bench["workloads"]) // 2)
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in SOURCES
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for group in ("configs", "workloads"):
        names = [e["name"] for e in bench[group]]
        assert len(names) == len(set(names))
    names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(names) == len(set(names))
    assert "setup_s" in names


def check_readers(root, bench):
    """Every per-layer metric has a reader and moves an end-to-end metric
    that each of its cells reports; every cell reports enough."""
    run_py = load_run_py(root)
    cells = [w["name"] for w in bench["workloads"]]
    for m in bench["per_layer"]:
        assert callable(run_py.load_module("layer_metrics", m["name"]).read)
        moved = next(e for e in bench["end_to_end"]
                     if e["name"] == m["moves"])
        listed = m.get("workloads", cells)
        assert len(listed) == len(set(listed))
        for cell in listed:
            assert cell in cells
            assert cell in moved.get("workloads", cells)
    for cell in cells:
        assert len(run_py.metrics_of(bench, "end_to_end", cell)) >= 2
        assert run_py.metrics_of(bench, "per_layer", cell)


# ----------------------------------------------------------- append-only

def digest(group, entry):
    """sha256 of an entry's canonical JSON. A per-layer metric is hashed
    without its ``workloads``, whose accepted names are kept apart: that
    list may grow at its end."""
    if group == "per_layer":
        entry = {k: v for k, v in entry.items() if k != "workloads"}
    return hashlib.sha256(json.dumps(
        entry, sort_keys=True, separators=(",", ":")).encode()).hexdigest()


def accepted_order(bench):
    """What ``accepted_order.json`` holds for ``bench``."""
    out = {"fixed": {k: bench[k] for k in FIXED}}
    for group in LISTS:
        out[group] = [
            {"name": e["name"], "sha256": digest(group, e),
             **({"workloads": e["workloads"]}
                if group == "per_layer" and "workloads" in e else {})}
            for e in bench[group]]
    return out


def check_append_only(bench, accepted):
    """The driver's rule for a PR that changes the program: each list of
    ``bench`` STARTS with the accepted entries, in the accepted order and
    unedited; so does each metric's ``workloads``. What follows the
    accepted prefix is free."""
    for key, value in accepted["fixed"].items():
        assert bench[key] == value, f"`{key}` was changed; {APPEND_ONLY}"
    for group in LISTS:
        have = bench[group]
        for i, want in enumerate(accepted[group]):
            where = f"`{group}`[{i}] is to be `{want['name']}`"
            assert i < len(have), f"{where}, and is gone; {APPEND_ONLY}"
            assert have[i]["name"] == want["name"], (
                f"{where}, and is `{have[i]['name']}`; {APPEND_ONLY}")
            assert digest(group, have[i]) == want["sha256"], (
                f"`{group}`: `{want['name']}` was edited; {APPEND_ONLY}")
            if group != "per_layer":
                continue        # hashed whole
            if "workloads" in want:
                got = have[i].get("workloads")
                assert got is not None and \
                    got[:len(want["workloads"])] == want["workloads"], (
                        f"`{want['name']}`: `workloads` is to start with "
                        f"{want['workloads']}, and is {got}; {APPEND_ONLY}")
            else:
                assert "workloads" not in have[i], (
                    f"`{want['name']}` was given a `workloads` list; "
                    f"{APPEND_ONLY}")
