#!/usr/bin/env python3
"""Rewrite ``accepted_order.json`` beside this file from ``BENCHMARK.json``
as it stands: the names of its lists in order, each entry's sha256, each
metric's ``workloads``. Run by hand, by a `benchmark` PR alone and after
its last edit to ``BENCHMARK.json``; no test calls it. A PR that changes
the program leaves the file as it is, and ``test_append_only.py`` then
holds that PR's ``BENCHMARK.json`` to it."""

import json
import os

import bench_contract       # beside this file

HERE = os.path.dirname(os.path.abspath(__file__))

if __name__ == "__main__":
    bench = bench_contract.load_bench(os.path.dirname(os.path.dirname(HERE)))
    with open(os.path.join(HERE, "accepted_order.json"), "w") as f:
        json.dump(bench_contract.accepted_order(bench), f, indent=1)
        f.write("\n")
