"""Job kind ``suffixsort`` and the readers ``pulls_per_job`` and
``replan_gap_s_per_job``, on the CPU: the generator against the stated
text, the planted repeat and the rounds it fixes, the byte count by
hand, the reference against sorted suffixes, the checker, the control
stopped one round early, the comparison on broken results and on a job
a round short or long, the job's pipeline on four kinds of text at
eight lengths on one and on four workers, the readers on a made-up
counter and ring, and rehearsals of ``suffix.w1``. Entries of
``BENCHMARK.json`` are found by name; no position is pinned. No number
here is a device number."""

import importlib.util
import json
import os
import sys

import numpy as np
import pytest

from thrill_tpu.api import Context
from thrill_tpu.parallel.mesh import MeshExec

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_BENCH = os.path.join(_ROOT, "chipbench")
TRAFFIC = {"chars": 512, "words": 256, "zipf": 1.1, "word_letters": "4-16",
           "index_bytes": 4, "initial_h": 4}
# the cell's text at other lengths: 2^16 words, the plant, its rounds
PLANTED = {**TRAFFIC, "words": 65536, "planted_repeat_bytes": 160,
           "rounds": 6}
EVERY_CELL = ("dispatches_per_job", "fetches_per_job", "compiles_in_window",
              "device_idle_share", "device_busy_ms_per_job", "job_roofline",
              "hbm_peak_share", "oom_ladder_events", "upload_s_per_job",
              "upload_bytes_per_job", "dispatch_call_s_per_job",
              "sync_wait_s_per_job", "fetch_s_per_job",
              "host_plan_s_per_job", "compile_s_in_window", "job_s_max")
ALL_RIGHT = {"sa_not_permutation": (0, 0), "sa_rows_differing": (0, 0),
             "sa_order_violations": (0, 0), "rounds_differing": (0, 0)}
LENGTHS = (1, 2, 3, 5, 255, 256, 1000, 4096)


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def job():
    return _load(os.path.join(_BENCH, "jobs", "suffixsort.py"),
                 "suffixsort_job_t")


@pytest.fixture(scope="module")
def pulls_reader():
    return _load(os.path.join(_BENCH, "layer_metrics", "pulls_per_job.py"),
                 "reader_pulls_per_job")


@pytest.fixture(scope="module")
def gap_reader():
    return _load(os.path.join(_BENCH, "layer_metrics",
                              "replan_gap_s_per_job.py"),
                 "reader_replan_gap_s_per_job")


def naive(text):
    b = bytes(text)
    return np.array(sorted(range(len(b)), key=lambda i: b[i:]),
                    dtype=np.uint32)


def text_of(job, kind, n):
    """The four kinds of text the issue names, from a seed."""
    if kind == "zipf":
        return job.generate(2**31 + 34 + n, {**TRAFFIC, "chars": n},
                            {})["text"]
    if kind == "one_char":         # the most rounds a length allows
        return np.full(n, ord("a"), np.uint8)
    if kind == "period3":
        return np.resize(np.frombuffer(b"abc", np.uint8), n).copy()
    return np.random.default_rng(n).integers(
        97, 100, n).astype(np.uint8)  # uniform over 3 letters


# ------------------------------------------------------------- generator

def test_the_generator_is_a_function_of_the_seed_alone(job):
    big = 2**31 + 11
    a, b = (job.generate(big, TRAFFIC, {}) for _ in range(2))
    other = job.generate(big + 1, TRAFFIC, {})
    assert sorted(a) == ["text"]
    assert a["text"].dtype == np.uint8 and a["text"].shape == (512,)
    assert a["text"].tobytes() == b["text"].tobytes()
    assert a["text"].tobytes() != other["text"].tobytes()
    assert job.records(TRAFFIC) == 512


def test_the_text_is_zipf_words_joined_by_single_spaces(job):
    traffic = {**TRAFFIC, "chars": 1 << 16}
    text = job.generate(7, traffic, {})["text"].tobytes()
    assert len(text) == 1 << 16
    assert set(text) <= set(b"abcdefghijklmnopqrstuvwxyz ")
    words = text.split(b" ")[:-1]           # the last one may be cut
    assert b"" not in words                 # single spaces
    assert all(4 <= len(w) <= 16 for w in words)
    # Zipf 1.1 over 256 words: the most frequent word is far ahead of
    # the median one, and words repeat (that is what makes the rounds)
    counts = sorted((words.count(w) for w in set(words)), reverse=True)
    assert len(counts) <= 256 and counts[0] > 8 * counts[len(counts) // 2]


def test_the_text_is_the_drawn_words_joined_and_cut(job):
    # the vectorised assembly against bytes.join, word by word
    n, seed = 1000, 2**31 + 7
    rng = np.random.default_rng(seed)
    vocab = job.vocabulary(rng, 256, 4, 16)
    p = 1.0 / np.arange(1, 257) ** 1.1
    ids = rng.choice(256, size=n // 5 + 1, p=p / p.sum())
    joined = b" ".join(bytes(vocab[i][vocab[i] != 0]) for i in ids) + b" "
    assert job.generate(seed, {**TRAFFIC, "chars": n},
                        {})["text"].tobytes() == joined[:n]


def longest_repeat(text, sa):
    """The longest common prefix of two neighbours of the suffix array,
    byte by byte: the longest substring the text holds twice."""
    ext = np.concatenate([text.astype(np.int64),
                          -1 - np.arange(len(text) + 1)])   # never equal
    a, b = sa[:-1].astype(np.int64), sa[1:].astype(np.int64)
    length = np.zeros(len(a), np.int64)
    live = np.ones(len(a), bool)
    while live.any():
        live[live] = ext[a[live] + length[live]] == ext[b[live]
                                                        + length[live]]
        length[live] += 1
    return int(length.max())


@pytest.mark.parametrize("n", [4096, 1 << 16])
@pytest.mark.parametrize("seed", [1, 9, 36, 2**31 + 36])
def test_the_planted_repeat_is_the_texts_longest_and_fixes_six_rounds(
        job, seed, n):
    planted = {**PLANTED, "chars": n}
    plain = {k: v for k, v in planted.items()
             if k != "planted_repeat_bytes"}
    text = job.generate(seed, planted, {})["text"]
    base = job.generate(seed, plain, {})["text"]
    # one span differs from the text without the plant, in its second
    # half, and is a copy of a span of the first half more than its
    # length away
    changed = np.flatnonzero(text != base)
    assert len(changed) and changed[0] > n // 2
    assert changed[-1] - changed[0] < 160
    rng = np.random.default_rng(seed)
    job.zipf_text(rng, n, planted)
    src, dst = job.plant_repeat(rng, base.copy(), 160)
    assert src + 160 <= n // 2 < dst and dst + 160 <= n
    assert dst - src > 160
    assert text[dst:dst + 160].tobytes() == text[src:src + 160].tobytes()
    want = job.reference({"text": text}, planted)
    # 128 < the longest repeat < 256: names of 256 characters are the
    # first that are all distinct, six doublings from 4
    assert 160 <= longest_repeat(text, want["sa"]) < 256
    assert (want["rounds_needed"], want["rounds"]) == (6, 6)
    unplanted = job.reference({"text": base}, plain)
    assert longest_repeat(base, unplanted["sa"]) < 128
    assert unplanted["rounds_needed"] < 6


def test_a_text_too_short_for_its_plant_is_refused(job):
    with pytest.raises(ValueError, match="cannot hold a planted repeat"):
        job.generate(1, {**PLANTED, "chars": 321}, {})
    assert len(job.generate(1, {**PLANTED, "chars": 322}, {})["text"]) \
        == 322


@pytest.mark.parametrize("power,rounds", [(1, 0), (2, 0), (4, 0), (8, 1),
                                          (64, 4), (128, 5), (256, 6)])
def test_rounds_from_the_first_power_that_names_all(job, power, rounds):
    assert job.rounds_from(power) == rounds


def test_the_job_kind_asks_for_the_ring_its_readers_need(job, monkeypatch):
    # the benchmark asks for the room through the variable that is
    # there for it, where the kind is loaded (before Run() is made);
    # the program's own default stays 512
    from thrill_tpu.common import trace
    assert os.environ["THRILL_TPU_TRACE_RING"] == "4096"
    assert trace.ring_capacity() == 4096
    monkeypatch.delenv("THRILL_TPU_TRACE_RING")
    assert trace.ring_capacity() == 512


def test_min_bytes_by_hand(job):
    # the text read once, a 4-byte index per suffix written once
    assert job.min_bytes(TRAFFIC, {}, None) == 512 + 4 * 512
    cell = {**TRAFFIC, "chars": 4194304}
    assert job.min_bytes(cell, {}, None) == 20_971_520


# ------------------------------------------------------------- reference

@pytest.mark.parametrize("kind", ["zipf", "one_char", "period3", "uniform3"])
@pytest.mark.parametrize("n", [1, 2, 3, 5, 255, 256, 512])
def test_the_reference_is_the_sorted_suffixes(job, kind, n):
    text = text_of(job, kind, n)
    want = job.reference({"text": text}, {})
    assert want["sa"].dtype == np.uint32
    assert np.array_equal(want["sa"], naive(text))
    assert job.order_violations(text, want["sa"]) == 0
    # the rounds a job from h = 4 needs: the first 4 * 2^r characters
    # that tell all suffixes apart (the end of the text smallest)
    b = bytes(text)
    r = 0
    while len({b[i:i + (4 << r)] for i in range(n)}) < n:
        r += 1
    assert want["rounds_needed"] == r and want["rounds"] is None


def test_the_checker_needs_no_construction(job):
    text = np.frombuffer(b"banana", np.uint8)
    assert job.order_violations(text, np.array([5, 3, 1, 0, 4, 2])) == 0
    # "ana" before "a": the end of the text is smallest
    assert job.order_violations(text, np.array([3, 5, 1, 0, 4, 2])) >= 1
    assert job.order_violations(text, np.array([5, 3, 1, 0, 2, 4])) >= 1
    assert job.order_violations(text, np.arange(6)) >= 1


@pytest.mark.parametrize("seed", [11, 13, 14, 2**31 + 15])
def test_the_control_comes_out_not_correct(job, seed):
    # at the rehearsal's size: one round early, ties in index order.
    # The last round tells apart only the pairs inside the planted
    # repeat, all by the one byte behind it, and index order gets them
    # all right or all wrong (seeds 11 and 13 right, 14 wrong); the
    # rounds are one short on every seed
    traffic = {**PLANTED, "chars": 4096}
    inp = job.generate(seed, traffic, {})
    want = job.reference(inp, traffic)
    assert job.compare({"sa": want["sa"], "rounds": 6}, want) == {
        **ALL_RIGHT, "rounds_needed": (6, 6)}
    control = job.control(inp, traffic)
    assert control["rounds"] == 5
    bad = job.compare(control, want)
    assert bad["sa_not_permutation"] == (0, 0)
    assert bad["rounds_differing"] == (1, 0)
    assert bad["rounds_needed"] == (6, 6)
    wrong = bad["sa_rows_differing"][0]
    assert (wrong, bad["sa_order_violations"][0] > 0) in ((0, False),
                                                          (66, True))
    assert any(value > limit for value, limit in bad.values())


@pytest.mark.parametrize("seed", [11, 13, 14, 2**31 + 15])
def test_the_control_of_a_plain_text_breaks_the_order(job, seed):
    # without the plant the last round has pairs of several repeats to
    # tell apart, and index order gets some wrong
    traffic = {**TRAFFIC, "chars": 4096, "words": 65536}
    inp = job.generate(seed, traffic, {})
    want = job.reference(inp, traffic)
    bad = job.compare(job.control(inp, traffic), want)
    assert bad["sa_not_permutation"] == (0, 0)
    assert bad["sa_rows_differing"][0] >= 2
    assert bad["sa_order_violations"][0] >= 1
    assert bad["rounds_differing"] == (1, 0)
    assert "rounds_needed" not in bad       # this traffic states none


def test_the_control_of_a_text_of_one_round_is_the_reference(job):
    # nothing was left untold: no tie to leave in index order
    inp = {"text": np.frombuffer(b"dcba", np.uint8)}
    control = job.control(inp, {})
    assert control["sa"].tolist() == [3, 2, 1, 0] and control["rounds"] == 0


# ------------------------------------------------------------ comparison

def test_compare_reads_a_broken_result(job):
    inp = job.generate(5, TRAFFIC, {})
    want = job.reference(inp, TRAFFIC)
    n, rounds = 512, want["rounds_needed"]
    all_n = {k: (n, 0) for k in ALL_RIGHT}

    def result(sa):
        return {"sa": sa, "rounds": rounds}

    assert job.compare(result(want["sa"]), want) == ALL_RIGHT
    # no result; the input in the result's place; another length;
    # another dtype kind
    assert job.compare(None, want) == all_n
    assert job.compare(dict(inp), want) == all_n
    assert job.compare(result(want["sa"][:n // 2]), want) == all_n
    assert job.compare(result(want["sa"].astype(np.float64)), want) == all_n
    # a suffix array that does not say its rounds
    assert job.compare({"sa": want["sa"]}, want) == {
        **ALL_RIGHT, "rounds_differing": (n, 0)}
    # a non-permutation: one index twice, one missing; order unchecked
    twice = want["sa"].copy()
    twice[7] = twice[8]
    got = job.compare(result(twice), want)
    assert got["sa_not_permutation"] == (2, 0)
    assert got["sa_rows_differing"] == (1, 0)
    assert got["sa_order_violations"] == (n, 0)
    assert got["rounds_differing"] == (0, 0)
    # an index out of range is no permutation either
    far = want["sa"].copy()
    far[0] = n + 5
    assert job.compare(result(far), want)["sa_not_permutation"] == (1, 0)
    # two rows swapped: still a permutation, out of order
    swapped = want["sa"].copy()
    swapped[[100, 101]] = swapped[[101, 100]]
    got = job.compare(result(swapped), want)
    assert got["sa_not_permutation"] == (0, 0)
    assert got["sa_rows_differing"] == (2, 0)
    assert got["sa_order_violations"][0] >= 1
    # int64 indices of the same values are the same suffix array
    assert job.compare(result(want["sa"].astype(np.int64)), want)[
        "sa_rows_differing"] == (0, 0)


@pytest.mark.parametrize("off", [-1, 1, 2])
def test_a_job_a_round_short_or_long_reads_its_distance(job, off):
    # the second guarantee, held in every job: the right suffix array
    # after another number of rounds than the text needs is not correct
    traffic = {**PLANTED, "chars": 4096}
    want = job.reference(job.generate(3, traffic, {}), traffic)
    got = job.compare({"sa": want["sa"], "rounds": 6 + off}, want)
    assert got == {**ALL_RIGHT, "rounds_differing": (abs(off), 0),
                   "rounds_needed": (6, 6)}


def test_a_text_that_needs_more_rounds_than_the_file_states_shows(job):
    # the reading is the text's, the limit the traffic file's
    traffic = {**PLANTED, "chars": 4096, "rounds": 5}
    want = job.reference(job.generate(3, traffic, {}), traffic)
    got = job.compare({"sa": want["sa"], "rounds": 6}, want)
    assert got["rounds_differing"] == (0, 0)
    assert got["rounds_needed"] == (6, 5)


# ------------------------------------------------- the job's pipeline

@pytest.fixture(scope="module")
def contexts():
    """One Context per mesh size for the whole matrix: texts of one
    length share their compiled programs."""
    made = {}

    def get(workers):
        if workers not in made:
            made[workers] = Context(MeshExec(num_workers=workers))
        return made[workers]

    yield get
    for ctx in made.values():
        ctx.close()


@pytest.mark.parametrize("workers", [1, 4])
@pytest.mark.parametrize("n", LENGTHS)
@pytest.mark.parametrize("kind", ["zipf", "one_char", "period3", "uniform3"])
def test_the_pipeline_gives_the_suffix_array(job, contexts, monkeypatch,
                                             kind, n, workers):
    # the device programs, as on the chip and in a rehearsal
    monkeypatch.setenv("THRILL_TPU_HOST_RADIX", "0")
    text = text_of(job, kind, n)
    inp = {"text": text}
    got = job.fetch(job.pipeline(contexts(workers), {"text": text.view()}))
    assert sorted(got) == ["rounds", "sa"]
    assert got["sa"].dtype == np.uint32
    assert job.compare(got, job.reference(inp, {})) == ALL_RIGHT
    if kind == "one_char" and n >= 255:
        # names tell 4 characters apart, every round doubles that
        assert got["rounds"] == int(np.ceil(np.log2(n / 4)))


def test_a_job_is_one_pipeline_and_nothing_compiles_after_the_first(
        job, monkeypatch):
    monkeypatch.setenv("THRILL_TPU_HOST_RADIX", "0")
    span_window = _load(os.path.join(_BENCH, "span_window.py"),
                        "span_window_suffix_t")
    text = job.generate(2**31 + 36, {**PLANTED, "chars": 4096}, {})["text"]
    ctx = Context(MeshExec(num_workers=1))
    try:
        took = [job.pipeline(ctx, {"text": text.view()})["rounds"]]
        s0 = ctx.overall_stats()        # behind the warm-up job
        for _ in range(3):
            took.append(job.pipeline(ctx, {"text": text.view()})["rounds"])
        s1 = ctx.overall_stats()
        records = list(ctx.tracer.ring)
        assert not ctx.tracer.wrapped
    finally:
        ctx.close()
    assert s1["compiles"] - s0["compiles"] == 0
    assert s1["device_uploads"] - s0["device_uploads"] == 3
    rounds = took[0]
    assert rounds == 6 and took == [rounds] * 4
    # a pull per names step, per round and for the result
    assert s1["pulls"] - s0["pulls"] == 3 * (rounds + 2)
    assert s1["device_fetches"] - s0["device_fetches"] == 3 * (rounds + 1)
    # jobs + 1 pipelines, and the window's jobs are cut by them
    roots = [r for r in records if r["cat"] == "stage"
             and "parent" not in r]
    assert len({r["pipe"] for r in roots}) == 4
    jobs = span_window.window_jobs(records, 3)
    assert jobs is not None and len(jobs) == 3
    assert span_window.window_jobs(records, 4) is None


# ----------------------------------------------------- the readers' ring

class Ring:
    def __init__(self):
        self.recs = []

    def add(self, cat, name, t0, dur_s, parent=None, **attrs):
        rec = {"event": "span", "cat": cat, "name": name,
               "span": len(self.recs) + 1, "t0_s": t0,
               "dur_us": int(round(dur_s * 1e6)), **attrs}
        if parent is not None:
            rec["parent"] = parent
        self.recs.append(rec)
        return rec["span"]


def made_up_ring(pulls=(3, 3, 3)):
    """A warm-up job and two more, one pipeline each. A job of
    ``pulls[k]`` pulls: all but the last are ``MinMax`` roots (a
    dispatch, a wait of 50 ms, a fetch of 1 ms), the last an
    ``AllGatherArrays`` root with a dispatch and no fetch. The next
    pull's first dispatch starts 3 ms after the fetch's end."""
    r = Ring()
    for (pipe, t0), n in zip(((0, 10.0), (40, 12.0), (80, 14.0)), pulls):
        t = t0
        for k in range(n):
            last = k == n - 1
            root = r.add("stage", "AllGatherArrays" if last else "MinMax",
                         t, 0.060, dia_id=pipe + k, pipe=pipe)
            inner = r.add("stage", "Sort", t + 0.001, 0.004, root,
                          dia_id=pipe + k, pipe=pipe)
            r.add("dispatch", "fused_Sort", t + 0.002, 0.002, inner)
            if not last:
                r.add("dispatch", "reduce_action", t + 0.005, 0.001, root)
                r.add("wait", "device", t + 0.006, 0.050, root)
                r.add("fetch", "fetch", t + 0.056, 0.001, root, bytes=4)
            t += 0.059          # the fetch ends at t + 0.057
    return r.recs


@pytest.mark.parametrize("stats,jobs,want", [
    ({"pulls": 24}, 3, 8.0),            # six rounds + 2, three jobs
    ({"pulls": 25}, 3, 25 / 3),         # jobs that differ show
    ({"pulls": 3}, 3, None),            # a pull a job says nothing
    ({"pulls": 0}, 3, None),            # the Tracer off
    ({}, 3, None),                      # a parent's program
    ({"pulls": 24}, 0, None)])
def test_pulls_per_job_is_the_counter_over_the_jobs(pulls_reader, stats,
                                                    jobs, want):
    assert pulls_reader.read({"stats": stats, "jobs": jobs}) == want


@pytest.mark.parametrize("window_s", [3.0, None],
                         ids=["device_trace", "no_device_trace"])
def test_the_gap_reader_sums_the_gaps_between_pulls(gap_reader, monkeypatch,
                                                    capsys, window_s):
    monkeypatch.setattr(gap_reader.span_window, "ring_records", made_up_ring)
    run = {"jobs": 2, "trace": window_s and {"window_s": window_s}}
    pulls = gap_reader.window_pulls(run)
    # without a device trace the window's last job is cut to its first
    # root, and left out
    assert [len(job) for job in pulls] == ([3, 3] if window_s else [3])
    assert [r["name"] for r in pulls[0][0] if r["cat"] == "stage"] == \
        ["MinMax", "Sort"]
    # two gaps a job: 0.059 + 0.002 - 0.057 = 4 ms each
    assert gap_reader.read(run) == pytest.approx(0.008, abs=1e-6)
    err = capsys.readouterr().err
    assert "replan gaps per job (s): 0.004000 0.004000" in err
    # a reader reads its own metric and says nothing of the others'
    assert "host phases per job" not in err


def test_a_job_of_one_pull_has_no_gap(gap_reader, monkeypatch):
    monkeypatch.setattr(gap_reader.span_window, "ring_records",
                        lambda: made_up_ring(pulls=(1, 1, 1)))
    assert gap_reader.read({"jobs": 2, "trace": {"window_s": 3.0}}) is None


def test_the_gap_reader_with_nothing_to_read_returns_none(gap_reader,
                                                          monkeypatch):
    for ring in (lambda: None, made_up_ring):
        monkeypatch.setattr(gap_reader.span_window, "ring_records", ring)
        jobs = 2 if ring() is None else 3       # too few pipelines
        assert gap_reader.read({"jobs": jobs, "trace": None}) is None
        assert gap_reader.read({"jobs": 0, "trace": None}) is None


def test_a_pull_that_ends_in_no_read_adds_no_gap(gap_reader):
    read = [{"cat": "fetch", "t0_s": 1.0, "dur_us": 1000}]
    silent = [{"cat": "dispatch", "t0_s": 1.004, "dur_us": 10}]
    later = [{"cat": "dispatch", "t0_s": 1.010, "dur_us": 10},
             {"cat": "wait", "t0_s": 1.011, "dur_us": 2000}]
    assert gap_reader.gaps([read, silent, later]) == \
        [pytest.approx(0.003)]
    assert gap_reader.gaps([read, [], later]) == []
    # overlapping spans never read below zero
    assert gap_reader.gaps([later, silent]) == [0.0]


# ------------------------------------------------------ the data files

def test_benchmark_json_names_the_cell_and_its_metrics_by_name(job):
    with open(os.path.join(_ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"] if w["name"] == "suffix.w1")
    assert (cell["config"], cell["chips"]) == ("suffixsort-zipftext", 1)
    assert cell["traffic"] == "closed_zipftext_2p22_rep160"
    entry = next(c for c in bench["configs"]
                 if c["name"] == "suffixsort-zipftext")
    assert entry["reduced"] == ["chars_per_job"]
    assert len(entry["source"]) <= 200 and len(cell["why"]) <= 200
    with open(os.path.join(_ROOT, entry["file"])) as f:
        config = json.load(f)
    assert config["job"] == "suffixsort"
    assert config["source"] == entry["source"]
    assert set(config["reduced"]) == set(entry["reduced"])
    shapes = config["shapes"]
    assert (shapes["index_bytes"], shapes["rank_bytes"],
            shapes["row_index_rank_bytes"],
            shapes["row_index_rank1_rank2_bytes"],
            shapes["initial_h"]) == (4, 4, 8, 12, 4)
    assert config["deployment"]["chars_per_chip"] == 1 << 26
    assert len(config["guarantees"]) >= 3 and config["assumed"]
    with open(os.path.join(_BENCH, "traffic",
                           cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    # the issue's parameters and no other
    assert traffic == {
        "loop": "closed", "clients": 1, "chars": 1 << 22, "words": 65536,
        "zipf": 1.1, "word_letters": "4-16", "planted_repeat_bytes": 160,
        "index_bytes": 4, "initial_h": 4, "rounds": 6, "traced_jobs": 3,
        "check": {"jobs": "all"}, "rehearse": {"chars": 4096}}
    # the plant lies in the band whose rounds the file states, and is
    # named among what was assumed
    assert 4 << (traffic["rounds"] - 1) < traffic["planted_repeat_bytes"] \
        < 4 << traffic["rounds"]
    assert "160" in config["assumed"]["planted_repeat"]
    assert job.records(traffic) == traffic["chars"]
    for name, unit, source in (
            ("pulls_per_job", "count", "program_counter"),
            ("replan_gap_s_per_job", "s", "program_span")):
        metric = next(m for m in bench["per_layer"] if m["name"] == name)
        assert {k: v for k, v in metric.items() if k != "workloads"} == {
            "name": name, "unit": unit, "better": "lower",
            "source": source, "layer": "DIA ops and fusion",
            "moves": "records_per_s"}
        assert "suffix.w1" in metric["workloads"]
        assert os.path.exists(os.path.join(_BENCH, "layer_metrics",
                                           name + ".py"))
    reported = {m["name"] for m in bench["per_layer"]
                if "suffix.w1" in m["workloads"]}
    assert reported >= set(EVERY_CELL) | {"pulls_per_job",
                                          "replan_gap_s_per_job"}
    # not a job of Iterate, of ReduceToIndex or of an exchange
    assert not reported & {"loop_host_s_per_job", "loop_captures_in_window",
                           "iterations_replayed_share",
                           "index_plans_per_job", "collective_share",
                           "exchange_bytes_per_job"}


def test_the_configuration_states_the_sizing_at_six_rounds():
    with open(os.path.join(_BENCH, "configs",
                           "suffixsort-zipftext.json")) as f:
        config = json.load(f)
    cut = config["reduced"]["chars_per_job"]
    for word in ("2^21", "2^22", "2^23", "peak_bytes_in_use", "six rounds"):
        assert word in cut
    assert sorted(config["assumed"]) == sorted((
        "text", "planted_repeat", "generator", "input_location",
        "output_location", "reference_tree", "sort_words", "class_sort_key",
        "padded_windows", "names", "flags_orientation"))


# ------------------------------------------------------- run.py end to end

@pytest.fixture
def rehearsal_env(monkeypatch):
    # what run.py sets for a rehearsal is restored afterwards: the worker
    # goes on to other tests
    for var in ("THRILL_TPU_HOST_RADIX", "THRILL_TPU_SORT_U32",
                "THRILL_TPU_PACK_MOVE"):
        monkeypatch.setenv(var, "")
        monkeypatch.delenv(var)
    monkeypatch.setattr(sys, "path", list(sys.path))


@pytest.mark.parametrize("trace", [0, 1])
def test_a_rehearsal_is_correct_and_reports_its_pulls(
        rehearsal_env, capsys, trace):
    run_py = _load(os.path.join(_BENCH, "run.py"),
                   f"chipbench_run_suffix{trace}")
    assert run_py.main(["--workload", "suffix.w1", "--seed",
                        str(2**31 + 36), "--seconds", "0.05", "--trace",
                        str(trace), "--rehearse"]) == 0
    captured = capsys.readouterr()
    last = json.loads(captured.out.strip().splitlines()[-1])
    assert last["correct"] is True and last["failed"] == 0
    for name in ALL_RIGHT:
        assert last["check"][name] == {"value": 0, "limit": 0}
    # what the text needed beside what the traffic file states
    assert last["check"]["rounds_needed"] == {"value": 6, "limit": 6}
    jobs = last["attempted"]
    assert last["check"]["jobs_compared"] == jobs
    # the text goes up once a job; every round and the names step read
    # one scalar back (so the result line tells the rounds); the suffix
    # array leaves through np.asarray
    assert last["counts"]["device_uploads"] == jobs
    assert last["counts"]["device_fetches"] == jobs * (6 + 1)
    assert last["counts"]["device_dispatches"] == jobs * (3 + 4 * 6 + 1)
    assert last["counts"]["oom_retries"] == 0
    assert last["counts"]["host_fallbacks"] == 0
    if not trace:
        assert "compiles or cache loads inside the window: 0" in captured.err
        return
    assert jobs == 3
    assert {"pulls_per_job", "replan_gap_s_per_job", "dispatches_per_job",
            "fetches_per_job", "compiles_in_window"} <= set(last["reported"])
    # two of the three jobs' seven gaps each (the last job is cut)
    gaps = next(line for line in captured.err.splitlines()
                if line.startswith("replan gaps per job (s): "))
    assert [len(job.split()) for job in
            gaps.split(": ")[1].split(" | ")] == [7, 7]


def test_a_rehearsal_with_the_control_reads_not_correct(rehearsal_env,
                                                        capsys):
    run_py = _load(os.path.join(_BENCH, "run.py"),
                   "chipbench_run_suffix_control")
    assert run_py.main(["--workload", "suffix.w1", "--seed", "14",
                        "--seconds", "0.05", "--rehearse",
                        "--control"]) == 0
    last = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert last["correct"] is False and last["failed"] == 0
    assert last["check"]["sa_not_permutation"]["value"] == 0
    assert last["check"]["rounds_differing"] == {"value": 1, "limit": 0}
    assert last["check"]["sa_rows_differing"]["value"] == 66
    assert last["check"]["sa_order_violations"]["value"] > 0
