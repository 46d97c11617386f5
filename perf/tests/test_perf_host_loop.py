"""The host's segment cycle: the simulator's ``segment/*`` spans and
counters on a tiny cell, the readings of ``host_loop.py`` on synthetic
traces, and the existing per-layer metrics of the recorded chip trace
pinned to the digits.  Runs on the CPU; loads no TPU library."""
import json
import os
import sys

import jax
import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
PERF = os.path.dirname(HERE)
REPO = os.path.dirname(PERF)
sys.path[:0] = [REPO, os.path.join(REPO, "src"), os.path.join(REPO, "tests")]

from perf import deploy, generator, host_loop, trace  # noqa: E402
from perf.tests import tinycell  # noqa: E402
from repro.obs import spans as obs_spans  # noqa: E402

DATA = os.path.join(HERE, "data", "trace_mc021_1node.json")
SEED = 2**31 + 4242


# -- the existing metrics of the recorded trace ------------------------------

# per-window device ms of each layer-time metric (the roofline shares'
# denominators for route + aggregate and the codec), as read before the
# segment spans existed
PINNED_MS = {"apply_ms": 2.0550627500000007, "lif_ms": 1.3199763749999813,
             "compaction_ms": 0.2406188750000001,
             "transport_ms": 0.0004088750000000001,
             "other_device_ms": 1.132017500000027,
             "route_aggregate_roofline": 0.022869374999999977,
             "codec_roofline": 0.0004926250000000001,
             "apply_roofline": 2.0550627500000007}


@pytest.fixture(scope="module")
def recorded_reduction():
    with open(DATA) as f:
        rec = json.load(f)
    defs = {}
    for p in sorted(os.listdir(os.path.join(PERF, "metrics"))):
        if p.endswith(".json"):
            with open(os.path.join(PERF, "metrics", p)) as f:
                d = json.load(f)
            defs[d["name"]] = d
    charges = trace.charge_ops(rec["stacks"],
                               trace.layer_rules(defs.values()))
    layers = sorted({d["layer"] for d in defs.values() if d["charges"]})
    return defs, rec, trace.reduce(rec["trace"], charges, rec["n_windows"],
                                   layers)


@pytest.mark.parametrize("name", sorted(PINNED_MS))
def test_existing_metric_reads_as_before(recorded_reduction, name):
    defs, rec, red = recorded_reduction
    d = defs[name]
    layer = (trace.OTHER if d["reduce"] == "catch_all_ms_per_window"
             else d["layer"])
    assert red["layer_s"][layer] / rec["n_windows"] * 1e3 == PINNED_MS[name]
    assert red["busy_s"] == 0.038171571


# -- readings on synthetic traces --------------------------------------------

def test_host_gap_is_the_mean_idle_between_segment_programs():
    chips = {"0": {"modules": [[0, 10], [15, 25], [27, 30]]},
             "1": {"modules": [[0, 10], [13, 20]]}}
    assert host_loop.host_gap_s(chips) == pytest.approx((5 + 2 + 3) / 3e9)
    assert host_loop.host_gap_s({"0": {"modules": [[0, 10]]}}) is None


def test_span_median_over_the_named_spans():
    host = [["segment/dispatch", 0, 3], ["segment/fetch", 5, 100],
            ["segment/dispatch", 200, 1], ["segment/dispatch", 300, 2],
            ["segment/dispatch", 400, 10]]
    assert host_loop.span_median_s(host, "segment/dispatch") == 2.5e-9
    assert host_loop.span_median_s(host, "segment/wait") is None


# one segment cycle on the host: the harness's spans around the program's
# (listed outer-first, as a profiler lists a thread's nested events)
CYCLE = [["bench.wait_stats", 0, 100], ["segment/wait", 0, 40],
         ["segment/fetch", 40, 55], ["bench.dispatch", 105, 35],
         ["segment/dispatch", 106, 33]]


def test_gap_is_named_by_the_innermost_span_covering_most_of_it():
    # device idle from 30 to 130: segment/wait 10, segment/fetch 55,
    # bench.wait_stats alone 5, nothing 5, bench.dispatch alone 1,
    # segment/dispatch 24
    assert host_loop.name_gap(CYCLE, 30, 130) == "segment/fetch"
    # a shorter fetch and wait_stats: the next dispatch covers most of it
    short = [["bench.wait_stats", 0, 50], ["segment/wait", 0, 40],
             ["segment/fetch", 40, 10]] + CYCLE[3:]
    assert host_loop.name_gap(short, 30, 130) == "segment/dispatch"
    assert host_loop.name_gap(CYCLE, 100, 105) == host_loop.UNNAMED
    assert host_loop.name_gap([], 0, 10) == host_loop.UNNAMED
    # the name does not hang on the order the spans are listed in, as a
    # name from the last span over the gap's midpoint does
    assert host_loop.name_gap(CYCLE[::-1], 30, 130) == "segment/fetch"
    chips = {"0": {"ops": [["a", 0, 30], ["b", 130, 10]], "modules": []}}
    by_order = [trace.reduce({"chips": chips, "host": h}, {}, 1, [])
                ["idle_gaps"][0][0] for h in (CYCLE, CYCLE[::-1])]
    assert by_order == ["segment/fetch", "bench.wait_stats"]


def test_host_gap_split_charges_the_innermost_spans():
    chips = {"0": {"modules": [[0, 30], [130, 170]]},
             "1": {"modules": [[0, 20], [125, 170]]}}
    split = host_loop.host_gap_split_s(chips, CYCLE)
    # boundaries [30, 130) and [20, 125)
    assert split == {"segment/fetch": pytest.approx(55e-9),
                     "segment/dispatch": pytest.approx(21.5e-9),
                     "segment/wait": pytest.approx(15e-9),
                     "bench.wait_stats": pytest.approx(5e-9),
                     host_loop.UNNAMED: pytest.approx(5e-9),
                     "bench.dispatch": pytest.approx(1e-9)}
    assert sum(split.values()) == pytest.approx(
        host_loop.host_gap_s(chips))
    assert host_loop.host_gap_split_s({"0": {"modules": [[0, 1]]}},
                                      CYCLE) == {}


def test_idle_gaps_longest_first_over_chips():
    chips = {"0": {"modules": [[0, 30], [130, 140], [145, 150]]},
             "1": {"modules": [[0, 20], [125, 135]]}}
    got = host_loop.longest_gaps(chips, CYCLE, k=2)
    assert got == [["segment/fetch", pytest.approx(105e-9)],
                   ["segment/fetch", pytest.approx(100e-9)]]
    assert len(host_loop.longest_gaps(chips, CYCLE)) == 3
    assert host_loop.longest_gaps(chips, [], k=1) == \
        [[host_loop.UNNAMED, pytest.approx(105e-9)]]


def test_slowest_segments_of_the_window():
    tr = obs_spans.Tracer()
    for seg, (d, w, f, c) in enumerate([(1, 5, 2, 1), (1, 3, 2, 0),
                                        (2, 9, 2, 0), (1, 4, 2, 0)]):
        t = 100.0 * seg
        tr.complete("segment/dispatch", t, d * 1e3, cat="host", seg=seg,
                    n_windows=8, compiles=c)
        tr.complete("segment/wait", t + 10, w * 1e3, cat="host", seg=seg)
        tr.complete("segment/fetch", t + 20, f * 1e3, cat="host", seg=seg,
                    arrays=3, bytes=96)
    splits = host_loop.segment_splits(tr.to_dict()["traceEvents"],
                                      since_us=100.0)
    assert sorted(splits) == [1, 2, 3]
    top = host_loop.slowest(splits, k=2)
    assert [s["seg"] for s in top] == [2, 3]
    assert top[0] == {"seg": 2, "total_ms": pytest.approx(13.0),
                      "dispatch_ms": pytest.approx(2.0),
                      "wait_ms": pytest.approx(9.0),
                      "fetch_ms": pytest.approx(2.0), "compiles": 0}


def test_host_spans_read_from_a_profiler_trace(tmp_path):
    tr = obs_spans.Tracer()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("bench.wait_stats"):
            with tr.span("segment/fetch", seg=0):
                np.asarray(jax.numpy.ones(8).sum())
        with jax.profiler.TraceAnnotation("unrelated"):
            pass
    finally:
        jax.profiler.stop_trace()
    host = host_loop.host_spans(str(tmp_path))
    names = sorted(h[0] for h in host)
    assert names == ["bench.wait_stats", "segment/fetch"]
    outer, inner = sorted(host, key=lambda h: h[1])
    assert outer[1] <= inner[1] and \
        inner[1] + inner[2] <= outer[1] + outer[2]


# -- the program's spans on a tiny cell --------------------------------------

@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tinycell.make(tmp_path_factory.mktemp("bench"))


def _program(root, tracer):
    cell = deploy.load_cell("tiny1.ground", root)
    n_win = cell["traffic"]["segment_windows"]
    return cell, n_win, host_loop.build(cell, jax.devices()[:1], tracer)


@pytest.fixture(scope="module")
def traced_cycle(root):
    tracer = obs_spans.Tracer()
    _, n_win, (init, run_segment, finish) = _program(root, tracer)
    carry = init(7)
    fetched = []
    for _ in range(3):
        carry, st = run_segment(carry, n_win)
        fetched.append((st, run_segment.fetch_stats(st)))
    finish(carry)
    events = [e for e in tracer.to_dict()["traceEvents"] if e["ph"] == "X"]
    return n_win, fetched, events


def test_segment_cycle_spans_share_a_seg(traced_cycle):
    n_win, _, events = traced_cycle
    assert [e["name"] for e in events] == \
        ["segment/dispatch", "segment/wait", "segment/fetch"] * 3
    assert [e["args"]["seg"] for e in events] == [0] * 3 + [1] * 3 + [2] * 3
    assert all(e["args"]["n_windows"] == n_win for e in events[::3])
    assert obs_spans.validate_trace({"traceEvents": events}) == []


def test_fetch_counts_buffers_and_bytes(traced_cycle):
    _, fetched, events = traced_cycle
    fetches = events[2::3]
    # the first fetch of a segment length counts what it copies; the
    # others copy the same and count nothing
    leaves = jax.tree_util.tree_leaves(fetched[0][0])
    assert fetches[0]["args"] == {
        "seg": 0, "arrays": len(leaves) * len(jax.devices()[:1]),
        "bytes": sum(np.asarray(x).nbytes for x in leaves)}
    assert [e["args"] for e in fetches[1:]] == [{"seg": 1}, {"seg": 2}]
    for st, host in fetched:
        for a, b in zip(jax.tree_util.tree_leaves(host),
                        jax.tree_util.tree_leaves(st)):
            assert isinstance(a, np.ndarray)
            np.testing.assert_array_equal(a, np.asarray(b))


def test_fetch_names_the_segment_it_was_given(root):
    # dispatch the next segment before fetching this one: each fetch
    # still carries the number of the segment its statistics came from
    tracer = obs_spans.Tracer()
    _, n_win, (init, run_segment, _) = _program(root, tracer)
    carry, st0 = run_segment(init(9), n_win)
    carry, st1 = run_segment(carry, n_win)
    run_segment.fetch_stats(st0)
    carry, st2 = run_segment(carry, n_win)
    run_segment.fetch_stats(st1)
    run_segment.fetch_stats(st2)
    got = [(e["name"], e["args"]["seg"])
           for e in tracer.to_dict()["traceEvents"] if e["ph"] == "X"]
    assert got == [("segment/dispatch", 0), ("segment/dispatch", 1),
                   ("segment/wait", 0), ("segment/fetch", 0),
                   ("segment/dispatch", 2),
                   ("segment/wait", 1), ("segment/fetch", 1),
                   ("segment/wait", 2), ("segment/fetch", 2)]
    # statistics the tracer never saw dispatched carry no number
    run_segment.fetch_stats(st2)
    assert tracer.to_dict()["traceEvents"][-1]["args"] == {"seg": None}


def test_compiles_counted_inside_the_dispatch(root):
    tracer = obs_spans.Tracer()
    _, _, (init, run_segment, _) = _program(root, tracer)
    carry = init(3)
    # window counts no other test compiles
    for n in (5, 5, 6, 5):
        carry, st = run_segment(carry, n)
        run_segment.fetch_stats(st)
    events = tracer.to_dict()["traceEvents"]
    got = [e["args"]["compiles"] for e in events
           if e["name"] == "segment/dispatch"]
    assert got[0] >= 1 and got[1] == 0 and got[2] >= 1 and got[3] == 0
    # each segment length's first fetch counts its buffers
    assert [("bytes" in e["args"]) for e in events
            if e["name"] == "segment/fetch"] == [True, False, True, False]


def test_compile_counter_counts_this_thread_inside_the_block():
    import threading
    from repro.snn import simulator as sim

    def compile_once(k):
        jax.jit(lambda x: x * k + 1.5).lower(
            jax.numpy.ones(3 + k)).compile()

    with sim.counting_compiles() as n:
        other = threading.Thread(target=compile_once, args=(1,))
        other.start()
        other.join()
        assert n[0] == 0
        compile_once(2)
        inside = n[0]
    compile_once(3)
    assert inside >= 1 and n[0] == inside


def test_tracer_leaves_the_lowered_segment_unchanged(root):
    _, n_win, (init, off, _) = _program(root, None)
    _, _, (_, on, _) = _program(root, obs_spans.Tracer())
    carry = init(0)
    assert off.lower(carry, n_win).as_text() == \
        on.lower(carry, n_win).as_text()


def test_disabled_cycle_records_nothing(root):
    before = len(obs_spans.NULL.to_dict()["traceEvents"])
    _, n_win, (init, run_segment, _) = _program(root, None)
    carry, st = run_segment(init(5), n_win)
    host = run_segment.fetch_stats(st)
    want = jax.device_get(st)
    for a, b in zip(jax.tree_util.tree_leaves(host),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_array_equal(a, b)
    assert len(obs_spans.NULL.to_dict()["traceEvents"]) == before


def test_tool_runs_the_harness_loop(root, tmp_path):
    tracer = obs_spans.Tracer()
    cell, n_win, (init, run_segment, _) = _program(root, tracer)
    carry, st = run_segment(init(generator.program_seed(SEED)), n_win)
    run_segment.fetch_stats(st)
    since = tracer.now_us()
    carry, n_run, (n_seg, window_s) = host_loop.cycle_loop(
        run_segment, carry, 0.3, n_win, str(tmp_path), 2)
    assert n_run >= 1 and n_seg == min(2, n_run) and window_s > 0
    splits = host_loop.segment_splits(tracer.to_dict()["traceEvents"],
                                      since)
    assert sorted(splits) == list(range(1, n_run + 1))
    top = host_loop.slowest(splits)
    assert 1 <= len(top) <= min(5, n_run)
    assert all(s["fetch_ms"] > 0 and s["compiles"] == 0 for s in top)
    # the traced segments' spans, on the profiler's host plane, nest as
    # the readings expect: the program's inside the loop's
    host = sorted(host_loop.host_spans(str(tmp_path)),
                  key=lambda h: (h[1], -h[2]))
    assert [h[0] for h in host] == n_seg * [
        "bench.dispatch", "segment/dispatch", "bench.wait_stats",
        "segment/wait", "segment/fetch"]
    for outer, inner in [(0, 1), (2, 3), (2, 4)]:
        o, i = host[outer], host[inner]
        assert o[1] <= i[1] and i[1] + i[2] <= o[1] + o[2]


def test_fetch_counts_every_shard(tmp_path):
    from md_helper import run_md
    root = tinycell.make(tmp_path, nodes=4)
    out = run_md(f"""
import sys, jax
sys.path[:0] = [{REPO!r}]
from perf import deploy, host_loop
from repro.obs import spans
cell = deploy.load_cell("tiny4.ground", {root!r})
tr = spans.Tracer()
init, run_segment, _ = host_loop.build(cell, jax.devices()[:4], tr)
carry, st = run_segment(init(1), 2)
run_segment.fetch_stats(st)
leaves = jax.tree_util.tree_leaves(st)
fetch = [e for e in tr.to_dict()["traceEvents"]
         if e["name"] == "segment/fetch"][0]
assert fetch["args"]["arrays"] == 4 * len(leaves), fetch
assert fetch["args"]["bytes"] == sum(x.nbytes for x in leaves), fetch
print("SHARDS_OK", fetch["args"])
""", n_devices=4)
    assert "SHARDS_OK" in out
