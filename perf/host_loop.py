"""What the host does while the chip sits idle: a cell's segment cycle,
read from the program's own spans.

    python perf/host_loop.py --workload <cell> --seed <n> --seconds <s>

Runs the cell's closed loop as ``harness.py`` does, with the simulator
built with an enabled ``repro.obs.spans.Tracer`` and each segment's
statistics read by ``run_segment.fetch_stats`` in place of
``jax.device_get``.  The simulator then records three spans a segment,
sharing its ``seg`` number: ``segment/dispatch`` (with ``compiles``, the
XLA compilations inside the call), ``segment/wait`` (the device still
running) and ``segment/fetch`` (the statistics copied).  A device trace
of the window's first segments, with those spans on its host plane,
gives:

    host_gap_ms     device idle from the end of one segment program to
                    the start of the next, mean over boundaries and chips
    host_gap_split_ms  what covers that idle: ms per boundary charged to
                    each innermost host span, or to none
    dispatch_ms     median ``segment/dispatch`` over the traced segments
    wait_ms         median ``segment/wait``
    stats_fetch_ms  median ``segment/fetch``
    longest_gaps    the ten longest gaps between segment programs, each
                    named by the innermost host span that covers most of it

The readings take the trace as ``trace.read_xplane`` gives it, with the
``segment/*`` host spans kept beside ``bench.*`` (``host_spans``), so
they can move into ``perf/trace.py`` as they are.  The tracer's spans go
to ``<trace dir>/spans.json``; standard error lists the five slowest
segments of the whole window with their split and compilations.  The
last line of standard output is one JSON object.  Without a TPU the run
exits non-zero.
"""
from __future__ import annotations

import argparse
import collections
import gc
import glob
import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))
sys.path.insert(0, os.path.dirname(HERE))

import numpy as np  # noqa: E402

from perf import generator, harness, trace  # noqa: E402

HOST_SPANS = ("bench.", "segment/")
UNNAMED = "host: none annotated"
STAGES = ("dispatch", "wait", "fetch")


# ---------------------------------------------------------------------------
# readings
# ---------------------------------------------------------------------------

def host_spans(trace_dir: str) -> list:
    """[name, start_ns, duration_ns] of the host's ``bench.*`` and
    ``segment/*`` spans in the newest profiler trace under ``trace_dir``:
    ``trace.read_xplane``'s host spans with ``segment/*`` kept too."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no trace under {trace_dir}")
    pd = ProfileData.from_file(max(paths, key=os.path.getmtime))
    return [[e.name, e.start_ns, e.duration_ns]
            for plane in pd.planes if plane.name.startswith("/host:CPU")
            for line in plane.lines for e in line.events
            if e.name.startswith(HOST_SPANS)]


def module_gaps(chips: dict) -> list:
    """[end, start) of the device idle between consecutive segment
    programs (``XLA Modules`` spans), every chip's boundaries."""
    return [(a1, b0) for c in chips.values()
            for (_, a1), (b0, _) in zip(c["modules"], c["modules"][1:])]


def host_gap_s(chips: dict) -> float | None:
    """Mean device idle between consecutive segment programs, over the
    boundaries of every chip."""
    gaps = [b - a for a, b in module_gaps(chips)]
    return sum(gaps) / len(gaps) * 1e-9 if gaps else None


def span_median_s(host: list, name: str) -> float | None:
    """Median duration of the host spans named ``name``."""
    durs = [d for n, _, d in host if n == name]
    return float(np.median(durs)) * 1e-9 if durs else None


def cover(host: list, a: float, b: float) -> collections.Counter:
    """How much of ``[a, b)`` each host span covers: the interval is cut
    at every span boundary and each piece is charged to the innermost
    span over it (the one that started last), as device time is charged
    to the innermost op; pieces no span covers go to ``UNNAMED``."""
    over = [(s, s + d, n) for n, s, d in host if s < b and s + d > a]
    cuts = sorted({a, b} | {t for s, e, _ in over for t in (s, e)
                            if a < t < b})
    got = collections.Counter()
    for lo, hi in zip(cuts, cuts[1:]):
        inner = [x for x in over if x[0] <= lo and x[1] >= hi]
        got[max(inner, key=lambda x: (x[0], -x[1]))[2] if inner
            else UNNAMED] += hi - lo
    return got


def name_gap(host: list, a: float, b: float) -> str:
    """The host span that covers most of ``[a, b)`` (see ``cover``)."""
    got = cover(host, a, b)
    got.pop(UNNAMED, None)
    return got.most_common(1)[0][0] if got else UNNAMED


def host_gap_split_s(chips: dict, host: list) -> dict:
    """What covers the idle between consecutive segment programs: per
    host span (or ``UNNAMED``), seconds per boundary, mean over the
    boundaries of every chip."""
    gaps = module_gaps(chips)
    got = collections.Counter()
    for a, b in gaps:
        got.update(cover(host, a, b))
    return {k: v * 1e-9 / len(gaps) for k, v in got.most_common()}


def longest_gaps(chips: dict, host: list, k: int = 10) -> list:
    """The ``k`` longest gaps between segment programs over all chips,
    [name, s], each named by ``name_gap``."""
    gaps = sorted(module_gaps(chips), key=lambda g: g[1] - g[0],
                  reverse=True)[:k]
    return [[name_gap(host, a, b), (b - a) * 1e-9] for a, b in gaps]


def segment_splits(events: list, since_us: float = 0.0) -> dict:
    """Per segment dispatched at or after ``since_us`` (tracer clock):
    its dispatch, wait and fetch in seconds and its compilations, from a
    tracer's Chrome-trace events."""
    segs = collections.defaultdict(dict)
    for e in events:
        stage = e["name"].partition("segment/")[2]
        if e["ph"] == "X" and stage in STAGES:
            seg = segs[e["args"]["seg"]]
            seg[stage] = e["dur"] * 1e-6
            if stage == "dispatch":
                seg["ts"] = e["ts"]
                seg["compiles"] = e["args"]["compiles"]
    return {k: v for k, v in segs.items() if v.get("ts", -1) >= since_us}


def slowest(splits: dict, k: int = 5) -> list:
    """The ``k`` segments whose dispatch, wait and fetch took longest."""
    total = lambda v: sum(v.get(s, 0.0) for s in STAGES)
    order = sorted(splits, key=lambda seg: -total(splits[seg]))
    return [{"seg": seg, "total_ms": total(splits[seg]) * 1e3,
             **{f"{s}_ms": splits[seg].get(s, 0.0) * 1e3 for s in STAGES},
             "compiles": splits[seg].get("compiles", 0)}
            for seg in order[:k]]


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

def build(cell: dict, devs, tracer):
    """``harness.build``'s program, with the simulator handed ``tracer``."""
    return harness.build(cell, devs, tracer=tracer)[1]


def cycle_loop(run_segment, carry, seconds: float, n_win: int,
               trace_dir: str, trace_segments: int):
    """``harness.timed_loop``'s closed loop, its ``bench.*`` spans kept,
    with the statistics read by ``run_segment.fetch_stats``: profiles
    the first ``trace_segments`` segments into ``trace_dir``, then runs
    on for ``seconds`` of segments, the time the profiler takes to stop
    left out.  Returns the carry, the segments run and (segments traced,
    seconds)."""
    import jax
    ann = jax.profiler.TraceAnnotation
    gc.collect()
    gc.disable()
    jax.profiler.start_trace(trace_dir)
    traced = None
    t0 = time.perf_counter()
    i = 0
    try:
        while time.perf_counter() - t0 < seconds:
            with ann("bench.dispatch"):
                carry, st = run_segment(carry, n_win)
            with ann("bench.wait_stats"):
                # rebinding frees the device statistics here, as the
                # harness's loop does
                st = run_segment.fetch_stats(st)
            i += 1
            if i == trace_segments:
                traced = (i, time.perf_counter() - t0)
                jax.profiler.stop_trace()
                # writing the trace takes seconds: the window runs on for
                # its full length after it, for the slow-segment listing
                t0 = time.perf_counter() - traced[1]
        if traced is None:
            traced = (i, time.perf_counter() - t0)
            jax.profiler.stop_trace()
    finally:
        gc.enable()
    return carry, i, traced


def run(workload: str, seed: int, seconds: float, root: str = harness.HERE,
        require_tpu: bool = True):
    """One traced-program run of one cell; returns the result dict."""
    from repro.obs import spans
    cell, devs = harness.open_cell(workload, root, require_tpu)
    mix = cell["traffic"]
    n_win = mix["segment_windows"]
    tracer = spans.Tracer(process_name=workload)
    init, run_segment, _ = build(cell, devs, tracer)
    carry = init(generator.program_seed(seed))
    for _ in range(mix["warmup_segments"]):
        carry, st = run_segment(carry, n_win)
        run_segment.fetch_stats(st)
    trace_dir = os.path.join(cell["cache"], "trace", cell["name"])
    shutil.rmtree(trace_dir, ignore_errors=True)
    since = tracer.now_us()
    carry, n_run, (n_seg, window_s) = cycle_loop(
        run_segment, carry, seconds, n_win, trace_dir, mix["trace_segments"])
    tracer.write(os.path.join(trace_dir, "spans.json"))
    splits = segment_splits(tracer.to_dict()["traceEvents"], since)
    module = run_segment.lower(carry, n_win).compile() \
        .runtime_executable().hlo_modules()[0].name
    tr = trace.read_xplane(trace_dir, module)
    tr["host"] = host_spans(trace_dir)
    chips, host = tr["chips"], tr["host"]
    busy_s = trace.reduce(tr, {}, n_seg * n_win, [])["busy_s"]
    readings = {"host_gap_ms": host_gap_s(chips),
                "dispatch_ms": span_median_s(host, "segment/dispatch"),
                "wait_ms": span_median_s(host, "segment/wait"),
                "stats_fetch_ms": span_median_s(host, "segment/fetch")}
    gap = readings["host_gap_ms"]
    dev = devs[0]
    return {"workload": workload,
            "device": {"platform": dev.platform, "kind": dev.device_kind,
                       "count": len(devs)},
            "segments": n_run,
            "compiles_in_window": sum(v.get("compiles", 0)
                                      for v in splits.values()),
            "slowest": slowest(splits),
            "metrics": {k: v * 1e3 for k, v in readings.items()
                        if v is not None},
            "traced_segments": n_seg, "window_s": window_s, "busy_s": busy_s,
            "host_gap_share_of_idle": (
                gap * n_seg / (window_s - busy_s)
                if gap is not None and window_s > busy_s else None),
            "host_gap_split_ms": {k: v * 1e3 for k, v in
                                  host_gap_split_s(chips, host).items()},
            "longest_gaps": longest_gaps(chips, host)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    try:
        out = run(args.workload, args.seed, args.seconds)
    except harness.NoChip as e:
        print(e, file=sys.stderr)
        return 2
    print(f"{out['segments']} segments, {out['compiles_in_window']} "
          "compilations in the window; slowest:", file=sys.stderr)
    for s in out["slowest"]:
        print(f"  seg {s['seg']}: {s['total_ms']:.3f} ms = dispatch "
              f"{s['dispatch_ms']:.3f} + wait {s['wait_ms']:.3f} + fetch "
              f"{s['fetch_ms']:.3f}, compiles {s['compiles']}",
              file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
