"""Trace smoke: serve two instrumented segments, validate every artifact.

Runs the streaming spike serving engine for ~2 segments with the full
observability stack on — flight-recorder ring in the device carry,
Perfetto span tracing on the host threads, Prometheus metrics — writes
the run directory, then validates what CI's ``trace-smoke`` job promises:

* ``trace.json`` parses as Chrome Trace Event JSON, per-track timestamps
  are monotonic, and every engine thread (``spike-ingest``,
  ``spike-device``, ``device``) contributed at least one span;
* host spans correlate to device windows: every ``window`` instant's
  absolute window index also appears in the flight-recorder rows;
* ``metrics.prom`` parses as Prometheus text exposition;
* ``python -m repro.obs.report`` builds a structured report from the
  directory (timeline rows + tenant SLO blocks present).

Exits non-zero with a reason on any failure.  ``--artifact PATH`` copies
the validated trace to PATH — how ``docs/observability_trace.json`` (the
committed example trace) is produced.

Usage: python tools/trace_smoke.py [--out-dir DIR] [--artifact PATH]
"""
from __future__ import annotations

import os
import sys

# must precede the jax import: the engine needs >1 host device
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=4 "
                           + os.environ.get("XLA_FLAGS", ""))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import argparse
import json
import shutil

SEGMENTS = 2


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out-dir", default="/tmp/trace_smoke")
    ap.add_argument("--artifact", default=None,
                    help="copy the validated trace.json here (refreshes "
                         "docs/observability_trace.json)")
    args = ap.parse_args()

    import numpy as np
    import jax

    from repro.obs import metrics as obs_metrics
    from repro.obs import recorder as obs_recorder
    from repro.obs import report as obs_report
    from repro.obs import spans as obs_spans
    from repro.serve.loadgen import PoissonLoadGen, TenantProfile
    from repro.serve.spike_engine import EngineConfig, SpikeEngine
    from repro.serve.tenancy import TenantSpec

    from repro.launch.mesh import make_wafer_mesh
    mesh = make_wafer_mesh(4, "w")
    cfg = EngineConfig(capacity=8, link_credits=16, notify_latency=2,
                      window_us=100.0, seg_windows=3, nx=2, ny=2, nz=1)
    tenants = [TenantSpec("a", reserve=8, rate_epw=16.0),
               TenantSpec("b", reserve=4, rate_epw=8.0)]
    src = PoissonLoadGen(11, [TenantProfile("a", 16.0),
                              TenantProfile("b", 8.0)], 4, cfg.capacity)
    eng = SpikeEngine(mesh, "w", tenants, cfg, src,
                      recorder=obs_recorder.RecorderConfig(depth=32),
                      tracer=obs_spans.Tracer())
    eng.warmup()
    rep = eng.run(SEGMENTS)
    run_dir = obs_report.write_engine_run(args.out_dir, eng, rep)
    print(f"run dir: {run_dir} ({rep.windows} windows, "
          f"{int(rep.delivered.sum())} delivered)")

    failures: list[str] = []

    # -- trace.json: parses, monotonic, every engine thread present --------
    trace_path = os.path.join(run_dir, "trace.json")
    try:
        with open(trace_path) as f:
            trace = json.load(f)
    except (OSError, ValueError) as e:
        sys.exit(f"trace-smoke FAIL: trace.json unreadable: {e}")
    problems = obs_spans.validate_trace(trace)
    failures += [f"trace.json: {p}" for p in problems]
    names = obs_spans.thread_names(trace)
    spans_per_track: dict[str, int] = {}
    windows_in_trace: set[int] = set()
    for ev in trace["traceEvents"]:
        if ev.get("ph") in ("X", "i"):
            track = names.get(ev.get("tid", 0), "?")
            spans_per_track[track] = spans_per_track.get(track, 0) + 1
            if ev.get("name") == "window":
                windows_in_trace.add(int(ev["args"]["window"]))
    for track in ("spike-ingest", "spike-device", "device"):
        if spans_per_track.get(track, 0) < 1:
            failures.append(f"trace.json: no spans on thread {track!r} "
                            f"(have {spans_per_track})")

    # -- correlation: trace window indices exist in the recorder rows ------
    rec_windows = {int(r["window"])
                   for r in obs_report._read_jsonl(
                       os.path.join(run_dir, "recorder.jsonl"))}
    orphans = windows_in_trace - rec_windows
    if not windows_in_trace:
        failures.append("trace.json: no per-window device instants")
    if orphans:
        failures.append(f"correlation: trace windows {sorted(orphans)} "
                        f"missing from recorder.jsonl {sorted(rec_windows)}")

    # -- metrics.prom: valid Prometheus exposition -------------------------
    try:
        metrics = obs_metrics.parse_prometheus(
            open(os.path.join(run_dir, "metrics.prom")).read())
        if not metrics:
            failures.append("metrics.prom: empty exposition")
    except (OSError, ValueError) as e:
        failures.append(f"metrics.prom: {e}")

    # -- report: structured output builds ----------------------------------
    try:
        report = obs_report.build_report(run_dir)
        if not report["timeline"]:
            failures.append("report: empty window timeline")
        if not all("slo" in t for t in report["tenants"]):
            failures.append("report: tenant rows missing SLO block")
    except Exception as e:  # noqa: BLE001 - smoke gate, report any failure
        failures.append(f"report: build_report raised {e!r}")

    if failures:
        sys.exit("trace-smoke FAIL:\n  " + "\n  ".join(failures))

    if args.artifact:
        shutil.copyfile(trace_path, args.artifact)
        print(f"artifact: {args.artifact}")
    print(f"trace-smoke OK: {sum(spans_per_track.values())} events on "
          f"{len(spans_per_track)} tracks, {len(rec_windows)} recorded "
          f"windows, {len(metrics)} metric families")


if __name__ == "__main__":
    main()
