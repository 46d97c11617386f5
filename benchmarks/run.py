"""Benchmark harness — one module per paper table/claim.

Usage:  PYTHONPATH=src python -m benchmarks.run [--only <name>] [--smoke]
                                                [--out-dir DIR]

For stable numbers, source the environment tuning first::

    . tools/env.sh && PYTHONPATH=src python -m benchmarks.run

``tools/env.sh`` preloads tcmalloc when present, pins OpenMP threading,
silences TF/XLA logging and sets ``--xla_step_marker_location`` so
profiles attribute time per flush window; everything in it is gated and
append-only, so it is safe on any machine.  The harness reports whether
it was sourced (the ``REPRO_BENCH_ENV`` sentinel) in the CSV header.
Output: ``name,value,notes`` CSV rows on stdout, plus machine-readable
``BENCH_<group>.json`` files (one JSON list of
``{op, shape, median_ms, events_per_s, ..., provenance}`` rows per group,
currently ``kernels``, ``link``, ``transport``, ``wire``, ``serve`` and
``microcircuit``) so the perf trajectory across PRs can be diffed without
parsing the CSV.  Every row carries a ``provenance`` block (git SHA +
dirty flag, jax/jaxlib versions, device count/platform, whether
``tools/env.sh`` was sourced); ``tools/check_docs.py`` rejects committed
artifacts without one.  ``--trace`` additionally writes observability
run directories (``repro.obs``: flight-recorder rows, Perfetto trace,
Prometheus metrics) for the modules that support it.

Each module runs in a spawned process of its own, and this process never
initialises JAX: a module that needs N host devices starts its measuring
script through ``Reporter.run_script``, and on a TPU only one process may
hold the chip.

``--smoke`` runs a reduced module set with shrunk shapes — fast enough for
the tier-1 time budget while still producing all the JSON files.  Smoke
rows are stamped ``"smoke": true`` and must NEVER be committed: the
committed ``BENCH_*.json`` are full-shape numbers, and
``tools/check_docs.py`` fails CI if a smoke-stamped (or known
smoke-shaped) artifact lands in the repo root.  As a second belt,
``--smoke`` defaults ``--out-dir`` to ``/tmp/bench`` — a smoke run
executed from the repo root can no longer clobber the committed
artifacts unless the caller explicitly points it there.

Modules:
  bench_aggregation  paper §3.1 throughput claims (the central table)
  bench_link         paper §1 link budget / wafer torus loads
  bench_ringbuffer   paper §2.1 credit flow-control sizing
  bench_renaming     paper §3.1 bucket renaming pressure
  bench_microcircuit paper §4 target workload: the cortical microcircuit
                     on a credit-throttled 2x2x2 wafer torus under a
                     fault matrix (no-fault / link down / link flap /
                     node down) — bio-real-time slowdown, delivery ratio
                     and p99 degradation per fault case
  bench_moe_dispatch beyond-paper: bucket dispatch as MoE EP
  bench_kernels      Pallas kernel cost models
  bench_transport    alltoall vs torus2d vs torus3d flush-window backends
                     head-to-head (8 forced host devices in a subprocess;
                     rows carry backend, mesh shape, credit_stalls and the
                     hop-by-hop stall breakdown)
  bench_wire         extoll vs ethernet wire profiles on every backend:
                     frame-exact bytes_on_wire, wire efficiency and
                     latency percentiles (+ codec round-trip row)
  bench_serve        streaming multi-tenant serving engine under open-loop
                     Poisson load: sustained events/s, per-tenant latency
                     digests and the quiet-tenant p99 QoS isolation row
"""
from __future__ import annotations

import argparse
import json
import multiprocessing
import os
import subprocess
import sys
import time
from concurrent.futures import ProcessPoolExecutor

from repro.obs import log as obs_log

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# appended to every measuring script: the process that measured reports
# what it ran on
_PROVENANCE_TAIL = """
from benchmarks.run import provenance as _provenance
print("BENCH_PROVENANCE " + json.dumps(_provenance()))
"""

MODULES = [
    "bench_aggregation",
    "bench_link",
    "bench_ringbuffer",
    "bench_renaming",
    "bench_microcircuit",
    "bench_moe_dispatch",
    "bench_kernels",
    "bench_transport",
    "bench_wire",
    "bench_serve",
]

SMOKE_MODULES = ["bench_aggregation", "bench_link", "bench_kernels",
                 "bench_transport", "bench_wire", "bench_serve",
                 "bench_microcircuit"]


def median_ms(fn, *args, iters: int = 15) -> float:
    """Median wall-clock of ``fn(*args)`` in ms (one warmup, then iters)."""
    import jax
    jax.tree_util.tree_leaves(fn(*args))[0].block_until_ready()
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        out = fn(*args)
        jax.tree_util.tree_leaves(out)[0].block_until_ready()
        times.append(time.perf_counter() - t0)
    times.sort()
    return times[len(times) // 2] * 1e3


def provenance() -> dict:
    """The provenance block stamped into every BENCH_*.json row: enough
    to answer "what produced this number" when diffing the committed perf
    trajectory across PRs (``tools/check_docs.py`` rejects committed rows
    missing it).  Called in the process that measured, after it
    measured: calling it initialises a JAX backend."""

    def git(*args: str) -> str:
        try:
            out = subprocess.run(["git", *args], capture_output=True,
                                 text=True, cwd=ROOT, timeout=10)
            return out.stdout.strip() if out.returncode == 0 else ""
        except OSError:
            return ""

    import jax
    import jaxlib
    return {
        "git_sha": git("rev-parse", "--short=12", "HEAD") or "unknown",
        "git_dirty": bool(git("status", "--porcelain")),
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "devices": jax.device_count(),
        "platform": jax.default_backend(),
        "env_tuned": os.environ.get("REPRO_BENCH_ENV", "0") != "0",
    }


class Reporter:
    """CSV reporter (the historical ``report(name, value, notes)`` callable)
    plus a structured ``bench()`` collector feeding BENCH_<group>.json.
    Modules consult ``.smoke`` to shrink their workload and ``.trace_dir``
    (non-None when ``--trace`` is set) to write observability run
    directories next to the JSON artifacts."""

    def __init__(self, smoke: bool = False, trace_dir: str | None = None):
        self.smoke = smoke
        self.trace_dir = trace_dir
        self.provenance: dict | None = None    # set by the first row
        self.groups: dict[str, list[dict]] = {}

    def __call__(self, name, value, notes=""):
        print(f"{name},{value},{notes}")
        sys.stdout.flush()

    def bench(self, group: str, op: str, shape: str, med_ms: float,
              events_per_s: float | None = None, notes: str = "",
              extra: dict | None = None):
        row = {"op": op, "shape": shape, "median_ms": round(med_ms, 6)}
        if self.smoke:
            row["smoke"] = True     # tools/check_docs.py refuses these
        if events_per_s is not None:
            row["events_per_s"] = round(events_per_s)
        if notes:
            row["notes"] = notes
        if extra:
            row.update(extra)
        if self.provenance is None:
            self.provenance = provenance()
        row["provenance"] = self.provenance
        self.groups.setdefault(group, []).append(row)
        note = f"{row.get('events_per_s', '')} ev/s {notes}".strip()
        self(f"{group}/{op}/{shape}/median_ms", round(med_ms, 4), note)

    def run_script(self, script: str, params: dict, timeout: int
                   ) -> list[dict]:
        """Run a module's measuring ``script`` (which prints its rows as
        one ``BENCH_JSON`` line) in a process of its own, with ``params``
        as its argument; its provenance then stamps this module's rows."""
        env = dict(os.environ)
        env["PYTHONPATH"] = os.pathsep.join(
            [os.path.join(ROOT, "src"), ROOT, env.get("PYTHONPATH", "")])
        out = subprocess.run(
            [sys.executable, "-c", script + _PROVENANCE_TAIL,
             json.dumps(params)],
            capture_output=True, text=True, timeout=timeout, env=env)
        if out.returncode != 0:
            raise RuntimeError(f"measuring script failed:\n{out.stdout}\n"
                               f"{out.stderr}")
        tagged = {}
        for line in out.stdout.splitlines():
            tag, _, rest = line.partition(" ")
            if tag in ("BENCH_JSON", "BENCH_PROVENANCE"):
                tagged[tag] = json.loads(rest)
        self.provenance = tagged["BENCH_PROVENANCE"]
        return tagged["BENCH_JSON"]


def dump(groups: dict[str, list[dict]], out_dir: str):
    log = obs_log.get_logger(__name__)
    os.makedirs(out_dir, exist_ok=True)
    for group, rows in groups.items():
        path = os.path.join(out_dir, f"BENCH_{group}.json")
        with open(path, "w") as f:
            json.dump(rows, f, indent=1)
            f.write("\n")
        log.info("wrote %s (%d rows)", path, len(rows))


def _run_module(mod_name: str, smoke: bool, trace_dir: str | None,
                quiet: bool, verbose: bool) -> dict[str, list[dict]]:
    """One module, in a process of its own: the harness's own process
    never initialises JAX, so a module may start JAX processes."""
    obs_log.setup_logging("INFO", quiet=quiet, verbose=verbose)
    report = Reporter(smoke=smoke, trace_dir=trace_dir)
    mod = __import__(f"benchmarks.{mod_name}", fromlist=["main"])
    t0 = time.perf_counter()
    mod.main(report)
    report(f"{mod_name}/_wall_s", round(time.perf_counter() - t0, 1))
    return report.groups


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    ap.add_argument("--smoke", action="store_true",
                    help="fast reduced run (tier-1 time budget)")
    ap.add_argument("--out-dir", default=None,
                    help="directory for BENCH_<group>.json files "
                         "(default: repo root for full runs, /tmp/bench "
                         "for --smoke so toy numbers can never clobber "
                         "the committed full-shape artifacts)")
    ap.add_argument("--trace", action="store_true",
                    help="write observability run directories (flight-"
                         "recorder rows, Perfetto trace, metrics) next to "
                         "the JSON artifacts for modules that support it")
    obs_log.add_log_args(ap)
    args = ap.parse_args()
    if args.out_dir is None:
        args.out_dir = "/tmp/bench" if args.smoke else "."
    # progress lines (module wall times, artifact writes) default to INFO
    # on stderr; stdout carries only the CSV / BENCH_JSON protocols
    obs_log.setup_logging("INFO", quiet=args.quiet, verbose=args.verbose)

    trace_dir = args.out_dir if args.trace else None
    modules = SMOKE_MODULES if args.smoke else MODULES

    print("name,value,notes")
    print("env/tuned,%d,1 when tools/env.sh was sourced (tcmalloc, OMP "
          "pinning, XLA step markers)"
          % (os.environ.get("REPRO_BENCH_ENV", "0") != "0"), flush=True)
    groups: dict[str, list[dict]] = {}
    spawn = multiprocessing.get_context("spawn")
    for mod_name in modules:
        if args.only and args.only not in mod_name:
            continue
        with ProcessPoolExecutor(1, mp_context=spawn) as pool:
            got = pool.submit(_run_module, mod_name, args.smoke, trace_dir,
                              args.quiet, args.verbose).result()
        for group, rows in got.items():
            groups.setdefault(group, []).extend(rows)
    dump(groups, args.out_dir)


if __name__ == "__main__":
    main()
