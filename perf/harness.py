"""The chip benchmark of the spike fabric: one cell, one run.

    python perf/harness.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell is a deployment (``configs/``) under a traffic mix (``traffic/``),
named by ``workloads/<cell>.json``.  The run builds the deployment's
network (cached under ``.cache/`` per checkout), builds the simulator
with ``simulator.build_sharded_segments`` on the cell's chips, handed
the inputs of the drive the mix names (``drives/``, see ``deploy``),
starts from ``init(seed)``, warms the segment shape up, and then runs a
closed loop for ``--seconds``: dispatch one segment, wait until its
window statistics are on the host, repeat.  After the window it reads peak
device memory, frees the program and compares sampled segments with the
plain reference (``reference/``), which decides ``correct``.

``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics, read from a device trace of the first segments of the
window (``trace.py``).  The last line of standard output is one JSON
object; the checks, each number beside its limit, are the last lines of
standard error.  Without a TPU, or with fewer chips than the cell asks
for, the run exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse
import contextlib
import gc
import importlib.util
import json
import os
import random
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))
sys.path.insert(0, os.path.dirname(HERE))

import numpy as np  # noqa: E402

from perf import deploy, generator, trace as ptrace  # noqa: E402
from perf.reference import check, segment  # noqa: E402

STEP_S = 1e-4            # one simulation step is 0.1 ms of biological time


class NoChip(RuntimeError):
    pass


def _compile_cache(jax):
    """``JAX_COMPILATION_CACHE_DIR`` when set, else ``.cache/jax`` here:
    a fixed path, so a later run of the checkout finds what this one
    compiled."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(deploy.CACHE, "jax"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def _devices(jax, chips: int, require_tpu: bool):
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devs[0].platform}")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return devs[:chips]


def drive(cell: dict):
    """The module of the drive the cell's mix names (``drives/``); a mix
    that names none is driven by ``background``."""
    return _load_reader(cell["root"], "drives",
                        cell["traffic"].get("drive", "background"))


def build(cell: dict, devs, tracer=None):
    """The timed path as a user builds it: the partition and (init,
    run_segment, finish), the simulator handed the drive's inputs and
    ``tracer``."""
    from repro.launch.mesh import make_wafer_mesh
    from repro.snn import simulator as sim
    drv = drive(cell)
    cfg = cell["config"]
    part = deploy.partition(cfg, cell["cache"])
    sc = deploy.sim_config(cfg, part)
    mesh = make_wafer_mesh(len(devs), devices=devs)
    return part, sim.build_sharded_segments(
        mesh, "wafer", sc, part, tracer=tracer,
        **drv.program_inputs(cell, part))


def timed_loop(jax, run_segment, carry, seconds: float, n_win: int,
               n_samples: int, rng: random.Random, trace_dir=None,
               trace_segments: int = 0):
    """Closed loop for ``seconds``: dispatch a segment, wait for its
    statistics on the host, keep a seeded uniform sample of segments
    (state before, state after, statistics) for the checks."""
    ann = (jax.profiler.TraceAnnotation if trace_dir
           else lambda name: contextlib.nullcontext())
    lat, stats, samples = [], [], []
    traced = None
    # the collector's full passes over a JAX process's heap would land
    # in random segments of the window; the loop allocates little
    gc.collect()
    gc.disable()
    if trace_dir:
        jax.profiler.start_trace(trace_dir)
    t0 = time.perf_counter()
    i = 0
    while time.perf_counter() - t0 < seconds:
        a = time.perf_counter()
        with ann("bench.dispatch"):
            out, st = run_segment(carry, n_win)
        with ann("bench.wait_stats"):
            st = jax.device_get(st)
        lat.append(time.perf_counter() - a)
        stats.append(st)
        if len(samples) < n_samples:
            samples.append((i, carry, out, st))
        else:
            r = rng.randrange(i + 1)
            if r < n_samples:
                samples[r] = (i, carry, out, st)
        carry = out
        i += 1
        if trace_dir and i == trace_segments:
            traced = (i, time.perf_counter() - t0)
            jax.profiler.stop_trace()
    if trace_dir and traced is None:
        traced = (i, time.perf_counter() - t0)
        jax.profiler.stop_trace()
    window = time.perf_counter() - t0
    gc.enable()
    return carry, lat, stats, samples, window, traced


def _sum(stats, get) -> int:
    return int(sum(np.asarray(get(s)).sum() for s in stats))


def open_cell(workload: str, root: str, require_tpu: bool):
    """The cell's files and the chips it runs on.  A drive that is not
    there fails here, before any device is touched."""
    cell = deploy.load_cell(workload, root)
    drive(cell)
    import jax
    devs = _devices(jax, cell["workload"]["chips"], require_tpu)
    if require_tpu:
        _compile_cache(jax)
    return cell, devs


def measure(cell: dict, program, seed: int, seconds: float, trace: bool,
            t_start: float):
    """Start the program from ``seed``, warm it up, run the timed window;
    everything after the window that the checks and metrics read."""
    import jax
    mix = cell["traffic"]
    n_win = mix["segment_windows"]
    init, run_segment, _ = program
    carry = init(generator.program_seed(seed))
    for _ in range(mix["warmup_segments"]):
        carry, st = run_segment(carry, n_win)
        jax.device_get(st)
    setup_s = time.perf_counter() - t_start
    trace_dir = None
    if trace:
        trace_dir = os.path.join(cell["cache"], "trace", cell["name"])
        shutil.rmtree(trace_dir, ignore_errors=True)
    carry, lat, stats, samples, window, traced = timed_loop(
        jax, run_segment, carry, seconds, n_win, mix["sampled_segments"],
        random.Random(seed), trace_dir, mix["trace_segments"])
    return {"carry": carry, "lat": lat, "stats": stats, "window": window,
            "samples": [(i, deploy.plain_state(a), deploy.plain_state(b),
                         deploy.plain_stats(s)) for i, a, b, s in samples],
            "traced": traced, "trace_dir": trace_dir, "setup_s": setup_s}


def run(workload: str, seed: int, seconds: float, trace: bool,
        root: str = HERE, require_tpu: bool = True, t_start=None):
    """One run of one cell; returns (result, checks)."""
    t_start = T_START if t_start is None else t_start
    cell, devs = open_cell(workload, root, require_tpu)
    e2e, layer_metrics = deploy.cell_metrics(cell, deploy.manifest(root))
    wl, cfg, mix = cell["workload"], cell["config"], cell["traffic"]
    n_win = mix["segment_windows"]
    t_jax = time.perf_counter()
    part, program = build(cell, devs)
    t_built = time.perf_counter()
    m = measure(cell, program, seed, seconds, trace, t_start)

    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devs)
    init, run_segment, finish = program
    module = None
    if trace:
        cell["network_counts"] = network_counts(part)
        module = run_segment.lower(m["carry"], n_win).compile() \
            .runtime_executable().hlo_modules()[0]
        module = (module.name, module.as_serialized_hlo_module_proto())
    _, drain_miss = finish(m.pop("carry"))
    drain_miss = int(np.asarray(drain_miss).sum())
    del program, init, run_segment, finish
    gc.collect()

    precision = cfg["apply_precision"]
    readings, _ = reference_readings(
        cell, reference_setup(cell, [precision]), m["samples"], precision)
    del part
    nums = check.worst(readings)
    nums["event_mismatch"] += drain_miss
    checks = {k: {"value": v, "limit": wl["limits"][k]}
              for k, v in nums.items()}
    correct = all(c["value"] <= c["limit"] for c in checks.values())

    lat, stats = m["lat"], m["stats"]
    metrics, extra = {}, {}
    if not trace:
        bio_s = len(lat) * n_win * cfg["fabric"]["window"] * STEP_S
        values = {"rtf": m["window"] / bio_s,
                  "segment_p95_ms": float(np.percentile(lat, 95)) * 1e3,
                  "setup_s": m["setup_s"]}
        units = {e["name"]: e["unit"] for e in e2e}
        metrics = {k: {"value": v, "unit": units[k]}
                   for k, v in values.items() if k in units}
    else:
        n_traced, window_s = m["traced"]
        metrics, extra = layer_readings(
            cell, layer_metrics, m["trace_dir"], module, stats[:n_traced],
            n_traced * n_win, devs[0].device_kind)
        extra["window_s"] = window_s
    dev = devs[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    result = {"correct": correct,
              "attempted": _sum(stats, lambda s: s.offered),
              "failed": _sum(stats, lambda s: s.overflow)
              + _sum(stats, lambda s: s.deadline_miss),
              "metrics": metrics, "device": device}
    if trace:
        device.update(busy_s=extra["busy_s"], window_s=extra["window_s"])
        result["breakdown"] = extra["breakdown"]
    result["segments"] = len(lat)
    result["timing"] = {"to_jax": t_jax - t_start, "build": t_built - t_jax,
                        "setup": m["setup_s"], "window": m["window"],
                        "segment_median": float(np.median(lat)),
                        "segment_max": float(np.max(lat)),
                        "segment_first": lat[0]}
    result["checks"] = checks
    return result, checks


def reference_setup(cell: dict, precisions):
    """The reference's network, window function and device weights
    rounded to each of ``precisions``."""
    import jax.numpy as jnp
    cfg = cell["config"]
    fab, net = cfg["fabric"], cfg["network"]
    w, inh = deploy.reference_weights(cfg, cell["cache"])
    rnet = segment.Network(w, inh, fab["n_shards"], net["delay_exc_steps"],
                           net["delay_inh_steps"])
    del w
    window_fn = drive(cell).reference_window(cell, rnet)
    w_all = jnp.asarray(rnet.w)
    weights = {p: segment.round_to(w_all, p) for p in precisions}
    del w_all
    return rnet, window_fn, weights


def reference_readings(cell: dict, ref, samples, precision: str,
                       control: str | None = None):
    """Each sampled segment against the plain reference run from its
    starting state: the program's numbers, and, with ``control``, the
    numbers of the reference computing the apply in that precision put
    in the program's place."""
    rnet, window_fn, weights = ref
    n = cell["traffic"]["segment_windows"]
    prog, ctl = [], []
    for _, before, after, st in samples:
        spikes, bad = check.in_flight(rnet, before)
        args = (rnet, cell["config"], before, spikes, window_fn)
        want = check.simulate(*args, weights[precision], n)
        prog.append(check.compare(check.program_outputs(rnet, after, st),
                                  want, bad))
        if control:
            ctl.append(check.compare(
                check.simulate(*args, weights[control], n), want))
    return prog, ctl


def _load_reader(root: str, kind: str, name: str):
    """The module ``<root>/<kind>/<name>.py``: a per-layer metric's
    reader (``metrics``) or a drive (``drives``)."""
    path = os.path.join(root, kind, f"{name}.py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {kind} module {name!r}: {path}")
    spec = importlib.util.spec_from_file_location(f"perf_{kind}_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def network_counts(part) -> dict:
    """Mean synapses onto each node of one event delivered there: over
    the source neurons that reach the node."""
    per = part.per_shard
    syn = np.stack([(part.weights[d * per:(d + 1) * per] != 0).sum(0)
                    for d in range(part.n_shards)], 1)
    return {"syn_per_event": syn.sum(0) / np.maximum((syn > 0).sum(0), 1)}


def layer_readings(cell, layer_metrics, trace_dir, module, stats,
                   n_windows, kind):
    """Per-layer metrics of the traced window."""
    root = cell["root"]
    defs = {}
    for p in sorted(os.listdir(os.path.join(root, "metrics"))):
        if p.endswith(".json"):
            with open(os.path.join(root, "metrics", p)) as f:
                d = json.load(f)
            defs[d["name"]] = d
    rules = ptrace.layer_rules(defs.values())
    name, proto = module
    charges = ptrace.charge_ops(ptrace.op_stacks(proto), rules)
    tr = ptrace.read_xplane(trace_dir, name)
    layers = sorted({d["layer"] for d in defs.values() if d["charges"]})
    red = ptrace.reduce(tr, charges, n_windows, layers)
    peaks = ptrace.chip_peaks(kind, os.path.join(root, "peaks.json"))
    ctx = {"stats": window_counters(stats),
           "network": cell.get("network_counts"), "config": cell["config"]}
    out = {}
    for m in layer_metrics:
        d = defs[m["name"]]
        layer_s = (red["layer_s"][ptrace.OTHER]
                   if d["reduce"] == "catch_all_ms_per_window"
                   else red["layer_s"].get(d["layer"], 0.0))
        per_window_s = layer_s / n_windows
        if d["reduce"] == "roofline_share":
            w = _load_reader(root, "metrics", m["name"]).work(ctx)
            least = max(w["flops"] / peaks["bf16_flops_per_s"],
                        w["bytes"] / peaks["hbm_bytes_per_s"])
            if per_window_s <= 0 or least <= 0:
                continue            # nothing to read: left out of the line
            value = 100.0 * least / per_window_s
        else:
            value = per_window_s * 1e3
        out[m["name"]] = {"value": value, "unit": m["unit"]}
    extra = {"busy_s": red["busy_s"],
             "breakdown": {"device_ops": red["device_ops"],
                           "idle_gaps": red["idle_gaps"]}}
    return out, extra


def window_counters(stats) -> dict:
    """The traced window's counters, (windows, nodes) each."""
    cat = lambda get: np.concatenate(
        [np.swapaxes(np.asarray(get(s)), 0, 1) for s in stats])
    return {"offered": cat(lambda s: s.offered),
            "sent": cat(lambda s: s.events_sent),
            "delivered": cat(lambda s: s.link.delivered_events)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result, checks = run(args.workload, args.seed, args.seconds,
                             bool(args.trace))
    except NoChip as e:
        print(e, file=sys.stderr)
        return 2
    print(f"segments {result['segments']}: " + ", ".join(
        f"{k} {v:.4f} s" for k, v in result.pop("timing").items()),
        file=sys.stderr)
    for k, c in checks.items():
        print(f"check {k} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(f"correct = {result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
