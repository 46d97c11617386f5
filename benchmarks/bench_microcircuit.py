"""Paper §4 — the named target workload: multi-wafer cortical microcircuit.

Runs the full windowed simulator (LIF dynamics + fused route/aggregate +
credit-throttled torus3d exchange) on the reduced-scale cortical
microcircuit over 8 forced host devices arranged as a 2x2x2 wafer torus,
under a **fault matrix**: no-fault baseline, one cable permanently dead,
a flapping cable, and a dropped wafer node (``repro.fabric.faults``).
Each row of ``BENCH_microcircuit.json`` carries the measured
biological-real-time slowdown, the delivery ratio, detour (reroute)
counts and the p99 latency degradation against the no-fault baseline —
the chaos-engineering counterpart of the paper's commissioning runs.

Needs 8 devices, so the timed work runs in a process of its own with
``xla_force_host_platform_device_count=8``, like ``bench_transport``.
"""
from __future__ import annotations

import os

SCRIPT = r'''
import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=8 "
                           + os.environ.get("XLA_FLAGS", ""))
import json, sys, time
import jax, numpy as np
from repro.fabric import healthy, link_fault, link_flap, node_fault
from repro.snn import microcircuit as mc, network, simulator as sim

params = json.loads(sys.argv[1])
scale, n_win, iters = params["scale"], params["windows"], params["iters"]
cap, cred = params["capacity"], params["credits"]
spec = mc.MicrocircuitSpec(scale=scale)
w, is_inh = spec.weight_matrix()
part = network.build_partition(w, is_inh, n_shards=8)
from repro.launch.mesh import make_wafer_mesh
mesh = make_wafer_mesh(8)
dims = (2, 2, 2)
cfg = sim.SimConfig(n_shards=8, per_shard=part.per_shard,
                    max_fan=part.fanout.shape[1], window=8, ring_len=32,
                    e_max=512, capacity=cap, transport="torus3d",
                    torus_nx=dims[0], torus_ny=dims[1], torus_nz=dims[2],
                    link_credits=cred, notify_latency=2)
# faults start at window 2 so the pipeline is warm when the cable dies
matrix = [
    ("no_fault",  healthy(dims, n_win)),
    ("link_down", link_fault(dims, n_win, 0, 0, start=2)),
    ("link_flap", link_flap(dims, n_win, 0, 0, period=2, start=2)),
    ("node_down", node_fault(dims, n_win, 3, start=2)),
]
bio_s = n_win * cfg.window * cfg.params.dt * 1e-3     # dt is ms
trace_dir = params.get("trace_dir")
rows = []
for name, sched in matrix:
    init, run = sim.build_sharded_sim(mesh, "wafer", cfg, part,
                                      spec.bg_rates(),
                                      fault_schedule=sched)
    st, stats = run(init(0), n_win)                   # compile + warmup
    jax.block_until_ready((st, stats))
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        st, stats = run(init(0), n_win)
        jax.block_until_ready((st, stats))
        ts.append(time.perf_counter() - t0)
    ts.sort()
    med_s = ts[len(ts) // 2]
    s = jax.tree_util.tree_map(np.asarray, stats)
    link = s.link
    offered = int(link.offered_events.sum())
    delivered = int(link.delivered_events.sum())
    rows.append({
        "fault": name,
        "mesh": "%dx%dx%d" % dims,
        "shape": "S=8 scale=%g W=%d C=%d credits=%d" % (scale, n_win,
                                                        cap, cred),
        "median_ms": med_s * 1e3 / n_win,
        "events_per_s": delivered / med_s if med_s > 0 else 0.0,
        "bio_slowdown": round(med_s / bio_s, 1),
        "spikes": int(s.spikes.sum()),
        "delivery_ratio": round(delivered / max(offered, 1), 4),
        "rerouted": int(link.rerouted.sum()),
        "parked": int(link.parked_events.sum()),
        "deferred": int(link.deferred_events.sum()),
        "deadline_miss": int(s.deadline_miss.sum()),
        "latency_p99_us": round(float(s.latency.p99_us.max()), 3),
    })
    if trace_dir:
        # untimed flight-recorder pass: same config + fault schedule with
        # the telemetry ring in the carry, decoded into an observability
        # run directory (render: python -m repro.obs.report <dir>)
        from repro import obs
        from repro.fabric import faults as fabric_faults
        from repro.obs import metrics as obs_metrics
        from repro.obs import report as obs_report
        init_r, run_r = sim.build_sharded_sim(
            mesh, "wafer", cfg, part, spec.bg_rates(), fault_schedule=sched,
            recorder=obs.RecorderConfig(depth=max(n_win, 8)))
        st_r, stats_r, ring = run_r(init_r(0), n_win)
        reg = obs_metrics.Registry()
        obs_metrics.export_link_stats(
            reg, jax.tree_util.tree_map(np.asarray, stats_r.link),
            backend="torus3d")
        obs_report.write_run_dir(
            os.path.join(trace_dir, "obs_microcircuit_%s" % name),
            meta={"kind": "microcircuit", "dims": list(dims),
                  "n_shards": 8, "fault": name, "windows": n_win,
                  "window_us": cfg.window * cfg.params.dt * 1e3,
                  "link_credits": cred},
            recorder_rows=obs.global_rows(ring, 8),
            fault_events=fabric_faults.transitions(sched),
            registry=reg)
base = rows[0]
for r in rows:
    r["p99_degradation"] = round(
        r["latency_p99_us"] / max(base["latency_p99_us"], 1e-9), 3)
    r["delivery_vs_healthy"] = round(
        r["delivery_ratio"] / max(base["delivery_ratio"], 1e-9), 4)
print("BENCH_JSON " + json.dumps(rows))
'''


def main(report) -> None:
    from repro.snn import microcircuit as mc
    params = {
        "scale": 0.003 if report.smoke else 0.01,
        "windows": 8 if report.smoke else 40,
        "iters": 1 if report.smoke else 3,
        "capacity": 32 if report.smoke else 48,
    }
    # throttled to the bucket capacity: the admission invariant's floor
    # and low enough that faults actually contend for detour credits
    params["credits"] = params["capacity"]
    if report.trace_dir:
        params["trace_dir"] = os.path.abspath(report.trace_dir)
    spec = mc.MicrocircuitSpec(scale=params["scale"])
    report("microcircuit/neurons", spec.n_neurons, f"scale={spec.scale}")
    for row in report.run_script(SCRIPT, params, timeout=2400):
        extra = {k: row[k] for k in (
            "fault", "mesh", "bio_slowdown", "spikes", "delivery_ratio",
            "delivery_vs_healthy", "rerouted", "parked", "deferred",
            "deadline_miss", "latency_p99_us", "p99_degradation")}
        report.bench(
            "microcircuit", row["fault"],
            f"mesh={row['mesh']} {row['shape']}",
            row["median_ms"], row["events_per_s"],
            notes=(f"bio x{row['bio_slowdown']} "
                   f"delivery={row['delivery_ratio']} "
                   f"rerouted={row['rerouted']}"),
            extra=extra)
