"""Flush-window transport head-to-head: alltoall vs torus2d vs torus3d
(paper §1/§3).

One exchange window (fused route+aggregate + ship + multicast) per
backend on 8 shards — crossbar, (2, 4) 2-D torus and (2, 2, 2) 3-D torus
— plus credit-throttled torus variants so the hop-by-hop stall path is
exercised, plus a multi-window congestion study (FabricState threaded
across a scan of sustained windows) so the in-fabric transit buffers
show rows parking mid-route AND resuming: the study row carries
``parked`` / ``unparked`` / ``hop0_reentries`` / ``dwell_us`` /
``latency_p99_us``.  Needs 8 devices, so the timed work runs in a
process of its own with ``xla_force_host_platform_device_count=8``; results feed
``BENCH_transport.json`` with backend, mesh shape, median_ms,
events_per_s and credit_stalls per row (see docs/benchmarks.md for the
full schema).
"""
from __future__ import annotations

from benchmarks._fabric_study import STUDY_SNIPPET

SCRIPT = r'''
import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=8 "
                           + os.environ.get("XLA_FLAGS", ""))
import json, sys, time
import jax, jax.numpy as jnp, numpy as np
from repro.core import events as ev, routing as rt
from repro.core.exchange import make_exchange
from repro.launch.mesh import make_wafer_mesh, wafer_torus_shape

params = json.loads(sys.argv[1])
n_shards, n_addr = 8, 1024
N, C, iters = params["n"], params["c"], params["iters"]
mesh = make_wafer_mesh(n_shards)
nx, ny = wafer_torus_shape(n_shards)
n3 = wafer_torus_shape(n_shards, ndim=3)
tabs = []
for s in range(n_shards):
    projs = [rt.Projection(a, a + 1, dest_node=(a * 7 + s) % n_shards,
                           dest_links=[a % 3]) for a in range(n_addr)]
    tabs.append(rt.build_tables(n_addr, projs, n_guid=64))
stacked = rt.RoutingTables(
    dest_of_addr=jnp.stack([t.dest_of_addr for t in tabs]),
    guid_of_addr=jnp.stack([t.guid_of_addr for t in tabs]),
    mcast_of_guid=jnp.stack([t.mcast_of_guid for t in tabs]))
words = ev.pack(
    jax.random.randint(jax.random.PRNGKey(0), (n_shards, N), 0, n_addr),
    jax.random.randint(jax.random.PRNGKey(1), (n_shards, N), 0, 1000))

def median_ms(fn, *args):
    jax.tree_util.tree_leaves(fn(*args))[0].block_until_ready()
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        out = fn(*args)
        jax.tree_util.tree_leaves(out)[0].block_until_ready()
        ts.append(time.perf_counter() - t0)
    ts.sort()
    return ts[len(ts) // 2] * 1e3

rows = []
cr = params["credits"]
cases = [("alltoall", None, "", "crossbar"),
         ("torus2d", {"nx": nx, "ny": ny}, "", "%dx%d" % (nx, ny)),
         ("torus2d", {"nx": nx, "ny": ny, "link_credits": cr},
          "+credits", "%dx%d" % (nx, ny)),
         ("torus3d", {"nx": n3[0], "ny": n3[1], "nz": n3[2]}, "",
          "%dx%dx%d" % n3),
         ("torus3d", {"nx": n3[0], "ny": n3[1], "nz": n3[2],
                      "link_credits": cr}, "+credits", "%dx%dx%d" % n3)]
for backend, opts, tag, meshname in cases:
    run = make_exchange(mesh, "wafer", n_shards=n_shards, capacity=C,
                        n_addr_per_shard=n_addr, transport=backend,
                        transport_opts=opts)
    out = run(words, stacked)
    med = median_ms(run, words, stacked)
    sent = int(np.asarray(out.link.sent_events).sum())
    sbh = np.asarray(out.link.stalled_by_hop).sum(0)
    rows.append({
        "backend": backend + tag,
        "mesh": meshname,
        "shape": "S=8 N={} C={}".format(N, C),
        "median_ms": med,
        "events_per_s": sent / (med * 1e-3) if med > 0 else 0.0,
        "credit_stalls": int(np.asarray(out.link.credit_stalls).sum()),
        "hops": int(np.asarray(out.link.hops)[0]),
        "forwarded_bytes": int(np.asarray(out.link.forwarded_bytes).sum()),
        "stalled_by_hop": [int(v) for v in sbh],
        "parked": int(np.asarray(out.link.parked_events).sum()),
        "dwell_us": round(
            float(np.asarray(out.link.queue_dwell_us).sum()), 3),
    })

# congestion study: thread the FabricState across a scan of sustained
# windows so parked rows actually RESUME mid-route (a one-shot window can
# park but never unpark); stats are summed over windows, timing is the
# whole scan divided by n_windows
''' + STUDY_SNIPPET + r'''

study_opts = {"nx": n3[0], "ny": n3[1], "nz": n3[2], "link_credits": cr}
study_mesh = "%dx%dx%d" % n3
base_med = None
# the recorder variant threads the flight-recorder ring (+stall
# attribution) through the same scan; its events_per_s against the plain
# study row is the observability overhead bound docs/observability.md
# cites (<5%)
for depth, tag in [(None, ""), (N_WIN, "+recorder")]:
    run = make_study("torus3d", study_opts, recorder_depth=depth)
    out = run()
    link, lat = out[0], out[1]
    med = median_ms(run)
    link = jax.tree_util.tree_map(np.asarray, link)
    sent = int(link.sent_events.sum() + link.unparked_events.sum())
    sbh = link.stalled_by_hop.sum((0, 1))
    row = {
        "backend": "torus3d+credits%s*%dwin" % (tag, N_WIN),
        "mesh": study_mesh,
        "shape": "S=8 N={} C={} W={}".format(N, C, N_WIN),
        "median_ms": med / N_WIN,
        "events_per_s": sent / (med * 1e-3) if med > 0 else 0.0,
        "credit_stalls": int(link.credit_stalls.sum()),
        "hops": int(link.hops[0].sum()),
        "forwarded_bytes": int(link.forwarded_bytes.sum()),
        "stalled_by_hop": [int(v) for v in sbh],
        "parked": int(link.parked_events.sum()),
        "unparked": int(link.unparked_events.sum()),
        "hop0_reentries": int(link.deferred_events.sum()),
        "dwell_us": round(float(link.queue_dwell_us.sum()), 3),
        # worst delivering window: late saturated windows may deliver
        # nothing at all (empty digest), so take the max over windows
        "latency_p99_us": round(float(np.asarray(lat.p99_us).max()), 3),
    }
    if depth is None:
        base_med = med
    else:
        ring = jax.tree_util.tree_map(np.asarray, out[2])
        row["ring_windows"] = int(ring.cursor[0])
        row["recorder_overhead_pct"] = round(
            (med - base_med) / base_med * 100.0, 2) if base_med else 0.0
    rows.append(row)
print("BENCH_JSON " + json.dumps(rows))
'''


def main(report) -> None:
    params = {
        "n": 512 if report.smoke else 4096,
        "c": 64 if report.smoke else 256,
        "iters": 5 if report.smoke else 15,
        "windows": 4 if report.smoke else 6,
    }
    # throttle to roughly half the typical per-link demand so stalls
    # occur, but never below the bucket capacity (admission invariant)
    params["credits"] = max(params["n"] // 8, params["c"])
    for row in report.run_script(SCRIPT, params, timeout=1200):
        extra = {k: row[k] for k in (
            "backend", "mesh", "credit_stalls", "hops", "forwarded_bytes",
            "stalled_by_hop", "parked", "dwell_us", "unparked",
            "hop0_reentries", "latency_p99_us", "ring_windows",
            "recorder_overhead_pct") if k in row}
        report.bench(
            "transport", row["backend"], f"mesh={row['mesh']} {row['shape']}",
            row["median_ms"], row["events_per_s"],
            notes=f"stalls={row['credit_stalls']} parked={row['parked']}",
            extra=extra)
