"""Wire-layer head-to-head: extoll vs ethernet protocol profiles on every
transport backend (the paper's §1 claim, now quantitative).

For each (backend, profile) pair one full exchange window runs on 8
forced host devices (subprocess, like ``bench_transport``): fused
route+aggregate, 64-bit wire-word codec, transport, multicast decode.
Each row reports median wall-clock, events/s, the frame-exact
``bytes_on_wire``, wire efficiency (= event payload bytes / bytes on
wire, per traversed hop) and the per-window wire-latency percentiles
from ``ExchangeOut.latency`` — so ``BENCH_wire.json`` holds the
Ethernet-vs-Extoll comparison as machine-readable numbers: the extoll
profile must show strictly higher wire efficiency and lower latency on
every backend.

A codec microbenchmark row (pack+unpack round-trip wall-clock) rides
along, since the codec is new hot-path work the exchange now pays, and
a congested ``torus3d+credits`` multi-window row (FabricState threaded
through a ``lax.scan``) measures the latency model's congestion terms:
its p99 must sit strictly above the uncongested torus3d row's while the
uncongested p50 is untouched.
"""
from __future__ import annotations

from benchmarks._fabric_study import STUDY_SNIPPET

SCRIPT = r'''
import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=8 "
                           + os.environ.get("XLA_FLAGS", ""))
import json, sys, time
import jax, jax.numpy as jnp, numpy as np
from repro import wire
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

def hops_matrix(backend, meshdims):
    ids = np.arange(n_shards)
    if backend == "alltoall":
        return (ids[:, None] != ids[None, :]).astype(np.int64)
    from repro.core.torus import Torus
    pad = tuple(meshdims) + (1,) * (3 - len(meshdims))
    host = Torus(nx=pad[0], ny=pad[1], nz=pad[2])
    return host.hops(ids[:, None], ids[None, :]).astype(np.int64)

rows = []
cases = [("alltoall", None, (), "crossbar"),
         ("torus2d", {"nx": nx, "ny": ny}, (nx, ny), "%dx%d" % (nx, ny)),
         ("torus3d", {"nx": n3[0], "ny": n3[1], "nz": n3[2]}, n3,
          "%dx%dx%d" % n3)]
for backend, opts, meshdims, meshname in cases:
    for profile in ("extoll", "ethernet"):
        run = make_exchange(mesh, "wafer", n_shards=n_shards, capacity=C,
                            n_addr_per_shard=n_addr, transport=backend,
                            transport_opts=dict(opts) if opts else None,
                            wire_format=profile)
        out = run(words, stacked)
        med = median_ms(run, words, stacked)
        sent = int(np.asarray(out.link.sent_events).sum())
        on_wire = int(np.asarray(out.link.bytes_on_wire).sum())
        # every traversed hop re-serializes the row's 8-byte words, so
        # wire efficiency = per-hop payload bytes / frame-exact wire bytes
        cnt = (np.asarray(out.sent_counts)
               * np.asarray(out.sent_mask)).astype(np.int64)
        payload = int((cnt * hops_matrix(backend, meshdims)).sum()) * 8
        rows.append({
            "backend": backend,
            "wire_format": profile,
            "mesh": meshname,
            "shape": "S=8 N={} C={}".format(N, C),
            "median_ms": med,
            "events_per_s": sent / (med * 1e-3) if med > 0 else 0.0,
            "bytes_on_wire": on_wire,
            "wire_efficiency": round(payload / max(on_wire, 1), 4),
            "latency_p50_us": round(
                float(np.asarray(out.latency.p50_us).max()), 3),
            "latency_p99_us": round(
                float(np.asarray(out.latency.p99_us).max()), 3),
            "latency_max_us": round(
                float(np.asarray(out.latency.max_us).max()), 3),
        })

# congestion row: torus3d under sustained credit-throttled windows (the
# FabricState threads a lax.scan), extoll profile — parked rows resume
# mid-route and the queueing term pushes p99 up while the uncongested
# p50 above stays at the serialization-only charge
''' + STUDY_SNIPPET + r'''
cr = max(N // 8, C)
run_c = make_study("torus3d", {"nx": n3[0], "ny": n3[1], "nz": n3[2],
                               "link_credits": cr,
                               "wire_format": "extoll"})
link, lat = run_c()
med = median_ms(run_c)
link = jax.tree_util.tree_map(np.asarray, link)
sent = int(link.sent_events.sum() + link.unparked_events.sum())
rows.append({
    "backend": "torus3d+credits*%dwin" % N_WIN,
    "wire_format": "extoll",
    "mesh": "%dx%dx%d" % n3,
    "shape": "S=8 N={} C={} W={}".format(N, C, N_WIN),
    "median_ms": med / N_WIN,
    "events_per_s": sent / (med * 1e-3) if med > 0 else 0.0,
    "bytes_on_wire": int(link.bytes_on_wire.sum()),
    "parked": int(link.parked_events.sum()),
    "unparked": int(link.unparked_events.sum()),
    "dwell_us": round(float(link.queue_dwell_us.sum()), 3),
    # worst delivering window: late saturated windows may deliver nothing
    # at all (empty digest), so take the max over windows
    "latency_p50_us": round(float(np.asarray(lat.p50_us).max()), 3),
    "latency_p99_us": round(float(np.asarray(lat.p99_us).max()), 3),
    "latency_max_us": round(float(np.asarray(lat.max_us).max()), 3),
})

# codec microbenchmark: pack+unpack round trip at window scale
meta = jnp.arange(n_shards * N, dtype=jnp.int32).reshape(n_shards, N)
rt_fn = jax.jit(lambda w, m: wire.decode_planar(wire.encode_planar(w, m)))
med = median_ms(rt_fn, words, meta)
rows.append({
    "backend": "codec", "wire_format": "64bit-word",
    "mesh": "-", "shape": "S=8 N={}".format(N), "median_ms": med,
    "events_per_s": n_shards * N / (med * 1e-3) if med > 0 else 0.0,
})
print("BENCH_JSON " + json.dumps(rows))
'''


def main(report) -> None:
    params = {
        "n": 512 if report.smoke else 4096,
        "c": 64 if report.smoke else 256,
        "iters": 5 if report.smoke else 15,
        "windows": 4 if report.smoke else 6,
    }
    for row in report.run_script(SCRIPT, params, timeout=1800):
        op = f"{row['backend']}/{row['wire_format']}"
        extra = {k: row[k] for k in row
                 if k not in ("median_ms", "events_per_s", "shape")}
        notes = ""
        if "wire_efficiency" in row:
            notes = (f"eff={row['wire_efficiency']} "
                     f"p50={row['latency_p50_us']}us")
        report.bench(
            "wire", op, f"mesh={row['mesh']} {row['shape']}",
            row["median_ms"], row["events_per_s"], notes=notes, extra=extra)
