"""End-to-end driver: the paper's target workload — a (reduced-scale)
Potjans-Diesmann cortical microcircuit spread over one 'wafer' shard per
device, spikes exchanged through the bucket-aggregated transport fabric.

Prints per-window communication stats (events, wire bytes, aggregation
efficiency, deadline misses) — the numbers the Extoll link budget cares
about — plus per-population firing rates.

NOTE: must run as its own process.  On a CPU it forces 4 host devices, so
it runs 4 shards there; on an accelerator it runs one shard per chip.
Run:  PYTHONPATH=src python examples/multiwafer_microcircuit.py \
          [alltoall|torus2d|torus3d] [extoll|ethernet]
(first arg selects the transport backend; default "alltoall".  "torus2d"
walks dimension-ordered neighbor hops on a most-square device torus (2x2
on 4 shards), "torus3d" on a most-cubic one (1x2x2) whose Z rings are the
wafer-stacking axis; both report the
link-level hop/forwarding stats with hop-by-hop credit flow control
available via the config's link_credits.  Second arg selects the wire
protocol profile (repro.wire): frame-exact bytes_on_wire and the
per-event latency percentiles are reported for it — run once with
"extoll" and once with "ethernet" to see the paper's protocol-tax and
switch-latency comparison.)
"""
import os
# the flag shapes only the CPU backend: an accelerator keeps its own devices
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")

import dataclasses
import sys

import jax
import numpy as np

from repro.configs import brainscales
from repro.core import aggregator
from repro.launch.mesh import (make_wafer_mesh, wafer_torus_shape,
                               wafer_wire_format)
from repro.snn import microcircuit as mc, network, simulator as sim


def main(transport: str = "alltoall", wire_format: str = "extoll"):
    spec = mc.MicrocircuitSpec(scale=0.004)
    w, is_inh = spec.weight_matrix()
    print(f"microcircuit: {spec.n_neurons} neurons, "
          f"{(w != 0).sum()} synapses (scale={spec.scale})")

    n_shards = jax.device_count()
    part = network.build_partition(w, is_inh, n_shards=n_shards)
    print(f"partition: {n_shards} wafer shards x {part.per_shard} neurons, "
          f"max fan-out {part.fanout.shape[1]} shards/source")

    bs = dataclasses.replace(brainscales.CONFIG, transport=transport,
                             wire_format=wire_format)
    cfg = sim.SimConfig(
        n_shards=n_shards, per_shard=part.per_shard,
        max_fan=part.fanout.shape[1],
        window=8,                  # <= min axonal delay (deadline flush)
        ring_len=32, e_max=512, capacity=512,
        **bs.transport_fields(),
    )
    if transport == "torus2d":
        print(f"transport: {transport} {wafer_torus_shape(n_shards)} torus")
    elif transport == "torus3d":
        print(f"transport: {transport} {wafer_torus_shape(n_shards, ndim=3)} torus")
    else:
        print(f"transport: {transport}")
    mesh = make_wafer_mesh(n_shards)
    init, run = sim.build_sharded_sim(mesh, "wafer", cfg, part,
                                      spec.bg_rates())
    state = init(seed=0)

    n_windows = 25                 # 25 x 8 x 0.1ms = 20 ms biological
    state, stats = run(state, n_windows)
    spikes = np.asarray(stats.spikes).sum(0)        # (windows,) per shard sum
    sent = np.asarray(stats.events_sent).sum()
    wire = np.asarray(stats.wire_bytes).sum()
    miss = np.asarray(stats.deadline_miss).sum()
    ovf = np.asarray(stats.overflow).sum()

    bio_ms = n_windows * cfg.window * cfg.params.dt
    total_spikes = int(np.asarray(stats.spikes).sum())
    print(f"\nsimulated {bio_ms:.1f} ms: {total_spikes} spikes, "
          f"mean rate {total_spikes / (spec.n_neurons * bio_ms * 1e-3):.1f} Hz")
    print(f"events shipped (incl. fan-out replicas): {int(sent)}")
    print(f"Extoll wire bytes: {int(wire)} "
          f"({int(wire) / max(int(sent), 1):.1f} B/event effective)")
    naive = aggregator.unaggregated_cost(int(sent))
    print(f"without aggregation: {int(naive.bytes)} bytes "
          f"-> bucket aggregation saves "
          f"{int(naive.bytes) / max(int(wire), 1):.1f}x")
    print(f"deadline misses: {int(miss)}   bucket overflows: {int(ovf)}")
    # frame-exact wire accounting + the per-event latency distribution of
    # the configured protocol profile (repro.wire); per-profile wire
    # EFFICIENCY needs the hop-weighted (src, dst) count matrix and lives
    # in BENCH_wire.json (benchmarks/bench_wire.py), not here
    fmt = wafer_wire_format(wire_format)
    on_wire = int(np.asarray(stats.link.bytes_on_wire).sum())
    lat = stats.latency
    n_win = np.asarray(lat.p50_us).shape[1]
    p50 = float(np.asarray(lat.p50_us)[:, 1:].mean()) if n_win > 1 else 0.0
    p99 = float(np.asarray(lat.p99_us).max())
    lmax = float(np.asarray(lat.max_us).max())
    print(f"wire profile '{fmt.name}': {on_wire} bytes on wire "
          f"(frame-exact; {fmt.header_bytes + fmt.crc_bytes} B/frame tax, "
          f"{fmt.gap_bytes} B gap, {fmt.cell_bytes} B cells)")
    print(f"event latency: p50 {p50:.2f} us (mean over windows), "
          f"p99 {p99:.2f} us, max {lmax:.2f} us")
    if transport in ("torus2d", "torus3d"):
        link = stats.link
        print(f"torus link stats: {int(np.asarray(link.hops)[0, 0])} "
              f"hops/window, "
              f"{int(np.asarray(link.forwarded_bytes).sum())} forwarded "
              f"bytes, max in-flight "
              f"{int(np.asarray(link.max_in_flight).max())} events, "
              f"{int(np.asarray(link.credit_stalls).sum())} credit stalls")
    assert miss == 0, "windowed exchange must respect timestamp deadlines"
    print("ok.")


if __name__ == "__main__":
    main(sys.argv[1] if len(sys.argv) > 1 else "alltoall",
         sys.argv[2] if len(sys.argv) > 2 else "extoll")
