"""Paper §1 — Extoll link budget and BrainScaleS topology load.

The paper gives the raw numbers (12 lanes x 8.4 Gbit/s per link, 7 links
per Tourmalet, 48 FPGAs -> 8 concentrators per wafer) but no load analysis;
this bench derives one: what biological real-time factor the interconnect
sustains for the full-scale cortical microcircuit spread over N wafers,
with and without aggregation.
"""
from __future__ import annotations

import numpy as np

from repro.core import events as ev
from repro.core import torus
from repro.snn import microcircuit as mc


def exchange_walltime(report, n_events: int = 4096, capacity: int = 256):
    """Wall-clock of one full software flush window (fused route+aggregate
    + packed single all_to_all + multicast decode) on the local mesh."""
    import jax
    import jax.numpy as jnp
    from benchmarks.run import median_ms
    from repro.core import routing as rt
    from repro.core.exchange import make_exchange

    n_shards = 1                           # in-process mesh: 1 host device
    n_addr = 1 << 12
    from repro.launch.mesh import make_wafer_mesh
    mesh = make_wafer_mesh(n_shards)
    projs = [rt.Projection(a, a + 1, dest_node=a % n_shards,
                           dest_links=[a % 8]) for a in range(n_addr)]
    t = rt.build_tables(n_addr, projs)
    tabs = rt.RoutingTables(t.dest_of_addr[None], t.guid_of_addr[None],
                            t.mcast_of_guid[None])
    k = jax.random.PRNGKey(0)
    words = ev.pack(jax.random.randint(k, (n_shards, n_events), 0, n_addr),
                    jax.random.randint(jax.random.fold_in(k, 1),
                                       (n_shards, n_events), 0, 1 << 15))
    run = make_exchange(mesh, "wafer", n_shards=n_shards, capacity=capacity,
                        n_addr_per_shard=n_addr)
    ms = median_ms(lambda: run(words, tabs))
    report.bench("link", "exchange_window",
                 f"S{n_shards}_N{n_events}_C{capacity}", ms,
                 events_per_s=n_events / ms * 1e3,
                 notes="fused route+aggregate, one packed all_to_all")


def main(report):
    link_bytes = torus.LINK_GBYTES * 1e9
    report("link/raw_GBps", round(torus.LINK_GBYTES, 2),
           "12 lanes x 8.4 Gbit/s")

    exchange_walltime(report)

    # full-scale microcircuit: 77k neurons, mean rate ~4 Hz biological;
    # BrainScaleS runs at 1e3-1e4 x biological speedup.
    n_neurons = int(mc.FULL_SIZES.sum())
    mean_rate_bio = 4.0
    for speedup in (1e3, 1e4):
        ev_per_s = n_neurons * mean_rate_bio * speedup
        # inter-wafer fraction ~ connections leaving a wafer (2 wafers,
        # random split: ~50% of the 0.3B synapses cross)
        cross_frac = 0.5
        cross_events = ev_per_s * cross_frac
        for aggregated, n_pkt in (("no", 1), ("yes", 124)):
            bytes_per_event = float(ev.packet_bytes(n_pkt)) / n_pkt
            gbytes = cross_events * bytes_per_event / 1e9
            links_needed = gbytes * 1e9 / link_bytes
            report(
                f"link/microcircuit/speedup={speedup:.0e}/agg={aggregated}",
                round(gbytes, 2),
                f"GB/s cross-wafer; {links_needed:.1f} links' worth",
            )

    # torus link load for the wafer topology (paper Fig. 1)
    for n_wafers in (2, 4, 8):
        t = torus.wafer_topology(n_wafers)
        traffic = torus.microcircuit_traffic(
            t.n_nodes, events_per_s=n_neurons * mean_rate_bio * 1e4)
        max_load = t.max_link_load(traffic)
        report(f"link/torus/wafers={n_wafers}/max_link_GBps",
               round(max_load / 1e9, 3),
               f"nodes={t.n_nodes} mean_hops={t.mean_hops():.2f} "
               f"bisection={t.bisection_gbytes():.0f}GB/s")
