"""Chip smoke test: the spike fabric's main path on a TPU, in one process.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chip   # the 4-shard checks, four chips

One chip runs three phases through the entry points a user calls:

* ``sim``: the Potjans-Diesmann microcircuit at ``scale=0.21`` (16,202
  neurons, one node's full 14-bit event address space) through
  ``simulator.build_sharded_sim`` on a 1-device ``wafer`` mesh with the
  ``alltoall`` transport and the ``extoll`` wire profile, 25 flush
  windows of 8 steps (20 ms of biological time).  The compiled segment
  must contain the Pallas kernels (``tpu_custom_call``); spikes > 0, no
  deadline miss, no overflow, and every window's latency histogram
  counts exactly the events delivered in it.
* ``kernels``: the placement and codec kernels, Pallas against XLA, on
  one window's real-width data of the 1- and 4-shard configurations;
  bit-exact.
* ``engine``: the multi-tenant ``SpikeEngine`` (2 tenants, the paper's
  124-event bucket) for a few segments; its conservation ledger holds.

``--four-chip`` runs only what exists across chips: the same network on
4 shards under ``alltoall`` and under an uncredited 1x2x2 ``torus3d``
(delivered counts, spikes, final neuron state and the number of events
each window's latency digest counts are bit-identical; the latencies
themselves differ by design, one crossbar hop against torus hops), and a
credited ``torus3d`` run whose link conservation identities hold; the
per-shard operands must be spread over the four devices.

Each phase prints one JSON line; the last line is
``{"ok": true, "device": {...}}`` and appears only if every check held.
Without a TPU, or with ``REPRO_PALLAS_INTERPRET`` set, it exits non-zero.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

SCALE = 0.21             # 16,202 neurons: per_shard * max_fan = 16,202
#                          on 1 shard and 4,051 * 4 = 16,204 on 4, both
#                          inside the 14-bit address field (16,384)
WINDOWS = 25             # x 8 steps x 0.1 ms = 20 ms biological time
RESIDUE = 256
# spike-compaction buffer and bucket row, per shard count: both carry a
# shard's spikes up to a mean rate of 316 Hz in a window (4096 = 16,202
# neurons x 0.8 ms x 316 Hz; 1024 = 4,051 x 0.8 ms x 316 Hz; a spike's
# <= 4 replicas go to distinct destinations, so a row needs no more than
# e_max).  In the seeded runs the busiest window peaks at 118 Hz on 1
# shard and 214 Hz on the busiest of 4.
E_MAX = {1: 4096, 4: 1024}
CAPACITY = {1: 4096, 4: 1024}
NOTIFY_LATENCY = 2       # windows before spent link credits return
# Credits for the credited 4-shard run: on a 1x2x2 torus each directed
# link carries at most 2 bucket rows a window (its own Y row and the one
# it forwards on Z), and credits come back NOTIFY_LATENCY windows later,
# so this budget never stalls.  At link_credits = 1024 the opening burst
# stalls rows and events miss their deadlines (the link identities still
# hold there).
LINK_CREDITS = 2 * CAPACITY[4] * (NOTIFY_LATENCY + 1)


class CheckFailed(AssertionError):
    pass


def check(cond, what: str):
    if not cond:
        raise CheckFailed(what)


def log(phase: str, **kv):
    print(json.dumps({"phase": phase, **kv}), flush=True)


def enable_compile_cache(jax) -> str:
    """Turn on JAX's persistent compilation cache and return its path.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting and is
    left alone.  Otherwise the cache lives at ``.jax_cache`` in the
    checkout: a fixed path, because a later run finds only what was
    cached under the path it looks in.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def peak_bytes(dev) -> int | None:
    stats = dev.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


# ---------------------------------------------------------------------------
# simulator
# ---------------------------------------------------------------------------

def partition(n_shards: int):
    from repro.snn import microcircuit as mc, network
    spec = mc.MicrocircuitSpec(scale=SCALE)
    w, is_inh = spec.weight_matrix()
    part = network.build_partition(w, is_inh, n_shards=n_shards)
    check(part.per_shard * part.fanout.shape[1] <= 1 << 14,
          "event addresses alias: per_shard * max_fan > 2**14")
    return spec, part


def build_sim(spec, part, transport: str, link_credits: int = 0):
    from repro.launch.mesh import make_wafer_mesh
    from repro.snn import simulator as sim
    n = part.n_shards
    torus = {}
    if transport == "torus3d":
        torus = dict(torus_nx=1, torus_ny=2, torus_nz=2)
    cfg = sim.SimConfig(
        n_shards=n, per_shard=part.per_shard, max_fan=part.fanout.shape[1],
        window=8, ring_len=32, e_max=E_MAX[n], capacity=CAPACITY[n],
        residue=RESIDUE, transport=transport, link_credits=link_credits,
        notify_latency=NOTIFY_LATENCY, wire_format="extoll", **torus)
    mesh = make_wafer_mesh(n)
    init, run = sim.build_sharded_sim(mesh, "wafer", cfg, part,
                                      spec.bg_rates())
    return cfg, init, run


def run_sim(jax, spec, part, transport: str, link_credits: int = 0,
            label: str = ""):
    """Compile, run once cold and once warm; return host-side stats."""
    import numpy as np
    t0 = time.perf_counter()
    cfg, init, run = build_sim(spec, part, transport, link_credits)
    state0 = init(0)
    jax.block_until_ready(state0)
    build_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    hlo = run.lower(state0, WINDOWS).compile().as_text()
    compile_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    jax.block_until_ready(run(state0, WINDOWS))
    first_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    state, stats = jax.block_until_ready(run(state0, WINDOWS))
    run_s = time.perf_counter() - t0
    stats = jax.tree_util.tree_map(np.asarray, stats)
    log("sim/" + (label or transport), shards=part.n_shards,
        neurons=int(part.n_neurons), windows=WINDOWS,
        e_max=cfg.e_max, capacity=cfg.capacity, residue=cfg.residue,
        link_credits=link_credits,
        build_s=build_s, compile_s=compile_s, first_run_s=first_s,
        run_s=run_s, spikes=int(stats.spikes.sum()),
        max_spikes_per_window=int(stats.spikes.max()),
        delivered=int(stats.link.delivered_events.sum()),
        overflow=int(stats.overflow.sum()),
        deadline_miss=int(stats.deadline_miss.sum()),
        note="one run's readings, not benchmark numbers")
    return hlo, stats, np.asarray(state.neuron.v)


def check_sim_stats(stats, what: str):
    check(int(stats.spikes.sum()) > 0, f"{what}: network is silent")
    check(int(stats.deadline_miss.sum()) == 0, f"{what}: deadline misses")
    check(int(stats.overflow.sum()) == 0, f"{what}: bucket overflow")
    hist_total = stats.latency.hist.sum(-1)          # (shards, windows)
    check((hist_total == stats.link.delivered_events).all(),
          f"{what}: latency histogram != delivered events in some window")


def phase_sim(jax, dev):
    spec, part = partition(1)
    hlo, stats, _ = run_sim(jax, spec, part, "alltoall")
    check("tpu_custom_call" in hlo,
          "simulator segment has no Pallas kernel (tpu_custom_call)")
    check_sim_stats(stats, "sim/alltoall")
    log("sim/memory", peak_bytes_in_use=peak_bytes(dev))


# ---------------------------------------------------------------------------
# kernels: Pallas against XLA on real-width windows
# ---------------------------------------------------------------------------

def phase_kernels(jax):
    import jax.numpy as jnp
    import numpy as np
    from repro.core import events as ev
    from repro.kernels import fused_route_bucket as frb
    from repro import wire

    for n_dest, fan in ((1, 1), (4, 4)):
        cap, e_max = CAPACITY[n_dest], E_MAX[n_dest]
        # one window as the credited torus offers it: deferred rows, the
        # residue, then fresh spikes x fan-out replicas
        n = n_dest * cap + RESIDUE + e_max * fan
        k = jax.random.split(jax.random.PRNGKey(n_dest), 5)
        words = ev.pack(jax.random.randint(k[0], (n,), 0, 1 << 14),
                        jax.random.randint(k[1], (n,), 0, 1 << 15),
                        valid=jax.random.bernoulli(k[2], 0.9, (n,)))
        # skewed destinations: destination 0 overflows its row
        dest = jnp.where(jax.random.bernoulli(k[3], 0.5, (n,)), 0,
                         jax.random.randint(k[3], (n,), -1, n_dest))
        meta = jax.random.randint(k[4], (n,), 0, 1 << 30)
        outs = []
        for use_pallas in (True, False):
            f = jax.jit(lambda w, d, m, p=use_pallas: frb.fused_aggregate(
                w, d, m, n_dest, cap, residue_len=RESIDUE,
                with_residue_meta=True, use_pallas=p, interpret=False))
            outs.append(jax.tree_util.tree_map(np.asarray,
                                               f(words, dest, meta)))
        same = [bool((a == b).all()) for a, b in
                zip(jax.tree_util.tree_leaves(outs[0]),
                    jax.tree_util.tree_leaves(outs[1]))]
        check(all(same), f"placement Pallas != XLA (S={n_dest}, C={cap})")
        check(int(outs[0].buckets.overflow) > 0,
              "placement data never exercised the overflow path")

        events = jnp.asarray(outs[0].buckets.data)
        bmeta = jnp.asarray(outs[0].buckets.guids)
        codec = []
        for use_pallas in (True, False):
            enc = jax.jit(lambda e, m, p=use_pallas: wire.encode_planar(
                e, m, use_pallas=p, interpret=False))
            dec = jax.jit(lambda b, p=use_pallas: wire.decode_planar(
                b, use_pallas=p, interpret=False))
            buf = enc(events, bmeta)
            codec.append([np.asarray(x) for x in (buf, *dec(buf))])
        check(all((a == b).all() for a, b in zip(*codec)),
              f"codec Pallas != XLA (S={n_dest}, C={cap})")
        check((codec[0][1] == np.asarray(events)).all()
              and (codec[0][2] == np.asarray(bmeta)).all(),
              "codec round trip is not exact")
        log("kernels", n_dest=n_dest, capacity=cap, window_events=n,
            placement_bit_exact=True, codec_bit_exact=True,
            overflow=int(outs[0].buckets.overflow))


# ---------------------------------------------------------------------------
# multi-tenant engine
# ---------------------------------------------------------------------------

def phase_engine(jax):
    import numpy as np
    from repro.launch.mesh import make_wafer_mesh
    from repro.serve.loadgen import PoissonLoadGen, TenantProfile
    from repro.serve.spike_engine import EngineConfig, SpikeEngine
    from repro.serve.tenancy import TenantSpec

    cap = 124                          # the paper's 496 B packet bucket
    cfg = EngineConfig(capacity=cap, link_credits=2 * cap, seg_windows=8,
                       nx=1, ny=1, nz=1)
    tenants = [TenantSpec("quiet", reserve=cap, rate_epw=40.0),
               TenantSpec("hot", reserve=cap // 2, rate_epw=200.0)]
    src = PoissonLoadGen(7, [TenantProfile("quiet", 40.0),
                             TenantProfile("hot", 200.0, burst_factor=2.0,
                                           burst_prob=0.3)], 1, cap)
    eng = SpikeEngine(make_wafer_mesh(1, "w"), "w", tenants, cfg, src)
    t0 = time.perf_counter()
    eng.warmup()
    compile_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    rep = eng.run(6)
    run_s = time.perf_counter() - t0
    check(rep.conservation_checked, "engine: conservation not checked")
    check(np.all(rep.injected == rep.delivered + rep.shed),
          "engine: injected != delivered + shed")
    check(int(rep.delivered.sum()) > 0, "engine delivered nothing")
    log("engine", tenants=len(tenants), capacity=cap, windows=rep.windows,
        injected=rep.injected.tolist(), delivered=rep.delivered.tolist(),
        shed=rep.shed.tolist(), compile_s=compile_s, run_s=run_s,
        note="one run's readings, not benchmark numbers")


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------

def phase_four_chip(jax):
    import numpy as np
    devs = jax.devices()[:4]
    spec, part = partition(4)

    # the per-shard weights alone are 2 x per x N f32 on every device
    w_bytes = 2 * part.per_shard * part.n_neurons * 4
    cfg, init, run = build_sim(spec, part, "alltoall")
    jax.block_until_ready(init(0))
    in_use = [int((d.memory_stats() or {}).get("bytes_in_use", 0))
              for d in devs]
    log("four/placement", bytes_in_use_per_device=in_use,
        weight_bytes_per_shard=w_bytes)
    check(min(in_use) >= w_bytes,
          "per-shard operands are not spread over the four devices")
    del cfg, init, run

    _, s_a2a, v_a2a = run_sim(jax, spec, part, "alltoall")
    _, s_tor, v_tor = run_sim(jax, spec, part, "torus3d",
                              label="torus3d_uncredited")
    for what, s in (("alltoall", s_a2a), ("torus3d", s_tor)):
        check_sim_stats(s, what)
    check((s_a2a.spikes == s_tor.spikes).all(), "spikes differ")
    check((s_a2a.link.delivered_events == s_tor.link.delivered_events)
          .all(), "delivered counts differ")
    check((s_a2a.latency.hist.sum(-1) == s_tor.latency.hist.sum(-1)).all(),
          "latency digests count different events")
    check((v_a2a == v_tor).all(), "final neuron state differs")
    log("four/equivalence", spikes=int(s_a2a.spikes.sum()),
        delivered=int(s_a2a.link.delivered_events.sum()),
        bit_identical=["spikes", "delivered_events", "latency_hist_total",
                       "neuron_v"])

    _, s_cr, _ = run_sim(jax, spec, part, "torus3d",
                         link_credits=LINK_CREDITS, label="torus3d_credited")
    link = s_cr.link
    check((link.offered_events == link.sent_events + link.deferred_events
           + link.parked_events).all(),
          "credited: offered != sent + deferred + parked")
    check((link.sent_events.sum(0) + link.unparked_events.sum(0)
           == link.delivered_events.sum(0)).all(),
          "credited: sent + unparked != delivered")
    check(int(s_cr.deadline_miss.sum()) == 0, "credited: deadline misses")
    log("four/credited", link_credits=LINK_CREDITS,
        deferred=int(link.deferred_events.sum()),
        parked=int(link.parked_events.sum()),
        delivered=int(link.delivered_events.sum()))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chip", action="store_true",
                    help="run only the 4-shard checks on four chips")
    args = ap.parse_args(argv)
    if os.environ.get("REPRO_PALLAS_INTERPRET") is not None:
        print("REPRO_PALLAS_INTERPRET is set: the chip path must compile "
              "the Pallas kernels", file=sys.stderr)
        return 2
    import repro  # noqa: F401  (fails here, before any output, outside a checkout)
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"no TPU: JAX found {dev.platform}", file=sys.stderr)
        return 2
    need = 4 if args.four_chip else 1
    if len(jax.devices()) < need:
        print(f"need {need} chips, JAX found {len(jax.devices())}",
              file=sys.stderr)
        return 2
    log("start", device_kind=dev.device_kind, count=len(jax.devices()),
        jax=jax.__version__, compile_cache=enable_compile_cache(jax))
    if args.four_chip:
        phase_four_chip(jax)
    else:
        phase_sim(jax, dev)
        phase_kernels(jax)
        phase_engine(jax)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
