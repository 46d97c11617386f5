"""Cross-backend fabric invariant fuzz (subprocess, 8 forced host devices).

The transit-buffer fabric (PR 5) has a full invariant set that must hold
for EVERY window of EVERY configuration — this file sweeps it over a
seeded random matrix of traffic, credit budgets and topologies (the seed
matrix is fixed, so CI failures reproduce exactly):

* **conservation with parked** — ``offered == sent + deferred + parked``
  per shard+window; globally ``sum(sent) + sum(unparked) ==
  sum(delivered)``; in-fabric occupancy balances window to window.
* **credit-unit invariance** — ``credits + pending + parked_by_link``
  equals its initial per-link total after every window, through
  ``notify_latency`` 0 and 2, a zero-credit bank, and the end-of-run
  fabric walk + uncredited drain.
* **deferral attribution** — ``deferred == stalled_by_hop.sum()`` with
  every deferral at hop 0 (mid-route shortages park, they never re-enter
  at the source), and parked rows only ever wait at transit hops >= 1.
* **payload custody** — a row delivered N windows after it parked arrives
  bit-exact (the fabric's custody copy, not a re-offer), checked against
  a host-side ledger of every parked row.
* **latency accounting** — the simulator's per-window digest histogram
  counts exactly the delivered events under congestion (waiting + hops +
  queueing), and the queueing term vanishes on an uncontended fabric.

Case generation draws all randomness through the repo's single audited
traffic source, ``repro.serve.loadgen`` (``traffic_rng`` substreams +
``draw_counts``/``draw_payload``) — the same helpers the serving
engine's open-loop load generator uses, so fuzzers and load generation
cannot quietly diverge.  The sweep runs >= 200 seeded cases: 10 fabric
configurations x 20 traffic seeds, plus the simulator-level congestion
runs and the cross-backend equivalence pin (ample credits + empty
buffers => torus2d/torus3d bit-identical to alltoall, latency digests
equal to the hop-only charges — the queueing term contributes exactly
nothing — under the new FabricState carry).

The multi-tenant fabric (``TenantTorusTransport``) gets its own sweep:
per-(tenant, window) conservation, partitioned credit-slot invariance
(reserved slices + shared pool), cross-shard replication and clean
drain; and the serving engine's QoS isolation claim is pinned end to
end (quiet tenant's p99 contended vs solo on identical traffic).

**Chaos mode** (``repro.fabric.faults``): the same invariant set
with a seeded ``chaos`` schedule killing one random physical cable
EVERY window (each dead cable revives next window with p=0.5).  Two
invariants are *adapted* for fault mode — hop-0 parks become legal (an
evicted row whose detour retry also stalls re-parks at its source
holding nothing) and deferral gains the unroutable case (both ring
arcs dirty) — and two are *added*: dead links are frozen (nothing
parked on a dead link after the window it dies) and parked holds
balance exactly (``parked_by_link.sum() == parked_count[hop >= 1]``).
Credit conservation, custody bit-exactness and the clean drain are
unchanged: a fault may delay or detour an event, never corrupt or
leak it.
"""
import os

import pytest

from md_helper import run_md

pytestmark = pytest.mark.slow

TESTS_DIR = os.path.dirname(os.path.abspath(__file__))


def test_fabric_invariant_fuzz_transport_level():
    out = run_md(f"""
import sys
sys.path.insert(0, {TESTS_DIR!r})
""" + r"""
import functools
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro import transport
from repro.serve.loadgen import traffic_rng, draw_counts, draw_payload

D, W, WINDOWS = 8, 6, 3
SEEDS = 20
from repro.launch.mesh import make_wafer_mesh
mesh = make_wafer_mesh(D)
spec = P("wafer")
counts_of = lambda rng: draw_counts(rng, (D, D), 31)
payload_of = lambda rng: draw_payload(rng, (D, D, W))

def make_fns(t):
    def body(lstate, p, c, enforce):
        lstate = jax.tree_util.tree_map(lambda x: x[0], lstate)
        out = t.exchange(lstate, p[0], c[0], axis_name="wafer",
                         enforce_credits=enforce)
        return jax.tree_util.tree_map(
            lambda x: x[None],
            (out.state, out.recv_payload, out.recv_counts, out.sent_mask,
             out.sent_now, out.stats))
    def dbody(lstate):
        lstate = jax.tree_util.tree_map(lambda x: x[0], lstate)
        out = t.drain_fabric(lstate, axis_name="wafer")
        return jax.tree_util.tree_map(
            lambda x: x[None],
            (out.state, out.recv_payload, out.recv_counts, out.stats))
    mk = lambda enforce: jax.jit(jax.shard_map(
        functools.partial(body, enforce=enforce), mesh=mesh,
        in_specs=(spec, spec, spec), out_specs=spec, check_vma=False))
    walk = jax.jit(jax.shard_map(dbody, mesh=mesh, in_specs=(spec,),
                                 out_specs=spec, check_vma=False))
    return mk(True), mk(False), walk

def fuzz_case(fns, t, seed, zero_bank):
    fn, fn_drain, fn_walk = fns
    rng = traffic_rng(seed)
    st0 = t.init_state(W)
    if zero_bank:
        st0 = st0._replace(bank=st0.bank._replace(
            credits=jnp.zeros_like(st0.bank.credits)))
    tot0 = (np.asarray(st0.bank.credits)
            + np.asarray(st0.bank.pending).sum(-1)
            + np.asarray(st0.parked_by_link))
    lstate = jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x, (D,) + x.shape), st0)
    ledger = {}                     # (s, d) -> custody payload row
    pc_prev = np.zeros((D, D), np.int64)
    for win in range(WINDOWS):
        counts = jnp.asarray(counts_of(rng))
        payload = jnp.asarray(payload_of(rng).astype(np.uint32))
        lstate, rp, rcnt, mask, snow, st = fn(lstate, payload, counts)
        off = np.asarray(st.offered_events)
        sent = np.asarray(st.sent_events)
        defr = np.asarray(st.deferred_events)
        park = np.asarray(st.parked_events)
        unpark = np.asarray(st.unparked_events)
        infab = np.asarray(st.in_fabric_events)
        cm, pm = np.asarray(counts), np.asarray(payload)
        # conservation with parked
        assert (off == sent + defr + park).all()
        assert sent.sum() + unpark.sum() == np.asarray(
            st.delivered_events).sum() == np.asarray(rcnt).sum()
        # deferral attribution: hop-0 only; parked rows at hops >= 1
        sbh = np.asarray(st.stalled_by_hop)
        pbh = np.asarray(st.parked_by_hop)
        assert (sbh.sum(-1) == defr).all() and sbh[:, 1:].sum() == 0
        assert (pbh[:, 0] == 0).all()
        assert (pbh.sum(-1) == infab).all()
        held = np.where(np.asarray(mask), 0, cm).sum(1)
        assert (held == defr).all()
        # credit-unit invariance + replication of the global tables
        cr = np.asarray(lstate.bank.credits)
        pend = np.asarray(lstate.bank.pending)
        pbl = np.asarray(lstate.parked_by_link)
        pc = np.asarray(lstate.parked_count)
        ph = np.asarray(lstate.parked_hop)
        assert (cr >= 0).all() and (pbl >= 0).all() and (pc >= 0).all()
        assert (cr == cr[0]).all() and (pend == pend[0]).all()
        assert (pc == pc[0]).all() and (pbl == pbl[0]).all()
        assert (cr[0] + pend[0].sum(-1) + pbl[0] == tot0).all()
        # occupancy balance: parked in, unparked out
        assert (pc[0].sum(1) == pc_prev.sum(1) + park - unpark).all()
        # payload custody: newly parked rows enter the ledger; rows the
        # fabric completed must arrive bit-exact from custody
        fresh_park = (pc[0] > 0) & (pc_prev == 0)
        resumed = (pc_prev > 0) & (pc[0] == 0)
        rp = np.asarray(rp)           # (D_dst, D_src, W)
        snow = np.asarray(snow)
        for s in range(D):
            for d in range(D):
                if fresh_park[s, d]:
                    ledger[(s, d)] = pm[s, d].copy()
                    assert ph[0, s, d] >= 1
                if resumed[s, d]:
                    exp = ledger.pop((s, d))
                    assert (rp[d, s] == exp).all(), (s, d, win)
                elif snow[s, d] and s != d and cm[s, d] > 0:
                    assert (rp[d, s] == pm[s, d]).all(), (s, d, win)
        pc_prev = pc[0].astype(np.int64)
    # end of run: walk the fabric empty, then an uncredited final flush
    lstate, rp, rcnt, st = fn_walk(lstate)
    rp = np.asarray(rp)
    for (s, d), exp in sorted(ledger.items()):
        assert (rp[d, s] == exp).all(), ("drain", s, d)
    assert np.asarray(rcnt).sum() == pc_prev.sum()
    assert (np.asarray(lstate.parked_count) == 0).all()
    assert (np.asarray(lstate.parked_by_link) == 0).all()
    counts = jnp.asarray(counts_of(rng))
    payload = jnp.asarray(payload_of(rng).astype(np.uint32))
    lstate, rp, rcnt, mask, snow, st = fn_drain(lstate, payload, counts)
    assert np.asarray(mask).all()
    assert np.asarray(rcnt).sum() == np.asarray(counts).sum()
    cr = np.asarray(lstate.bank.credits)
    pend = np.asarray(lstate.bank.pending)
    assert (cr[0] + pend[0].sum(-1) == tot0).all()

# fixed seed matrix: 10 fabric configurations x 20 traffic seeds = 200
# seeded cases (zero_bank rides the credits=64 configurations)
CONFIGS = []
for name, opts in [("torus2d", dict(nx=2, ny=4)),
                   ("torus3d", dict(nx=2, ny=2, nz=2))]:
    for credits, nl, zero_bank in [(36, 2, False), (96, 2, False),
                                   (40, 0, False),        # zero-latency
                                   (1 << 20, 2, False),   # ample
                                   (64, 2, True)]:        # zero-credit
        CONFIGS.append((name, opts, credits, nl, zero_bank))

cases = 0
for name, opts, credits, nl, zero_bank in CONFIGS:
    t = transport.create(name, n_shards=D, link_credits=credits,
                         notify_latency=nl, **opts)
    fns = make_fns(t)
    for seed in range(SEEDS):
        try:
            fuzz_case(fns, t, seed, zero_bank)
        except Exception:
            print(f"[fuzz] FAILED {name} credits={credits} nl={nl} "
                  f"zero_bank={zero_bank} seed={seed}")
            raise
        cases += 1
print(f"FUZZ_CASES={cases}")
assert cases >= 200
print("FABRIC_FUZZ_OK")
""", timeout=1200)
    assert "FABRIC_FUZZ_OK" in out


def test_fabric_chaos_fuzz():
    """Chaos mode: a pinned-seed ``chaos`` schedule kills one random
    cable every window (revive p=0.5) while the transport-level
    invariant fuzz runs.  Conservation, credit-unit invariance, payload
    custody and the clean end-of-run drain must all survive; dead links
    must be frozen (``parked_by_link[dead] == 0`` once the mask lands);
    and at least some traffic must actually detour (``rerouted > 0``
    across the sweep)."""
    out = run_md(r"""
import functools
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro import transport
from repro.fabric import chaos, mask_at
from repro.serve.loadgen import traffic_rng, draw_counts, draw_payload

D, W, WINDOWS = 8, 6, 6
SEEDS = 5
from repro.launch.mesh import make_wafer_mesh
mesh = make_wafer_mesh(D)
spec = P("wafer")

def make_fns(t):
    def body(lstate, p, c):
        lstate = jax.tree_util.tree_map(lambda x: x[0], lstate)
        out = t.exchange(lstate, p[0], c[0], axis_name="wafer",
                         enforce_credits=True)
        return jax.tree_util.tree_map(
            lambda x: x[None],
            (out.state, out.recv_payload, out.recv_counts, out.sent_mask,
             out.sent_now, out.stats))
    def dbody(lstate):
        lstate = jax.tree_util.tree_map(lambda x: x[0], lstate)
        out = t.drain_fabric(lstate, axis_name="wafer")
        return jax.tree_util.tree_map(
            lambda x: x[None],
            (out.state, out.recv_payload, out.recv_counts, out.stats))
    fn = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(spec, spec, spec),
                               out_specs=spec, check_vma=False))
    walk = jax.jit(jax.shard_map(dbody, mesh=mesh, in_specs=(spec,),
                                 out_specs=spec, check_vma=False))
    return fn, walk

def chaos_case(fns, t, dims, seed):
    fn, fn_walk = fns
    rng = traffic_rng(seed)
    masks = np.asarray(chaos(dims, WINDOWS, seed).link_down)
    assert masks.any(), "chaos schedule killed nothing"
    st0 = t.init_state(W)
    tot0 = (np.asarray(st0.bank.credits)
            + np.asarray(st0.bank.pending).sum(-1)
            + np.asarray(st0.parked_by_link))
    lstate = jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x, (D,) + x.shape), st0)
    ledger = {}
    pc_prev = np.zeros((D, D), np.int64)
    rer = 0
    for win in range(WINDOWS):
        counts = jnp.asarray(draw_counts(rng, (D, D), 31))
        payload = jnp.asarray(draw_payload(rng, (D, D, W)).astype(np.uint32))
        # stamp this window's fault mask; exchange resets it to None
        down = jnp.broadcast_to(jnp.asarray(masks[win]),
                                (D,) + masks[win].shape)
        lstate = lstate._replace(link_down=down)
        lstate, rp, rcnt, mask, snow, st = fn(lstate, payload, counts)
        off = np.asarray(st.offered_events)
        sent = np.asarray(st.sent_events)
        defr = np.asarray(st.deferred_events)
        park = np.asarray(st.parked_events)
        unpark = np.asarray(st.unparked_events)
        infab = np.asarray(st.in_fabric_events)
        rer += int(np.asarray(st.rerouted).sum())
        cm, pm = np.asarray(counts), np.asarray(payload)
        # conservation with parked — identical to the healthy fuzz
        assert (off == sent + defr + park).all()
        assert sent.sum() + unpark.sum() == np.asarray(
            st.delivered_events).sum() == np.asarray(rcnt).sum()
        # deferral attribution stays hop-0 only (unroutable rows defer at
        # the source, they never HOL-block); parked rows MAY now sit at
        # hop 0 — an evicted row whose detour retry stalled holds nothing
        sbh = np.asarray(st.stalled_by_hop)
        pbh = np.asarray(st.parked_by_hop)
        assert (sbh.sum(-1) == defr).all() and sbh[:, 1:].sum() == 0
        assert (pbh.sum(-1) == infab).all()
        held = np.where(np.asarray(mask), 0, cm).sum(1)
        assert (held == defr).all()
        # credit-unit invariance + replication, unchanged under faults
        cr = np.asarray(lstate.bank.credits)
        pend = np.asarray(lstate.bank.pending)
        pbl = np.asarray(lstate.parked_by_link)
        pc = np.asarray(lstate.parked_count)
        ph = np.asarray(lstate.parked_hop)
        assert (cr >= 0).all() and (pbl >= 0).all() and (pc >= 0).all()
        assert (cr == cr[0]).all() and (pend == pend[0]).all()
        assert (pc == pc[0]).all() and (pbl == pbl[0]).all()
        assert (cr[0] + pend[0].sum(-1) + pbl[0] == tot0).all()
        # dead links are frozen: any row parked on a link the mask just
        # killed was evicted this window, and no chosen route (default or
        # detour) may traverse a dead link
        assert (pbl[0][masks[win]] == 0).all(), win
        # parked holds balance exactly: each transit-parked row (hop >= 1)
        # holds its count on one arrival link, hop-0 parks hold nothing
        assert pbl[0].sum() == pc[0][ph[0] >= 1].sum()
        # occupancy balance: parked in, unparked out
        assert (pc[0].sum(1) == pc_prev.sum(1) + park - unpark).all()
        # payload custody stays bit-exact through eviction + re-park
        fresh_park = (pc[0] > 0) & (pc_prev == 0)
        resumed = (pc_prev > 0) & (pc[0] == 0)
        rp = np.asarray(rp)
        snow = np.asarray(snow)
        for s in range(D):
            for d in range(D):
                if fresh_park[s, d]:
                    ledger[(s, d)] = pm[s, d].copy()
                if resumed[s, d]:
                    exp = ledger.pop((s, d))
                    assert (rp[d, s] == exp).all(), (s, d, win)
                elif snow[s, d] and s != d and cm[s, d] > 0:
                    assert (rp[d, s] == pm[s, d]).all(), (s, d, win)
        pc_prev = pc[0].astype(np.int64)
    # the fabric walk ignores faults (a drained fabric is an operator
    # action): custody drains bit-exact, tables empty, credits conserve
    lstate, rp, rcnt, st = fn_walk(lstate)
    rp = np.asarray(rp)
    for (s, d), exp in sorted(ledger.items()):
        assert (rp[d, s] == exp).all(), ("drain", s, d)
    assert np.asarray(rcnt).sum() == pc_prev.sum()
    assert (np.asarray(lstate.parked_count) == 0).all()
    assert (np.asarray(lstate.parked_by_link) == 0).all()
    cr = np.asarray(lstate.bank.credits)
    pend = np.asarray(lstate.bank.pending)
    assert (cr[0] + pend[0].sum(-1) == tot0).all()
    return rer

cases, rerouted = 0, 0
for name, dims, opts in [("torus2d", (2, 4), dict(nx=2, ny=4)),
                         ("torus3d", (2, 2, 2),
                          dict(nx=2, ny=2, nz=2))]:
    for credits in (48, 96):
        t = transport.create(name, n_shards=D, link_credits=credits,
                             notify_latency=2, **opts)
        fns = make_fns(t)
        for seed in range(SEEDS):
            try:
                rerouted += chaos_case(fns, t, dims, seed)
            except Exception:
                print(f"[chaos] FAILED {name} credits={credits} "
                      f"seed={seed}")
                raise
            cases += 1
print(f"CHAOS_CASES={cases} rerouted={rerouted}")
assert cases >= 20
assert rerouted > 0, "chaos sweep never detoured a single event"
print("FABRIC_CHAOS_OK")
""", timeout=1200)
    assert "FABRIC_CHAOS_OK" in out


def test_fabric_fuzz_simulator_latency_invariants():
    """Congested simulator runs: the latency digest histogram counts
    exactly the delivered events of every window (waiting + hop charges
    + queueing), percentile ordering holds, and the park/resume fabric
    is actually exercised end to end."""
    out = run_md("""
import jax, numpy as np
from repro.snn import microcircuit as mc, network, simulator as sim
spec = mc.MicrocircuitSpec(scale=0.003)
w, is_inh = spec.weight_matrix()
part = network.build_partition(w, is_inh, n_shards=4)
from repro.launch.mesh import make_wafer_mesh
mesh = make_wafer_mesh(4)

for transport, kw in [("torus2d", {}),
                      ("torus3d", dict(torus_nx=1, torus_ny=2,
                                       torus_nz=2))]:
    cfg = sim.SimConfig(n_shards=4, per_shard=part.per_shard,
                        max_fan=part.fanout.shape[1], window=8,
                        ring_len=32, e_max=256, capacity=32,
                        transport=transport, link_credits=32,
                        notify_latency=2, **kw)
    init, runf = sim.build_sharded_sim(mesh, "wafer", cfg, part,
                                       spec.bg_rates())
    exercised = False
    for seed in (0, 1, 2):
        st, stats = runf(init(seed), 10)
        s = jax.tree_util.tree_map(np.asarray, stats)
        link = s.link
        assert (s.latency.hist.sum(-1) == link.delivered_events).all()
        assert (s.latency.max_us >= s.latency.p99_us).all()
        assert (s.latency.p99_us >= s.latency.p50_us).all()
        assert (link.offered_events == link.sent_events
                + link.deferred_events + link.parked_events).all()
        assert ((link.sent_events + link.unparked_events).sum(0)
                == link.delivered_events.sum(0)).all()
        assert (link.stalled_by_hop.sum(-1) == link.deferred_events).all()
        exercised = exercised or (link.parked_events.sum() > 0
                                  and link.unparked_events.sum() > 0)
    assert exercised, transport + ": fabric never parked+resumed"
print("SIM_FUZZ_OK")
""", n_devices=4, timeout=1200)
    assert "SIM_FUZZ_OK" in out


def test_cross_backend_equivalence_ample_credits():
    """With ample credits and empty transit buffers the torus backends
    remain bit-identical to ``alltoall`` — delivered events, guids,
    counts and multicast links — under the new FabricState carry, and
    their latency digests equal the hop-only charges exactly: the
    queueing term contributes nothing on an uncontended fabric."""
    out = run_md("""
import jax, jax.numpy as jnp, numpy as np
from repro import wire
from repro.core import events as ev, routing as rt
from repro.core.exchange import make_exchange
n_shards, N, C, n_addr = 8, 64, 16, 96
from repro.launch.mesh import make_wafer_mesh
mesh = make_wafer_mesh(n_shards)
tabs = []
for s in range(n_shards):
    projs = [rt.Projection(a, a+1, dest_node=(a * 5 + s) % n_shards,
                           dest_links=[a % 3, 7]) for a in range(n_addr)]
    tabs.append(rt.build_tables(n_addr, projs, n_guid=64))
stacked = rt.RoutingTables(
    dest_of_addr=jnp.stack([t.dest_of_addr for t in tabs]),
    guid_of_addr=jnp.stack([t.guid_of_addr for t in tabs]),
    mcast_of_guid=jnp.stack([t.mcast_of_guid for t in tabs]))
addr = jax.random.randint(jax.random.PRNGKey(0), (n_shards, N), 0, n_addr)
ts = jax.random.randint(jax.random.PRNGKey(1), (n_shards, N), 0, 1000)
words = ev.pack(addr, ts)

run_a = make_exchange(mesh, "wafer", n_shards=n_shards, capacity=C,
                      n_addr_per_shard=n_addr, transport="alltoall")
ref = run_a(words, stacked)

from repro.core.torus import Torus
ids = np.arange(n_shards)
for backend, opts, pad in [
    ("torus2d", {"nx": 2, "ny": 4, "link_credits": 1 << 20}, (2, 4, 1)),
    ("torus3d", {"nx": 2, "ny": 2, "nz": 2, "link_credits": 1 << 20},
     (2, 2, 2)),
]:
    run = make_exchange(mesh, "wafer", n_shards=n_shards, capacity=C,
                        n_addr_per_shard=n_addr, transport=backend,
                        transport_opts=opts)
    t = run(words, stacked)
    for field in ("recv_events", "recv_guids", "recv_counts",
                  "link_events"):
        assert (np.asarray(getattr(ref, field))
                == np.asarray(getattr(t, field))).all(), (backend, field)
    assert np.asarray(t.sent_mask).all()
    assert np.asarray(t.link.parked_events).sum() == 0
    assert np.asarray(t.link.in_fabric_events).sum() == 0
    # the carried FabricState leaves the run exactly as it entered:
    # empty tables, full credit conservation
    ls = t.link_state
    assert (np.asarray(ls.parked_count) == 0).all()
    assert (np.asarray(ls.parked_by_link) == 0).all()
    assert (np.asarray(ls.bank.credits)
            + np.asarray(ls.bank.pending).sum(-1) == 1 << 20).all()
    # latency digest == hop-only charges (queueing term exactly zero):
    # recompute the digest per shard from counts and the host hop model
    host = Torus(nx=pad[0], ny=pad[1], nz=pad[2])
    hops = host.hops(ids[:, None], ids[None, :]).astype(np.int64)
    fmt = wire.get_profile("extoll")
    # compiled like the device side, so the f32 mean sums in the same order
    digest = jax.jit(lambda c, h, w: wire.summarize_latency(
        wire.hop_latency_us(fmt, c, h), w))
    for me in range(n_shards):
        cnt = jnp.asarray(np.asarray(t.sent_counts)[me])
        w8 = jnp.where(jnp.arange(n_shards) != me, cnt, 0)
        exp = digest(cnt, jnp.asarray(hops[me]), w8)
        got = jax.tree_util.tree_map(lambda x: x[me], t.latency)
        for a, b in zip(exp, got):
            assert (np.asarray(a) == np.asarray(b)).all(), (backend, me)
print("CROSS_BACKEND_OK")
""")
    assert "CROSS_BACKEND_OK" in out


def test_tenant_fabric_invariant_fuzz():
    """Multi-tenant torus: the single-tenant invariant set extended with
    tenant ids — per (tenant, shard, window) conservation, partitioned
    credit-slot invariance over the ``(T+1)*K`` bank (each tenant's
    reserved slice plus the shared pool balances independently), global
    per-tenant delivery accounting through park/resume, and a clean
    post-drain fabric.  Traffic comes from the shared ``loadgen`` RNG
    helpers, per-(tenant, window) substreams."""
    out = run_md(r"""
import numpy as np
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import flow_control as fc
from repro.transport.torus import TenantTorusTransport
from repro.serve.loadgen import traffic_rng, draw_counts, draw_payload

n, W, WINDOWS, SEEDS = 8, 6, 8, 4
from repro.launch.mesh import make_wafer_mesh
mesh = make_wafer_mesh(n, "w")

CONFIGS = [
    # (reserves, link_credits, notify) — incl. a pure best-effort tenant
    ((24, 8), 64, 2),
    ((16, 12, 0), 48, 2),   # shared pool >= max row: best-effort viable
    ((8, 8), 40, 0),
]

def run_case(part, notify, seed):
    T = part.n_tenants
    tr = TenantTorusTransport(n, (2, 2, 2), partition=part,
                              notify_latency=notify, max_row_events=20)
    counts = np.zeros((WINDOWS, T, n, n), np.int32)
    payload = np.zeros((WINDOWS, T, n, n, W), np.uint32)
    for w in range(WINDOWS):
        for t in range(T):
            rng = traffic_rng(seed, t, w)
            counts[w, t] = draw_counts(rng, (n, n), 20)
            payload[w, t] = draw_payload(rng, (n, n, W))
    state0 = tr.init_state(W)

    def shard_fn(cnts, pays):
        def body(st, x):
            c, p = x
            out = tr.exchange(st, p, c, axis_name="w")
            return out.state, (out.recv_counts, out.stats, out.state)
        st, outs = jax.lax.scan(body, state0, (cnts[0], pays[0]))
        dr = tr.drain_fabric(st, axis_name="w")
        lift = lambda t_: jax.tree.map(lambda a: a[None], t_)
        return lift(outs), lift((dr.state, dr.recv_counts, dr.stats))

    f = jax.jit(jax.shard_map(shard_fn, mesh=mesh, in_specs=(P("w"), P("w")),
                              out_specs=(P("w"), P("w")), check_vma=False))
    cin = jnp.asarray(counts.transpose(2, 0, 1, 3))
    pin = jnp.asarray(payload.transpose(2, 0, 1, 3, 4))
    (rcnt, stats, states), (dstate, dcnt, dstats) = jax.tree.map(
        np.asarray, f(cin, pin))
    limits = np.asarray(fc.partition_limits(part, n * tr.n_links))

    # per (tenant, shard, window) conservation
    off = stats.offered_events       # (n, WINDOWS, T)
    assert (off == stats.sent_events + stats.deferred_events
            + stats.parked_events).all()
    assert (off.sum((0, 1)) == counts.sum((0, 2, 3))).all()
    # partitioned credit-slot invariance after EVERY window, replicated
    cr = states.bank.credits         # (n, WINDOWS, (T+1)K)
    pend = states.bank.pending
    pbl = states.parked_by_link
    assert (cr == cr[:1]).all() and (pbl == pbl[:1]).all()
    assert (cr[0] + pend[0].sum(-1) + pbl[0] == limits[None]).all()
    assert (cr >= 0).all() and (pbl >= 0).all()
    # shared-pool holds of parked rows never exceed their park counts
    hs = states.parked_hold_shared
    assert (hs >= 0).all()
    assert ((hs > 0) <= (states.parked_count > 0)).all()
    # global per-tenant delivery accounting through park/resume
    sent = stats.sent_events.sum((0, 1))
    unp = stats.unparked_events.sum((0, 1))
    deliv = rcnt.sum((0, 1, 3)) + dcnt.sum((0, 2))
    assert (sent + unp + dstats.unparked_events.sum(0) == deliv).all()
    # clean post-drain fabric: empty tables, every credit home or pending
    assert dstate.parked_count.sum() == 0
    assert dstate.parked_by_link.sum() == 0
    assert dstate.parked_hold_shared.sum() == 0
    assert (dstate.bank.credits[0]
            + dstate.bank.pending[0].sum(-1) == limits).all()

cases = 0
for reserves, credits, notify in CONFIGS:
    part = fc.make_partition(credits, reserves)
    for seed in range(SEEDS):
        try:
            run_case(part, notify, seed)
        except Exception:
            print(f"[tenant-fuzz] FAILED reserves={reserves} "
                  f"credits={credits} notify={notify} seed={seed}")
            raise
        cases += 1
print(f"TENANT_FUZZ_CASES={cases}")
print("TENANT_FUZZ_OK")
""", timeout=1200)
    assert "TENANT_FUZZ_OK" in out


@pytest.mark.timeout(1260)
def test_qos_isolation_engine_level():
    """The acceptance claim end to end: a quiet tenant with a burst-sized
    reserved slice, offered IDENTICAL traffic (per-(tenant, window) RNG
    substreams), sees its p99 latency degrade by at most the pinned
    factor when a saturating bursty co-tenant fills the fabric — and the
    co-tenant's overload lands in MEASURED shed, with both tenants'
    ledgers conserving exactly.  (Engine threads run in the subprocess;
    the pytest ``timeout`` marker is the outer belt, ``run_md``'s
    subprocess timeout the inner.)"""
    out = run_md(r"""
import numpy as np
import jax

from repro.serve.loadgen import PoissonLoadGen, TenantProfile
from repro.serve.spike_engine import EngineConfig, SpikeEngine
from repro.serve.tenancy import TenantSpec

QOS_P99_BOUND = 4.0
n = 8
from repro.launch.mesh import make_wafer_mesh
mesh = make_wafer_mesh(n, "w")
tenants = [TenantSpec("quiet", reserve=32, rate_epw=40.0),
           TenantSpec("hot", reserve=8, rate_epw=400.0)]
cfg = EngineConfig(capacity=16, link_credits=64, notify_latency=2,
                   window_us=100.0, seg_windows=4, nx=2, ny=2, nz=2)

def run(hot_rate):
    src = PoissonLoadGen(7, [TenantProfile("quiet", 40.0),
                             TenantProfile("hot", hot_rate,
                                           burst_factor=3.0,
                                           burst_prob=0.25)],
                         n, cfg.capacity)
    eng = SpikeEngine(mesh, "w", tenants, cfg, src)
    rep = eng.run(6)
    assert np.all(rep.injected == rep.delivered + rep.shed)
    return rep

solo = run(0.0)
cont = run(400.0)
# identical quiet traffic in both runs, event for event
assert solo.injected[0] == cont.injected[0] > 0
# the saturating co-tenant overloads measurably...
assert cont.shed[1] > 0
# ...but the quiet tenant keeps its guaranteed service: no shed, and
# p99 within the pinned factor of its solo baseline
assert cont.shed[0] == 0
p99_solo = solo.tenants[0].p99_us
p99_cont = cont.tenants[0].p99_us
assert p99_solo > 0
assert p99_cont <= QOS_P99_BOUND * p99_solo, (p99_cont, p99_solo)
print("p99 solo=%.1fus contended=%.1fus" % (p99_solo, p99_cont))
print("QOS_OK")
""", timeout=1200)
    assert "QOS_OK" in out


def test_recorder_conservation_under_chaos():
    """Flight-recorder conservation with the fabric under fire: a seeded
    ``chaos`` schedule (one random cable killed every window, p=0.5
    revival) on a credit-throttled torus3d, recorder ring in the carry.
    Per shard and per counter the ring's window deltas must sum
    bit-exactly to the end-of-run ``LinkStats`` — faults may defer,
    detour or park an event, but the recorder never miscounts one — and
    the per-link stall attribution lane must keep summing to the global
    deferred total while links die and heal."""
    out = run_md(r"""
import jax, numpy as np
from repro import obs
from repro.fabric import chaos
from repro.snn import microcircuit as mc, network, simulator as sim

spec = mc.MicrocircuitSpec(scale=0.003)
w, is_inh = spec.weight_matrix()
part = network.build_partition(w, is_inh, n_shards=8)
from repro.launch.mesh import make_wafer_mesh
mesh = make_wafer_mesh(8)
dims = (2, 2, 2)
N_WIN = 10
for seed in (0, 1, 2):
    sched = chaos(dims, N_WIN, seed)
    for credits in (16, 32):
        cfg = sim.SimConfig(n_shards=8, per_shard=part.per_shard,
                            max_fan=part.fanout.shape[1], window=8,
                            ring_len=32, e_max=512, capacity=16,
                            transport="torus3d", torus_nx=2, torus_ny=2,
                            torus_nz=2, link_credits=credits,
                            notify_latency=2)
        init, runf = sim.build_sharded_sim(
            mesh, "wafer", cfg, part, spec.bg_rates(),
            fault_schedule=sched,
            recorder=obs.RecorderConfig(depth=N_WIN + 4))
        st, stats, ring = runf(init(seed), N_WIN)
        s = jax.tree_util.tree_map(np.asarray, stats)
        for sh in range(8):
            tot = obs.counter_totals(
                obs.ring_rows(obs.ring_shard(ring, sh)))
            for f in obs.COUNTER_FIELDS:
                want = int(getattr(s.link, f)[sh].sum())
                assert int(tot[f]) == want, (seed, credits, sh, f)
        rows = obs.global_rows(ring, 8)
        sbl = sum(int(np.asarray(r["stalled_by_link"]).sum())
                  for r in rows)
        assert sbl == int(s.link.deferred_events.sum()), (seed, credits)
        # the chaos run actually rerouted (the schedule is not a no-op)
        assert int(s.link.rerouted.sum()) > 0 or seed > 0
print("CHAOS_RECORDER_OK")
""", timeout=1200)
    assert "CHAOS_RECORDER_OK" in out
