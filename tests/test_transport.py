"""Transport subsystem tests (subprocess with forced host devices):

* torus2d AND torus3d deliver bit-identical buckets to the alltoall
  backend (on a (2, 4) and a (2, 2, 2) torus of 8 shards), and their
  lowered HLO contains ONLY neighbor collective-permutes (no all-to-all,
  no all-gather) — the acceptance bar of the torus transport PRs.  With
  credits enabled the count grows by exactly the dimension-wise ring
  all-gather hops and stays permute-only.
* Hop-by-hop credit flow control with in-fabric transit buffers
  conserves events for random traffic and tiny random credit budgets
  across many seeds: offered == sent + deferred + parked per
  shard/window, deferred == stalled_by_hop.sum() (every deferral is a
  hop-0 source-FIFO stall — mid-route shortages PARK in the fabric
  instead), and globally sum(sent) + sum(unparked) == sum(delivered).
  The replicated global FabricState stays bit-identical across shards
  and satisfies credits + pending + parked_by_link == limit on every
  link after every window (credit-unit conservation with held buffer
  credits), including across a multi-window run ended by a fabric-walk
  drain.
* Mid-route resume, deterministically: a row short of credits at hop 1
  of its 3-hop route parks there and resumes at hop 1 — not hop 0 —
  next window, each route link paid exactly once across the two windows.
* CreditBank edge case at transport level: a zero-credit bank defers
  every off-node row (nothing lost, nothing parked — local rows still
  deliver).
* The sharded simulator over torus2d/torus3d reproduces the alltoall
  spike train exactly when uncongested, and under congestion the
  transport-deferral / residue re-offer / park-resume chain balances
  window by window.
"""
import pytest

from md_helper import run_md

pytestmark = pytest.mark.slow


def test_torus_matches_alltoall_and_neighbor_only_hlo():
    out = run_md("""
import jax, jax.numpy as jnp, numpy as np
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

def hlo_counts(run):
    txt = jax.jit(run).lower(words, stacked).as_text()
    return (txt.count("all_to_all") + txt.count("all-to-all"),
            txt.count("all_gather") + txt.count("all-gather"),
            txt.count("collective_permute") + txt.count("collective-permute"))

run_a = make_exchange(mesh, "wafer", n_shards=n_shards, capacity=C,
                      n_addr_per_shard=n_addr, transport="alltoall")
ref = run_a(words, stacked)
assert hlo_counts(run_a)[0] == 1

# data-phase permutes: sum over rings of (n//2 fwd + (n-1)//2 bwd);
# credited runs add the (n-1)-hop-per-ring counts all-gather, still
# permute-only (hop-by-hop admission needs the global offered matrix)
for backend, opts, exp_cp in [
    ("torus2d", {"nx": 2, "ny": 4}, 1 + 3),
    ("torus3d", {"nx": 2, "ny": 2, "nz": 2}, 1 + 1 + 1),
    ("torus2d", {"nx": 2, "ny": 4, "link_credits": 1 << 20}, 4 + 1 + 3),
    ("torus3d", {"nx": 2, "ny": 2, "nz": 2, "link_credits": 1 << 20},
     3 + 1 + 1 + 1),
]:
    run = make_exchange(mesh, "wafer", n_shards=n_shards, capacity=C,
                        n_addr_per_shard=n_addr, transport=backend,
                        transport_opts=opts)
    t = run(words, stacked)
    # bit-identical delivered event multisets (in fact identical buffers)
    assert (np.asarray(ref.recv_events) == np.asarray(t.recv_events)).all()
    assert (np.asarray(ref.recv_guids) == np.asarray(t.recv_guids)).all()
    assert (np.asarray(ref.recv_counts) == np.asarray(t.recv_counts)).all()
    assert (np.asarray(ref.link_events) == np.asarray(t.link_events)).all()
    assert np.asarray(t.sent_mask).all(), (backend, opts)
    # torus wire model: every hop pays -> forwarded bytes >= crossbar bytes
    assert int(np.asarray(t.link.forwarded_bytes).sum()) >= \\
        int(np.asarray(ref.link.forwarded_bytes).sum())
    n_a2a, n_ag, n_cp = hlo_counts(run)
    assert n_a2a == 0, f"{backend} must not lower an all-to-all ({n_a2a})"
    assert n_ag == 0, f"{backend} must not lower an all-gather ({n_ag})"
    assert n_cp == exp_cp, (backend, opts, n_cp, exp_cp)
print("TORUS_EQUIV_OK")
""")
    assert "TORUS_EQUIV_OK" in out


def test_torus_hop_by_hop_credit_conservation_property():
    """offered == sent + deferred + parked per shard+window,
    stalled_by_hop sums to deferred, global sum(sent) + sum(unparked) ==
    sum(delivered), for random traffic against tiny random per-link
    credit budgets, with the fabric state threaded across windows; the
    replicated bank + transit tables stay identical on every shard,
    never go negative, and conserve credit units (credits + pending +
    parked_by_link == limit per link) through the run AND through an
    end-of-run fabric-walk drain."""
    out = run_md("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro import transport
from repro.core import flow_control as fc

D, W = 8, 6
from repro.launch.mesh import make_wafer_mesh
mesh = make_wafer_mesh(D)
spec = P("wafer")

def make_fns(t):
    def body(lstate, p, c, enforce):
        lstate = jax.tree_util.tree_map(lambda x: x[0], lstate)
        out = t.exchange(lstate, p[0], c[0], axis_name="wafer",
                         enforce_credits=enforce)
        return jax.tree_util.tree_map(
            lambda x: x[None], (out.state, out.recv_counts, out.sent_mask,
                                out.stats))
    def dbody(lstate):
        lstate = jax.tree_util.tree_map(lambda x: x[0], lstate)
        out = t.drain_fabric(lstate, axis_name="wafer")
        return jax.tree_util.tree_map(
            lambda x: x[None], (out.state, out.recv_counts, out.stats))
    import functools
    mk = lambda enforce: jax.jit(jax.shard_map(
        functools.partial(body, enforce=enforce), mesh=mesh,
        in_specs=(spec, spec, spec), out_specs=spec, check_vma=False))
    walk = jax.jit(jax.shard_map(dbody, mesh=mesh, in_specs=(spec,),
                                 out_specs=spec, check_vma=False))
    return mk(True), mk(False), walk

rng = np.random.default_rng(0)
for name, opts in [("torus2d", dict(nx=2, ny=4)),
                   ("torus3d", dict(nx=2, ny=2, nz=2))]:
    any_deferred = any_parked = any_resumed = False
    for seed in range(8):
        limit = int(rng.integers(30, 120))
        t = transport.create(name, n_shards=D, link_credits=limit,
                             notify_latency=2, **opts)
        fn, fn_drain, fn_walk = make_fns(t)
        lstate = jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(x, (D,) + x.shape), t.init_state(W))
        for win in range(4):
            counts = jnp.asarray(rng.integers(0, 30, (D, D)), jnp.int32)
            payload = jnp.asarray(
                rng.integers(0, 1 << 31, (D, D, W)), jnp.uint32)
            lstate, rcnt, mask, st = fn(lstate, payload, counts)
            off = np.asarray(st.offered_events)
            sent = np.asarray(st.sent_events)
            defr = np.asarray(st.deferred_events)
            park = np.asarray(st.parked_events)
            unpark = np.asarray(st.unparked_events)
            assert (off == sent + defr + park).all(), (name, seed, win)
            assert (sent.sum() + unpark.sum()
                    == np.asarray(st.delivered_events).sum())
            assert np.asarray(rcnt).sum() == sent.sum() + unpark.sum()
            # every deferral is a hop-0 source-FIFO stall now (mid-route
            # shortages park in the fabric instead of re-entering)
            sbh = np.asarray(st.stalled_by_hop)
            assert (sbh.sum(-1) == defr).all(), (name, seed, win)
            assert sbh[:, 1:].sum() == 0
            # parked rows wait at a transit hop (>= 1), never at hop 0
            pbh = np.asarray(st.parked_by_hop)
            assert (pbh[:, 0] == 0).all()
            assert (pbh.sum(-1) == np.asarray(st.in_fabric_events)).all()
            # deferred rows really were withheld: mask rows account
            held = np.where(np.asarray(mask), 0, np.asarray(counts)).sum(1)
            assert (held == defr).all()
            cr = np.asarray(lstate.bank.credits)
            pend = np.asarray(lstate.bank.pending)
            pbl = np.asarray(lstate.parked_by_link)
            assert (cr >= 0).all() and (pbl >= 0).all()
            # replicated fabric state identical on every shard
            assert (cr == cr[0]).all() and (pend == pend[0]).all()
            pc = np.asarray(lstate.parked_count)
            assert (pc == pc[0]).all() and (pbl == pbl[0]).all()
            # credit-unit conservation on every link: available + in
            # flight as notification + held by a parked row == limit
            assert (cr[0] + pend[0].sum(-1) + pbl[0] == limit).all()
            any_deferred = any_deferred or defr.sum() > 0
            any_parked = any_parked or park.sum() > 0
            any_resumed = any_resumed or unpark.sum() > 0
        # end-of-run drain: walk the fabric empty, then ship the final
        # offers regardless of credits; all held credits return
        lstate, rcnt, st = fn_walk(lstate)
        assert (np.asarray(rcnt).sum()
                == np.asarray(st.unparked_events).sum())
        assert (np.asarray(lstate.parked_count) == 0).all()
        assert (np.asarray(lstate.parked_by_link) == 0).all()
        counts = jnp.asarray(rng.integers(0, 30, (D, D)), jnp.int32)
        payload = jnp.asarray(rng.integers(0, 1 << 31, (D, D, W)),
                              jnp.uint32)
        lstate, rcnt, mask, st = fn_drain(lstate, payload, counts)
        assert np.asarray(mask).all()
        assert np.asarray(rcnt).sum() == np.asarray(counts).sum()
        cr, pend = np.asarray(lstate.bank.credits), \
            np.asarray(lstate.bank.pending)
        assert (cr[0] + pend[0].sum(-1) == limit).all()
    assert any_deferred, name + ": tiny credits never stalled a source"
    assert any_parked, name + ": nothing ever parked mid-route"
    assert any_resumed, name + ": no parked row ever resumed"

# ample credits -> nothing deferred, nothing parked, all delivered
t = transport.create("torus3d", n_shards=D, nx=2, ny=2, nz=2,
                     link_credits=1 << 20, notify_latency=2)
fn, _, _ = make_fns(t)
lstate = jax.tree_util.tree_map(
    lambda x: jnp.broadcast_to(x, (D,) + x.shape), t.init_state(W))
counts = jnp.asarray(rng.integers(0, 30, (D, D)), jnp.int32)
payload = jnp.asarray(rng.integers(0, 1 << 31, (D, D, W)), jnp.uint32)
_, rcnt, mask, st = fn(lstate, payload, counts)
assert np.asarray(mask).all()
assert np.asarray(st.deferred_events).sum() == 0
assert np.asarray(st.parked_events).sum() == 0
assert np.asarray(rcnt).sum() == np.asarray(counts).sum()

# zero-credit bank: every off-node row defers at hop 0 (nothing can even
# enter the fabric, so nothing parks), local rows still deliver, nothing
# lost (offered == deferred + local)
t0 = transport.create("torus3d", n_shards=D, nx=2, ny=2, nz=2,
                      link_credits=64, notify_latency=2)
fn0, _, _ = make_fns(t0)
base0 = t0.init_state(W)
empty = base0._replace(bank=base0.bank._replace(
    credits=jnp.zeros_like(base0.bank.credits)))
lstate = jax.tree_util.tree_map(
    lambda x: jnp.broadcast_to(x, (D,) + x.shape), empty)
counts = jnp.asarray(rng.integers(1, 30, (D, D)), jnp.int32)
payload = jnp.asarray(rng.integers(0, 1 << 31, (D, D, W)), jnp.uint32)
lstate, rcnt, mask, st = fn0(lstate, payload, counts)
local = np.diag(np.asarray(counts))
defr = np.asarray(st.deferred_events)
assert (np.asarray(st.offered_events) == defr + local).all()
assert np.asarray(st.parked_events).sum() == 0
assert (np.asarray(rcnt).sum(1) == local).all()
assert (np.asarray(lstate.bank.credits) == 0).all()
assert (np.asarray(lstate.parked_count) == 0).all()
print("CONSERVATION_OK")
""")
    assert "CONSERVATION_OK" in out


def _advance(state, adm):
    """Apply one admission replay's bank/table updates host-side (the
    same sequence ``TorusTransport.exchange`` performs on device)."""
    from repro.core import flow_control as fc
    bank = fc.credit_tick(state.bank, adm.spent, notify=adm.notify)
    return state._replace(bank=bank, parked_count=adm.park_count,
                          parked_hop=adm.park_hop, parked_age=adm.park_age,
                          parked_by_link=adm.parked_by_link)


def test_admission_round_robin_no_starvation():
    """Two sources contending for the same saturated mid-route link must
    BOTH make progress: the canonical admission order rotates with the
    bank's progress epoch, so the lower-index shard cannot win every
    refund cycle (a delivery = completing fresh OR resuming from park).
    Host-level (``_admit_global`` is collective-free) so the arbitration
    is pinned without a device mesh."""
    import jax.numpy as jnp
    import numpy as np
    from repro.transport.torus import Torus2DTransport

    # (2, 4) torus; routes 0->5 and 1->5 share node (1,0).y+ / (1,1).y+,
    # each with exactly one full row of credits -> one winner per refund
    t = Torus2DTransport(8, nx=2, ny=4, link_credits=16, notify_latency=2,
                         max_row_events=16)
    state = t.init_state(payload_width=4)
    counts = np.zeros((8, 8), np.int32)
    counts[0, 5] = counts[1, 5] = 16
    counts = jnp.asarray(counts)
    wins = np.zeros(8, np.int64)
    for _ in range(7 * 8):          # >= n_shards progress rounds
        adm = t._admit_global(state, counts)
        done = (np.asarray(adm.fresh_complete)
                | np.asarray(adm.resumed_complete))
        wins += done[:, 5]
        state = _advance(state, adm)
    assert wins[0] > 0 and wins[1] > 0, wins[:2]


def test_midroute_park_and_resume_deterministic():
    """A row short of credits at hop 1 of its 3-hop route parks AT hop 1
    — having crossed its source egress link — and next window resumes
    from hop 1, not hop 0: the two windows together traverse each route
    link exactly once (links_traversed sums to the hop count, which is
    what makes ``bytes_on_wire`` charge every link once), the arrival
    link's credit is held while parked and released on departure."""
    import jax.numpy as jnp
    import numpy as np
    from repro.transport.torus import Torus2DTransport

    # (2, 4) torus: route 0 -> 5 is (0,0).x+ then (1,0).y+ then (1,1).y+
    # (3 hops).  Choke the hop-1 link (node 1, direction y+).
    t = Torus2DTransport(8, nx=2, ny=4, link_credits=32, notify_latency=2,
                         max_row_events=32)
    hop1_link = 1 * t.n_links + 2               # node 1, y+ (dirs x+x-y+y-)
    hop0_link = 0 * t.n_links + 0               # node 0, x+
    state = t.init_state(payload_width=4)
    state = state._replace(bank=state.bank._replace(
        credits=state.bank.credits.at[hop1_link].set(0)))
    counts = np.zeros((8, 8), np.int32)
    counts[0, 5] = 8
    counts = jnp.asarray(counts)

    # window 1: the row enters the fabric, crosses hop 0, parks at hop 1
    adm1 = t._admit_global(state, counts)
    assert bool(adm1.fresh_park[0, 5])
    assert not bool(adm1.fresh_complete[0, 5])
    assert int(adm1.stall_hop[0, 5]) == -1, "parked, not deferred"
    assert int(adm1.park_hop[0, 5]) == 1
    assert int(adm1.park_count[0, 5]) == 8
    assert int(adm1.links_traversed[0, 5]) == 1
    # the arrival link (hop 0) holds the row's credits while it waits
    assert int(adm1.parked_by_link[hop0_link]) == 8
    state = _advance(state, adm1)
    assert int(state.bank.credits[hop0_link]) == 32 - 8

    # window 2: un-choke hop 1; the row must resume at hop 1 (charging
    # hops 1 and 2 only) and complete — NOT re-enter at hop 0
    state = state._replace(bank=state.bank._replace(
        credits=state.bank.credits.at[hop1_link].set(32)))
    adm2 = t._admit_global(state, jnp.zeros((8, 8), jnp.int32))
    assert bool(adm2.resumed_complete[0, 5])
    assert int(adm2.resume_age[0, 5]) == 1, "delivered after 1 parked window"
    # a lone row resuming through an otherwise empty fabric is not queued
    # behind anything — least of all its own held events (the queueing
    # gather starts at the blocked hop, past its own arrival link)
    assert int(adm2.queue_events[0, 5]) == 0
    assert int(adm2.links_traversed[0, 5]) == 2
    assert int(adm2.park_count[0, 5]) == 0
    # each of the 3 route links paid exactly once across both windows
    total = int(adm1.links_traversed[0, 5]) + int(adm2.links_traversed[0, 5])
    assert total == int(t.route_hops()[0, 5]) == 3
    # hop 0's credit was NOT re-spent on resume: held 8 released, and no
    # fresh spend hits it in window 2
    assert int(adm2.spent[hop0_link]) == 0
    assert int(adm2.parked_by_link[hop0_link]) == 0
    state = _advance(state, adm2)
    # held credit finishes its notification round-trip: conservation
    cr = np.asarray(state.bank.credits)
    pend = np.asarray(state.bank.pending)
    pbl = np.asarray(state.parked_by_link)
    assert (cr + pend.sum(-1) + pbl == 32).all()


def test_simulator_torus_equivalence_and_backpressure():
    out = run_md("""
import jax, numpy as np
from repro.snn import microcircuit as mc, network, simulator as sim
spec = mc.MicrocircuitSpec(scale=0.003)
w, is_inh = spec.weight_matrix()
part = network.build_partition(w, is_inh, n_shards=4)
from repro.launch.mesh import make_wafer_mesh
mesh = make_wafer_mesh(4)

def run(transport, link_credits=0, capacity=512, n_windows=8, **kw):
    cfg = sim.SimConfig(n_shards=4, per_shard=part.per_shard,
                        max_fan=part.fanout.shape[1], window=8, ring_len=32,
                        e_max=256, capacity=capacity, transport=transport,
                        link_credits=link_credits, notify_latency=2, **kw)
    init, runf = sim.build_sharded_sim(mesh, "wafer", cfg, part,
                                       spec.bg_rates())
    st, stats = runf(init(0), n_windows)
    return jax.tree_util.tree_map(np.asarray, stats)

# 1. uncongested torus2d AND torus3d == alltoall, window for window
#    (torus3d on (1, 2, 2): the Z rings carry the second fold)
sa = run("alltoall")
st = run("torus2d")
s3 = run("torus3d", torus_nx=1, torus_ny=2, torus_nz=2)
assert sa.spikes.sum() > 0
for s in (st, s3):
    assert (sa.spikes == s.spikes).all()
    assert (sa.events_sent == s.events_sent).all()
    assert s.deadline_miss.sum() == 0
    assert s.link.credit_stalls.sum() == 0
    assert (s.link.hops > 0)[:, 1:].all()
assert sa.deadline_miss.sum() == 0
# wire-latency digest rides WindowStats for every backend: the histogram
# accounts exactly the delivered events, and the torus' multi-hop routes
# can only slow the median relative to the single-hop crossbar
for s in (sa, st, s3):
    assert (s.latency.hist.sum(-1) == s.link.delivered_events).all()
    assert (s.latency.p50_us[:, 1:] > 0).all()
    assert (s.latency.max_us >= s.latency.p99_us).all()
    assert (s.latency.p99_us >= s.latency.p50_us).all()
for s in (st, s3):
    assert (s.latency.p50_us >= sa.latency.p50_us).all()

# 2. tiny credits: back-pressure engages; the deferral + park/resume
# chain balances (link_credits must stay >= capacity -- the admission
# invariant)
for transport, kw in [("torus2d", {}),
                      ("torus3d", dict(torus_nx=1, torus_ny=2, torus_nz=2))]:
    sc = run(transport, link_credits=40, capacity=32, n_windows=12, **kw)
    link = sc.link
    assert link.credit_stalls.sum() > 0, transport + ": unexercised"
    assert (link.offered_events == link.sent_events
            + link.deferred_events + link.parked_events).all()
    assert ((link.sent_events + link.unparked_events).sum(0)
            == link.delivered_events.sum(0)).all()
    assert (link.stalled_by_hop.sum(-1) == link.deferred_events).all()
    # in-fabric occupancy balances window to window: parked events enter,
    # unparked events leave, per shard (rows are owned by their source)
    infab_prev = np.concatenate(
        [np.zeros((4, 1), np.int64),
         link.in_fabric_events.astype(np.int64)[:, :-1]], axis=1)
    assert (link.in_fabric_events ==
            infab_prev + link.parked_events - link.unparked_events).all()
    # the exchange at iteration k ships window k-1's aggregated buckets
    assert (link.offered_events[:, 1:] == sc.events_sent[:, :-1]).all()
    assert (link.offered_events[:, 0] == 0).all()
    # transport-deferred events re-enter the same row's aggregation:
    # fresh_k = offered_k - residue_{k-1} - link_deferred_k >= 0
    defr_prev = np.concatenate(
        [np.zeros((4, 1), sc.deferred.dtype), sc.deferred[:, :-1]], axis=1)
    fresh = sc.offered - defr_prev - link.deferred_events
    assert (fresh >= 0).all()
    # aggregation-level identity still balances on every row (parked
    # rows left the caller's custody, so they are "sent" here)
    assert (sc.offered == sc.events_sent + sc.deferred + sc.overflow).all()
    # latency digest stays exact under congestion: every delivered event
    # lands in the histogram (deferred AND parked events are counted on
    # the window that finally delivers them, waiting included)
    assert (sc.latency.hist.sum(-1) == sc.link.delivered_events).all()
print("SIM_TORUS_OK")
""", n_devices=4)
    assert "SIM_TORUS_OK" in out
