"""Core library tests: events, routing, bucket cycle model, aggregator,
flow control, torus — including the paper's §3.1 throughput claims."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import aggregator as agg
from repro.core import bucket as bk
from repro.core import events as ev
from repro.core import flow_control as fc
from repro.core import routing as rt
from repro.core import torus

from md_helper import run_md
from prop import draw, given


# ---------------------------------------------------------------------------
# events
# ---------------------------------------------------------------------------

@given(addr=draw.array((64,), 0, 1 << 14), ts=draw.array((64,), 0, 1 << 15))
def test_event_pack_roundtrip(addr, ts):
    w = ev.pack(jnp.asarray(addr), jnp.asarray(ts))
    a, t, v = ev.unpack(w)
    assert (np.asarray(a) == addr).all()
    assert (np.asarray(t) == ts).all()
    assert np.asarray(v).all()


def test_event_invalid_flag():
    w = ev.pack(jnp.arange(4), jnp.arange(4), valid=jnp.array([1, 0, 1, 0], bool))
    assert (np.asarray(ev.is_valid(w)) == [True, False, True, False]).all()


def test_ts_wraparound_ordering():
    # deadline just past the wrap point is "before" one far in the future
    a = jnp.asarray(10)          # wrapped
    b = jnp.asarray(ev.TS_MASK - 5)
    assert bool(ev.ts_before(b, a))
    assert not bool(ev.ts_before(a, b))
    assert int(ev.ts_slack(a, b)) == 16


def test_packet_cost_paper_constants():
    """The paper's numbers: 496 B payload = 124 events; header overhead
    limits single events to one per two 210 MHz clocks."""
    assert ev.PACKET_MAX_EVENTS == 124
    assert int(ev.wire_cycles(1)) == 2          # 1 event / 2 clocks
    assert int(ev.wire_cycles(124)) == 32       # 3.875 events/clock drained
    assert abs(float(ev.wire_efficiency(124)) - 496 / 512) < 1e-6
    assert int(ev.packet_bytes(0)) == 0


def test_import_starts_no_jax_backend():
    """Importing the package and the benchmark harness leaves JAX without
    a backend: on a TPU only one process may hold the chip, so a process
    that starts JAX children (``benchmarks.run``) must not have taken it
    by importing a module-level device array."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    run_md(
        "import importlib, pkgutil, sys\n"
        f"sys.path.insert(0, {root!r})\n"
        "import repro, benchmarks.run\n"
        "for m in pkgutil.walk_packages(repro.__path__, 'repro.'):\n"
        "    importlib.import_module(m.name)\n"
        "from jax._src import xla_bridge\n"
        "assert not xla_bridge.backends_are_initialized()\n",
        n_devices=1)


# ---------------------------------------------------------------------------
# routing
# ---------------------------------------------------------------------------

def test_routing_tables_and_multicast():
    projs = [rt.Projection(0, 4, dest_node=7, dest_links=[0, 3]),
             rt.Projection(4, 8, dest_node=9, dest_links=[1])]
    tabs = rt.build_tables(16, projs)
    w = ev.pack(jnp.arange(10), jnp.zeros(10, jnp.int32))
    dest, guid, routed = tabs.route(w)
    assert (np.asarray(dest[:4]) == 7).all()
    assert (np.asarray(dest[4:8]) == 9).all()
    assert (np.asarray(dest[8:]) == rt.NO_ROUTE).all()
    assert not np.asarray(routed[8:]).any()
    masks = tabs.multicast(guid[:8])
    assert (np.asarray(masks[:4]) == 0b1001).all()
    assert (np.asarray(masks[4:8]) == 0b0010).all()


def test_multicast_expansion():
    w = ev.pack(jnp.arange(3), jnp.zeros(3, jnp.int32))
    masks = jnp.asarray([0b101, 0b010, 0b000], jnp.uint32)
    links = rt.expand_multicast(w, masks, n_links=3)
    valid = np.asarray(ev.is_valid(links))
    assert valid[0, 0] and not valid[1, 0] and valid[2, 0]
    assert not valid[0, 1] and valid[1, 1] and not valid[2, 1]
    assert not valid[:, 2].any()


# ---------------------------------------------------------------------------
# bucket cycle model (the paper's simulation model)
# ---------------------------------------------------------------------------

def _trace(cfg, T, E, n_dest, seed=0, rate=1.0):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    addr = jax.random.randint(k1, (T, E), 0, 1 << 12)
    ts = (jnp.arange(T)[:, None] + 100 + jax.random.randint(
        k3, (T, E), 0, 50)) & ev.TS_MASK
    valid = jax.random.bernoulli(k2, rate, (T, E))
    words = ev.pack(addr, ts, valid)
    dests = jax.random.randint(jax.random.fold_in(k1, 9), (T, E), 0, n_dest)
    return words, dests


@pytest.mark.parametrize("n_buckets,n_dest", [(4, 4), (4, 16), (8, 64)])
def test_bucket_conservation(n_buckets, n_dest):
    """No event is lost: in == sent + queued + in-bucket + stalled."""
    cfg = bk.BucketConfig(n_buckets=n_buckets, capacity=16, n_dest=n_dest,
                          flush_margin=8)
    words, dests = _trace(cfg, 80, 2, n_dest)
    st, out = bk.run_trace(cfg, words, dests)
    n_in = int(np.asarray(ev.is_valid(words)).sum())
    sent = int(out.sent_count.sum())
    q = int(st.q_count.sum())
    fill = int(st.fill.sum())
    stalled = int(out.stalled.sum())
    assert sent + q + fill + stalled == n_in


def test_bucket_renaming_pressure():
    """More destinations than buckets must still work (paper: 2^16 dests,
    few buckets, map table + free list + urgent eviction)."""
    cfg = bk.BucketConfig(n_buckets=2, capacity=8, n_dest=32, flush_margin=4)
    words, dests = _trace(cfg, 60, 1, 32)
    st, out = bk.run_trace(cfg, words, dests)
    # every sent packet has a valid destination and consistent count
    sent_mask = np.asarray(out.sent_dest) >= 0
    counts = np.asarray(out.sent_count)[sent_mask]
    assert (counts > 0).all() and (counts <= 8).all()
    # the map table only binds existing buckets
    mt = np.asarray(st.map_table)
    assert ((mt == -1) | ((mt >= 0) & (mt < 2))).all()


def test_bucket_sent_events_match_destination():
    cfg = bk.BucketConfig(n_buckets=4, capacity=8, n_dest=8, flush_margin=8)
    # dest = addr % 8 so we can verify routing of flushed payloads
    T, E = 50, 2
    k = jax.random.PRNGKey(3)
    addr = jax.random.randint(k, (T, E), 0, 64)
    ts = (jnp.arange(T)[:, None] + 60) & ev.TS_MASK
    words = ev.pack(addr, jnp.broadcast_to(ts, (T, E)))
    dests = addr % 8
    st, out = bk.run_trace(cfg, words, dests)
    sd = np.asarray(out.sent_dest)
    se = np.asarray(out.sent_events)
    sc = np.asarray(out.sent_count)
    for t in range(T):
        if sd[t] < 0:
            continue
        payload = se[t][: sc[t]]
        a = (payload >> ev.TS_BITS) & ev.ADDR_MASK
        assert ((a % 8) == sd[t]).all()


def test_paper_claim_single_event_rate():
    """Un-aggregated traffic to all-different destinations drains at
    ~0.5 events/cycle (one event per two clocks, paper §3.1)."""
    cfg = bk.BucketConfig(n_buckets=8, capacity=124, n_dest=256,
                          flush_margin=10_000)   # deadline fires instantly
    T = 400
    addr = jnp.arange(T).reshape(T, 1) % 256
    ts = jnp.full((T, 1), 1, jnp.int32)          # already-urgent deadlines
    words = ev.pack(addr, ts)
    dests = addr                                  # every event its own dest
    st, out = bk.run_trace(cfg, words, dests)
    sent = int(out.sent_count.sum())
    rate = sent / T
    assert rate <= 0.55, f"single-event rate {rate} should be <= ~0.5"
    assert rate >= 0.3


def test_paper_claim_aggregated_rate():
    """Same-destination traffic aggregates into big packets and keeps up
    with one event/cycle input (the paper's fix)."""
    cfg = bk.BucketConfig(n_buckets=4, capacity=124, n_dest=4,
                          flush_margin=4, queue=8)
    T = 600
    addr = jnp.zeros((T, 1), jnp.int32)
    ts = (jnp.arange(T).reshape(T, 1) + 200) & ev.TS_MASK   # relaxed deadlines
    words = ev.pack(addr, ts)
    dests = jnp.zeros((T, 1), jnp.int32)
    st, out = bk.run_trace(cfg, words, dests)
    stalled = int(out.stalled.sum())
    sent = int(out.sent_count.sum()) + int(st.q_count.sum()) + int(st.fill.sum())
    assert stalled == 0, "aggregated stream should absorb 1 event/cycle"
    assert sent == T
    # and the packets are large (amortized headers)
    counts = np.asarray(out.sent_count)
    big = counts[counts > 0]
    assert big.mean() > 30


# ---------------------------------------------------------------------------
# aggregator
# ---------------------------------------------------------------------------

@given(n=draw.ints(1, 300), d=draw.ints(1, 70), c=draw.ints(1, 130),
       seed=draw.ints(0, 10_000))
def test_aggregate_impls_agree(n, d, c, seed):
    k1, k2, k3, k4 = jax.random.split(jax.random.PRNGKey(seed), 4)
    words = ev.pack(jax.random.randint(k1, (n,), 0, 1 << 14),
                    jax.random.randint(k2, (n,), 0, 1 << 15),
                    valid=jax.random.bernoulli(k4, 0.85, (n,)))
    dest = jax.random.randint(k3, (n,), -2, d)
    guid = jax.random.randint(k4, (n,), 0, 100)
    b1 = agg.aggregate(words, dest, guid, d, c, impl="onehot")
    for impl in ("sort", "fused"):
        b2 = agg.aggregate(words, dest, guid, d, c, impl=impl)
        assert (b1.counts == b2.counts).all(), impl
        assert (b1.data == b2.data).all(), impl
        assert (b1.guids == b2.guids).all(), impl
        assert int(b1.overflow) == int(b2.overflow), impl
    # conservation: accepted + overflow == valid routed events
    valid = np.asarray(ev.is_valid(words) & (dest >= 0) & (dest < d))
    assert int(b1.counts.sum()) + int(b1.overflow) == valid.sum()


def test_aggregate_window_order():
    words = ev.pack(jnp.arange(6), jnp.arange(6))
    dest = jnp.asarray([1, 1, 0, 1, 0, 1])
    b = agg.aggregate(words, dest, None, 2, 3, impl="onehot")
    # destination 1 gets events 0,1,3 in order; 5 overflows
    a = (np.asarray(b.data[1]) >> ev.TS_BITS) & ev.ADDR_MASK
    assert list(a[:3]) == [0, 1, 3]
    assert int(b.overflow) == 1


def test_overflow_mask_matches_aggregate():
    words = ev.pack(jnp.arange(10), jnp.zeros(10, jnp.int32))
    dest = jnp.zeros(10, jnp.int32)
    m = agg.overflow_mask(words, dest, 4, 6)
    assert np.asarray(m).sum() == 4
    b = agg.aggregate(words, dest, None, 4, 6)
    assert int(b.overflow) == 4


def test_window_cost_model():
    c = agg.window_cost(jnp.asarray([124, 1, 0, 248]))
    assert int(c.packets) == 1 + 1 + 0 + 2
    un = agg.unaggregated_cost(125)
    assert int(un.cycles) == 125 * 2
    assert float(c.efficiency) > float(un.efficiency)


# ---------------------------------------------------------------------------
# flow control
# ---------------------------------------------------------------------------

@given(size=draw.ints(2, 64), lat=draw.ints(1, 16))
def test_ring_never_overruns(size, lat):
    cfg = fc.RingConfig(size=size, notify_latency=lat)
    st, stats = fc.run(cfg, 300, produce_rate=1.0, consume_rate=1)
    assert int(stats.produced) <= 300
    # rd never passes wr; credits never negative (invariants)
    assert int(st.rd) <= int(st.wr)
    assert int(st.credits) >= 0
    assert int(stats.produced) == int(stats.consumed) + (int(st.wr) - int(st.rd))


def test_ring_throughput_credit_limit():
    """Sustained throughput = min(1, size / notify_latency) (credit loop)."""
    full = fc.run(fc.RingConfig(size=32, notify_latency=8), 1000)[1]
    starved = fc.run(fc.RingConfig(size=4, notify_latency=8), 1000)[1]
    assert int(full.produced) >= 990
    ratio = int(starved.produced) / 1000
    assert 0.35 <= ratio <= 0.65, ratio     # ~ 4/8 with batching effects


def test_credit_bank_zero_initial_credits():
    """A bank that starts empty can never be spent from — the caller must
    defer everything (spent=0) and the bank stays empty forever: nothing
    is lost, nothing is created."""
    bank = fc.init_credits(4, 0, 2)
    for _ in range(5):
        bank = fc.credit_tick(bank, jnp.zeros((4,), jnp.int32))
        assert (np.asarray(bank.credits) == 0).all()
        assert (np.asarray(bank.pending) == 0).all()


def test_credit_bank_zero_notify_latency():
    """notify_latency=0 -> the refund is immediate: credit_tick with any
    legal spend leaves the bank unchanged (credits cap one window's
    traffic but nothing carries across windows)."""
    bank = fc.init_credits(3, 10, 0)
    assert bank.pending.shape == (3, 0)
    out = fc.credit_tick(bank, jnp.asarray([10, 3, 0], jnp.int32))
    assert (np.asarray(out.credits) == 10).all()
    # contrast: latency 1 delays the refund exactly one tick
    b1 = fc.init_credits(3, 10, 1)
    spent = jnp.asarray([10, 3, 0], jnp.int32)
    b1 = fc.credit_tick(b1, spent)
    assert list(np.asarray(b1.credits)) == [0, 7, 10]
    b1 = fc.credit_tick(b1, jnp.zeros((3,), jnp.int32))
    assert (np.asarray(b1.credits) == 10).all()


@given(lat=draw.ints(1, 6), seed=draw.ints(0, 1 << 16))
def test_credit_bank_conservation_invariant(lat, seed):
    """credits + pending.sum() is invariant under credit_tick for any
    legal spend sequence (spent <= credits), and credits never go
    negative — the identity the hop-by-hop transport banks rely on."""
    rng = np.random.default_rng(seed)
    limit = int(rng.integers(1, 50))
    bank = fc.init_credits(5, limit, lat)
    for _ in range(4 * lat):
        avail = np.asarray(bank.credits)
        spent = rng.integers(0, avail + 1).astype(np.int32)
        bank = fc.credit_tick(bank, jnp.asarray(spent))
        total = np.asarray(bank.credits) + np.asarray(bank.pending).sum(-1)
        assert (total == limit).all()
        assert (np.asarray(bank.credits) >= 0).all()


# ---------------------------------------------------------------------------
# torus
# ---------------------------------------------------------------------------

def test_torus_route_and_hops():
    t = torus.Torus(4, 4, 4)
    for (s, d) in [(0, 63), (5, 5), (1, 62), (17, 3)]:
        path = t.route(s, d)
        assert path[0] == s and path[-1] == d
        assert len(path) - 1 == int(t.hops(s, d))
        # consecutive nodes differ by one ring step
        for u, v in zip(path[:-1], path[1:]):
            assert int(t.hops(u, v)) == 1


def test_torus_hops_symmetric_and_wrap():
    t = torus.Torus(4, 2, 2)
    s = np.arange(t.n_nodes)
    for d0 in range(t.n_nodes):
        assert (t.hops(s, d0) == t.hops(d0, s)).all()
    # wrap: node 0 -> 3 on the x ring is 1 hop
    assert int(t.hops(0, 3)) == 1


def test_wafer_topology_paper_constants():
    assert torus.FPGAS_PER_WAFER == 48
    assert torus.CONCENTRATORS_PER_WAFER == 8
    assert torus.FPGAS_PER_CONCENTRATOR == 6
    assert abs(torus.LINK_GBYTES - 12.6) < 1e-9
    t = torus.wafer_topology(4)
    assert t.n_nodes == 32


def test_link_loads_conserve_traffic():
    t = torus.Torus(2, 2, 2)
    m = np.zeros((8, 8))
    m[0, 7] = 100.0
    loads = t.link_loads(m)
    assert sum(loads.values()) == 100.0 * t.hops(0, 7)


def test_link_loads_vectorized_matches_scalar_oracle():
    """The batched numpy link_loads must reproduce the per-pair routed
    oracle exactly — same links, same bytes — across ring shapes that
    exercise wraps, ties (even rings) and degenerate axes, in 2-D AND
    3-D: the Z-axis walk is the path the torus3d transport's credit
    accounting relies on, so Z-dominant shapes (long wafer stacks, odd
    and even Z rings for both tie-break branches) are covered
    explicitly."""
    rng = np.random.default_rng(7)
    z_exercised = 0
    for shape in [(2, 2, 2), (2, 4, 3), (1, 5, 1), (2, 4, 1), (3, 3, 3),
                  (4, 4, 2),
                  # Z-dominant: the wafer-stacking axis is the longest ring
                  (2, 2, 5), (1, 2, 6), (2, 4, 4), (1, 1, 7)]:
        t = torus.Torus(*shape)
        n = t.n_nodes
        m = rng.random((n, n)) * (rng.random((n, n)) < 0.4)
        np.fill_diagonal(m, 0)
        got = t.link_loads(m)
        want = t.link_loads_scalar(m)
        assert set(got) == set(want), shape
        for k in want:
            assert abs(got[k] - want[k]) < 1e-9, (shape, k)
        # every link is a single ring hop
        for (u, v) in got:
            assert int(t.hops(u, v)) == 1, (shape, u, v)
        # the Z axis really carried traffic (directions 4/5), both ways
        if shape[2] > 1:
            zdirs = {t.link_dir(u, v) for (u, v) in got
                     if t.link_dir(u, v) >= 4}
            z_exercised += len(zdirs)
    assert z_exercised >= 8, "Z-axis links barely exercised"


def test_route_links_matches_route():
    """route_links enumerates exactly the (node, direction) egress links
    of the dimension-ordered route — the credit-spending unit of the
    hop-by-hop torus transports."""
    t = torus.Torus(2, 4, 3)
    rng = np.random.default_rng(3)
    for _ in range(50):
        s, d = (int(v) for v in rng.integers(0, t.n_nodes, 2))
        links = t.route_links(s, d)
        path = t.route(s, d)
        assert len(links) == len(path) - 1 == int(t.hops(s, d))
        for (u, dir_), exp_u, exp_v in zip(links, path[:-1], path[1:]):
            assert u == exp_u
            # stepping u one hop along dir_ lands on the next path node
            x, y, z = (int(c) for c in t.coords(u))
            step = [(1, 0, 0), (-1, 0, 0), (0, 1, 0), (0, -1, 0),
                    (0, 0, 1), (0, 0, -1)][dir_]
            nxt = t.node_id((x + step[0]) % t.nx, (y + step[1]) % t.ny,
                            (z + step[2]) % t.nz)
            assert int(nxt) == exp_v


def test_link_loads_multiwafer_scale():
    """The vectorized path must handle a multi-wafer torus (the scale the
    scalar loop cannot): conservation of traffic-bytes x hops."""
    t = torus.wafer_topology(16)            # 2 x 4 x 16 = 128 nodes
    n = t.n_nodes
    m = torus.microcircuit_traffic(n, 1e6)
    loads = t.link_loads(m)
    ids = np.arange(n)
    s, d = np.meshgrid(ids, ids, indexing="ij")
    want = float((m * t.hops(s, d)).sum())
    assert abs(sum(loads.values()) - want) < 1e-6 * max(want, 1.0)
