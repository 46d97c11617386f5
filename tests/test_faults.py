"""Fault-injection layer: schedules, host routing oracles, reroute-around.

Three tiers:

* **Host oracles** (plain numpy, no devices) — ``repro.fabric.faults``
  schedule constructors are deterministic and shaped right, and the
  ``core.torus`` detour helpers agree with the primary router:
  ``route_links_detour`` with no flips IS ``route_links``, and
  ``route_links_avoiding`` never routes through a dead link.
* **Fast-tier smoke** (4 devices, runs in the default ``not slow``
  tier) — one deterministic single-link-down case on a 2x2 torus:
  conservation holds, the fabric detours (``rerouted > 0``), the drain
  walks empty and every credit comes home.
* **Slow liveness property** (8 devices) — with one permanently-dead
  cable and ample credits, EVERY offered event is delivered the same
  window via a detour (none lost, none stuck), the dead cable is never
  spent, and ``rerouted > 0`` is pinned on both torus2d and torus3d.

The transport-level *chaos* sweep (a cable killed every window) lives
with the rest of the invariant fuzz in ``test_fabric_fuzz.py``; the
engine-level mid-segment link death is in ``test_serve_engine.py``.
"""
import numpy as np
import pytest

from md_helper import run_md


# -- host oracles (no devices) ----------------------------------------------

def test_fault_schedule_constructors():
    from repro.fabric import (chaos, healthy, link_fault, link_flap,
                              n_fabric_links, node_fault)
    dims = (2, 2, 2)
    K = n_fabric_links(dims)
    assert K == 8 * 6

    h = healthy(dims, 4)
    assert h.link_down.shape == (4, K) and not np.asarray(h.link_down).any()

    lf = np.asarray(link_fault(dims, 6, 0, 0, start=2, stop=5).link_down)
    assert (lf.sum(1) == [0, 0, 2, 2, 2, 0]).all()   # one cable = 2 links

    fl = np.asarray(link_flap(dims, 8, 0, 0, period=2).link_down)
    assert (fl.sum(1) == [2, 2, 0, 0, 2, 2, 0, 0]).all()

    nf = np.asarray(node_fault(dims, 4, 3, start=1).link_down)
    # 6 incident cables, each killing both directed channels
    assert nf[0].sum() == 0 and (nf[1:].sum(1) == 12).all()

    c1, c2 = chaos(dims, 16, seed=5), chaos(dims, 16, seed=5)
    assert (np.asarray(c1.link_down) == np.asarray(c2.link_down)).all()
    assert c1.link_down.shape == (16, K)
    # every window has at least the freshly-killed cable down
    assert (np.asarray(c1.link_down).sum(1) >= 2).all()
    # a different seed gives a different run
    assert (np.asarray(chaos(dims, 16, seed=6).link_down)
            != np.asarray(c1.link_down)).any()


def test_mask_at_clamps_to_schedule():
    import jax.numpy as jnp
    from repro.fabric import link_fault, mask_at
    sched = link_fault((2, 2), 4, 0, 0, start=3)
    assert not np.asarray(mask_at(sched, 0)).any()
    assert np.asarray(mask_at(sched, 3)).sum() == 2
    # windows past the table clamp to the last row: permanent stays dead
    assert np.asarray(mask_at(sched, jnp.int32(99))).sum() == 2


def test_cable_links_pairs_reverse_channel():
    from repro.fabric import cable_links, link_id
    dims = (2, 2, 2)
    for node in range(8):
        for direction in range(6):
            a, b = cable_links(dims, node, direction)
            assert a == link_id(dims, node, direction)
            # the cable is symmetric: the neighbor's reverse channel
            # names the same physical cable from the other end
            v, rdir = b // 6, b % 6
            assert cable_links(dims, v, rdir) == (b, a)


def test_route_links_detour_no_flips_is_primary_route():
    from repro.core.torus import Torus
    for torus, n in [(Torus(2, 4, 1), 8), (Torus(2, 2, 2), 8)]:
        for s in range(n):
            for d in range(n):
                assert (torus.route_links_detour(s, d)
                        == torus.route_links(s, d)), (s, d)


def test_route_links_avoiding_never_uses_dead_links():
    from repro.core.torus import Torus
    from repro.fabric import cable_links
    rng = np.random.default_rng(0)
    torus, dims = Torus(2, 2, 2), (2, 2, 2)
    found_detour = False
    for _ in range(200):
        down = set()
        for _ in range(int(rng.integers(0, 3))):
            node = int(rng.integers(0, 8))
            direction = int(rng.integers(0, 6))
            for l in cable_links(dims, node, direction):
                down.add((l // 6, l % 6))
        s, d = int(rng.integers(0, 8)), int(rng.integers(0, 8))
        got = torus.route_links_avoiding(s, d, down)
        if got is None:
            continue
        links, flips = got
        assert not any(l in down for l in links), (s, d, links)
        found_detour = found_detour or any(flips)
    assert found_detour, "sweep never exercised a long-way detour"


# -- fast-tier smoke: deterministic single link down (4 devices) -------------

def test_single_link_down_smoke():
    """One cable dies on a 2x2 torus at window 1: traffic detours the
    long way around its ring, conservation and the credit-unit identity
    hold every window, and the post-run drain leaves an empty fabric.
    Deterministic (fixed traffic seed + static schedule); runs in the
    fast tier as the belt for the slow chaos sweep."""
    out = run_md(r"""
import functools
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro import transport
from repro.fabric import link_fault, mask_at
from repro.serve.loadgen import traffic_rng, draw_counts

n, W, n_win, credits = 4, 4, 8, 8
t = transport.create("torus2d", n_shards=n, nx=2, ny=2,
                     link_credits=credits, notify_latency=2)
sched = link_fault((2, 2), n_win, 0, 0, start=1)
from repro.launch.mesh import make_wafer_mesh
mesh = make_wafer_mesh(n, "w")

@functools.partial(jax.shard_map, mesh=mesh, in_specs=(P("w"), P("w")),
                   out_specs=P("w"), check_vma=False)
def body(counts, win_ids):
    state = t.init_state(payload_width=W)
    def step(state, x):
        cnt, w = x
        st = state._replace(link_down=mask_at(sched, w))
        out = t.exchange(st, jnp.zeros((n, W), jnp.uint32), cnt,
                         axis_name="w")
        return out.state, out.stats
    state, stats = jax.lax.scan(step, state, (counts[0], win_ids[0]))
    dr = t.drain_fabric(state, axis_name="w")
    return jax.tree.map(lambda x: x[None],
                        (stats, dr.recv_counts, dr.state))

rng = traffic_rng(11)
counts = np.stack([draw_counts(rng, (n, n), 7) for _ in range(n_win)])
counts = jnp.asarray(counts.transpose(1, 0, 2))          # (n, n_win, n)
win_ids = jnp.tile(jnp.arange(n_win)[None], (n, 1))
stats, drc, dstate = jax.tree.map(np.asarray,
                                  jax.jit(body)(counts, win_ids))

assert (stats.offered_events == stats.sent_events
        + stats.deferred_events + stats.parked_events).all()
delivered = int(stats.delivered_events.sum()) + int(drc.sum())
sent_all = int(stats.sent_events.sum() + stats.unparked_events.sum()
               + drc.sum())
assert delivered == sent_all
assert int(stats.rerouted.sum()) > 0, "no detour around the dead cable"
assert (dstate.parked_count == 0).all()
assert (dstate.parked_by_link == 0).all()
assert (dstate.bank.credits[0] + dstate.bank.pending[0].sum(-1)
        == credits).all()
print("delivered=%d rerouted=%d" % (delivered, int(stats.rerouted.sum())))
print("SINGLE_LINK_DOWN_OK")
""", n_devices=4, timeout=600)
    assert "SINGLE_LINK_DOWN_OK" in out


# -- slow liveness property (8 devices, both backends) -----------------------

@pytest.mark.slow
def test_liveness_dead_link_ample_credits():
    """The reroute-around liveness claim: one permanently-dead cable +
    ample credits => every offered event is delivered in its own window
    via a detour — nothing defers, parks, or gets lost — the dead
    cable's credit slots are never touched, and ``rerouted > 0`` is
    pinned.  Both torus2d and torus3d."""
    out = run_md(r"""
import functools
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro import transport
from repro.fabric import cable_links, link_fault, mask_at
from repro.serve.loadgen import traffic_rng, draw_counts

D, W, n_win = 8, 4, 6
AMPLE = 1 << 16
from repro.launch.mesh import make_wafer_mesh
mesh = make_wafer_mesh(D, "w")

for name, dims, opts in [("torus2d", (2, 4), dict(nx=2, ny=4)),
                         ("torus3d", (2, 2, 2), dict(nx=2, ny=2, nz=2))]:
    t = transport.create(name, n_shards=D, link_credits=AMPLE,
                         notify_latency=2, **opts)
    sched = link_fault(dims, n_win, 0, 0)
    dead = list(cable_links(dims, 0, 0))

    @functools.partial(jax.shard_map, mesh=mesh, in_specs=(P("w"), P("w")),
                       out_specs=P("w"), check_vma=False)
    def body(counts, win_ids):
        state = t.init_state(payload_width=W)
        def step(state, x):
            cnt, w = x
            st = state._replace(link_down=mask_at(sched, w))
            out = t.exchange(st, jnp.zeros((D, W), jnp.uint32), cnt,
                             axis_name="w")
            return out.state, out.stats
        state, stats = jax.lax.scan(step, state, (counts[0], win_ids[0]))
        return jax.tree.map(lambda x: x[None], (stats, state))

    rng = traffic_rng(23)
    counts = np.stack([draw_counts(rng, (D, D), 15) for _ in range(n_win)])
    counts = jnp.asarray(counts.transpose(1, 0, 2))
    win_ids = jnp.tile(jnp.arange(n_win)[None], (D, 1))
    stats, state = jax.tree.map(np.asarray, jax.jit(body)(counts, win_ids))

    # liveness: with ample credits the detour admits everything — every
    # offered event is delivered the window it was offered
    assert (stats.sent_events == stats.offered_events).all()
    assert stats.deferred_events.sum() == 0
    assert stats.parked_events.sum() == 0
    assert (stats.delivered_events.sum(0)
            == stats.sent_events.sum(0)).all()
    rer = int(stats.rerouted.sum())
    assert rer > 0, name + ": no detours despite a dead cable"
    # the dead cable is never spent: its credit slots sit untouched
    assert (state.bank.credits[0, dead] == AMPLE).all()
    assert (state.bank.pending[0, dead] == 0).all()
    print("%s: delivered=%d rerouted=%d" %
          (name, int(stats.delivered_events.sum()), rer))
print("LIVENESS_OK")
""", timeout=1200)
    assert "LIVENESS_OK" in out
