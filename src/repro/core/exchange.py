"""Multi-shard spike exchange — the JAX-native Extoll fabric (paper §3).

One "wafer shard" per mesh device along a named axis.  A flush window is:

  1. **route+aggregate** — the fused window kernel
                   (``repro.kernels.fused_route_bucket``): source LUT
                   lookup (§3, LUT 1) and destination-bucketed binning with
                   static capacity (§3.1) in one sort-based pass
  2. **transport**  — a pluggable backend (``repro.transport``) ships every
                   bucket to its owner; each (event, guid) pair is one
                   64-bit wire word (``repro.wire.codec``), and the
                   backend's ``WireFormat`` profile prices the window
                   (frame-exact ``bytes_on_wire``, per-hop latency):

                   * ``"alltoall"`` — wire words|counts packed into ONE
                     ``(n_shards, 2·capacity+1)`` u32 buffer, one global
                     ``all_to_all`` per window; the fabric as a crossbar,
                     paying the latency-bound hop once, exactly like the
                     paper amortizes the Extoll packet header over a bucket.
                   * ``"torus2d"`` / ``"torus3d"`` — torus-faithful: shards
                     fold onto a 2-D (x, y) or 3-D (x, y, z) device torus
                     and each window travels via dimension-ordered neighbor
                     ``ppermute`` hops (X rings, then Y, then Z — the wafer
                     axis) through store-and-forward buffers, governed by
                     hop-by-hop credit-based link flow control (§2.1's
                     notification credits, on EVERY egress link of the
                     route — transit links included).  The lowered HLO
                     contains only neighbor collective-permutes — per-link
                     hop latency, bandwidth and mid-route back-pressure
                     become visible (``LinkStats``) instead of being
                     averaged away by a global collective.

  3. **multicast** — destination-side GUID lookup -> multicast mask,
                   replaying events onto local HICANN links       (§3, LUT 2)

All stages run inside ``shard_map`` so the collectives are explicit and the
roofline's collective term can be read straight off the lowered HLO.

Overflow and back-pressure share one policy: events beyond a bucket's
capacity — and, under the torus backends, whole buckets refused by a
congested link anywhere on their route (``sent_mask``) — are *deferred* to
the next window through the
caller's residue machinery rather than buffered unboundedly in the fabric.
Tests assert conservation at both levels: aggregation
(``offered == sent + deferred + dropped``) and transport
(``offered == sent + deferred``, globally ``sum(sent) == sum(delivered)``).
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import transport as tp
from repro import wire
from repro.core import aggregator, events as ev
from repro.core.routing import RoutingTables


class ExchangeOut(NamedTuple):
    """Per-shard result of one flush window (shapes are per-shard)."""

    recv_events: jax.Array   # (n_shards, C) u32 events received per source
    recv_guids: jax.Array    # (n_shards, C) i32
    recv_counts: jax.Array   # (n_shards,) i32
    link_events: jax.Array   # (n_links, n_shards*C) u32 after multicast
    sent_counts: jax.Array   # (n_shards,) i32 events sent per destination
    overflow: jax.Array      # () i32 events beyond bucket capacity
    wire_bytes: jax.Array    # () i32 off-shard bytes this window (all hops)
    sent_mask: jax.Array     # (n_shards,) bool False = bucket row deferred
                             #   by link flow control (re-offer next window)
    link: tp.LinkStats       # per-window link-level stats (incl. the exact
                             #   frame-level bytes_on_wire of the backend's
                             #   WireFormat profile)
    link_state: tp.LinkState  # advanced credit state (thread across windows)
    latency: wire.LatencySummary  # wire-latency digest of this shard's
                             #   off-shard rows DELIVERED this window: per
                             #   traversed link, switch latency + frame
                             #   serialization, plus the queueing dwell
                             #   behind parked in-fabric traffic
                             #   (repro.wire.latency; no waiting term — a
                             #   one-shot window has none)


def exchange_window(
    words: jax.Array,                 # (N,) u32 this shard's new events
    tables: RoutingTables,
    *,
    axis_name: str,
    n_shards: int,
    capacity: int,
    n_links: int = 8,
    impl: str = "auto",
    transport: tp.Transport | None = None,
    link_state: tp.LinkState | None = None,
    wire_format: str | wire.WireFormat = "extoll",
) -> ExchangeOut:
    """One flush window of the spike fabric; call inside shard_map.

    ``wire_format`` selects the frame profile of the default transport;
    an explicitly passed ``transport`` keeps its own profile (the single
    source of truth for byte and latency accounting).
    """

    # 1. fused route + aggregate (the paper's LUT 1 + §3.1 buckets)
    if impl in ("auto", "fused", "pallas"):
        from repro.kernels import fused_route_bucket as frb
        use_pallas = None if impl == "auto" else (impl == "pallas")
        b = frb.fused_route_aggregate(
            words, tables.dest_of_addr, tables.guid_of_addr, n_shards,
            capacity, use_pallas=use_pallas).buckets
    else:   # reference impls, route + aggregate staged separately
        dest, guid, routed = tables.route(words)
        words = jnp.where(routed, words, ev.INVALID_EVENT)
        b = aggregator.aggregate(words, dest, guid, n_shards, capacity,
                                 impl=impl)

    # 2. transport ships every bucket; each (event, guid) pair is one
    #    64-bit wire word (repro.wire.codec: deadline | label | guid meta
    #    lane | valid), lane-planar in a single u32 buffer so alltoall
    #    still lowers to exactly ONE all_to_all
    if transport is None:
        transport = tp.create("alltoall", n_shards=n_shards,
                              wire_format=wire_format)
    payload = wire.encode_planar(b.data, b.guids)
    if link_state is None:
        link_state = transport.init_state(payload.shape[-1])
    out = transport.exchange(link_state, payload, b.counts,
                             axis_name=axis_name)
    recv_events, recv_guids = wire.decode_planar(out.recv_payload)
    recv_counts = out.recv_counts

    # mask out slots beyond the per-source count
    slot = jnp.arange(capacity)[None, :]
    live = slot < recv_counts[:, None]
    recv_events = jnp.where(live, recv_events, ev.INVALID_EVENT)

    # 3. destination-side GUID -> multicast mask -> local links
    flat_ev = recv_events.reshape(-1)
    flat_gu = jnp.where(live, recv_guids, -1).reshape(-1)
    masks = tables.multicast(flat_gu)
    bits = (masks[None, :] >> jnp.arange(n_links, dtype=jnp.uint32)[:, None]) & 1
    link_events = jnp.where(bits.astype(bool), flat_ev[None, :], ev.INVALID_EVENT)

    # per-event wire latency of the rows THIS shard delivered: every
    # traversed link charges switch latency + one re-serialization of the
    # row's frame train (store-and-forward), plus the queueing dwell
    # behind traffic parked along the route and — for rows the fabric
    # delivers from its transit buffers — the park dwell accumulated
    # while waiting there (repro.wire.latency's congestion terms; both
    # exactly zero on an uncontended fabric).  Rows parked mid-route this
    # window are excluded (``sent_now``) — their latency is charged by
    # the window that finally delivers them, custody counts and all.
    my = jax.lax.axis_index(axis_name)
    hops_row = transport.route_hops()[my]
    c_row = jnp.where(out.unparked_now > 0, out.unparked_now, b.counts)
    lat_us = (wire.hop_latency_us(transport.wire_fmt, c_row, hops_row)
              + out.queue_us[my] + out.park_wait_us[my])
    lat_w = (jnp.where((jnp.arange(n_shards) != my) & out.sent_now,
                       b.counts, 0) + out.unparked_now)
    latency = wire.summarize_latency(lat_us, lat_w)

    return ExchangeOut(
        recv_events=recv_events,
        recv_guids=recv_guids,
        recv_counts=recv_counts,
        link_events=link_events,
        sent_counts=b.counts,
        overflow=b.overflow,
        wire_bytes=out.stats.forwarded_bytes,
        sent_mask=out.sent_mask,
        link=out.stats,
        link_state=out.state,
        latency=latency,
    )


def make_exchange(mesh, axis_name: str, *, n_shards: int, capacity: int,
                  n_addr_per_shard: int, n_links: int = 8, impl: str = "auto",
                  transport: str = "alltoall",
                  transport_opts: dict | None = None,
                  wire_format: str | wire.WireFormat = "extoll"):
    """Build the jitted multi-shard exchange.

    ``transport`` selects the backend
    (``"alltoall" | "torus2d" | "torus3d"``);
    ``transport_opts`` are forwarded to :func:`repro.transport.create`
    (torus mesh shape, link credits...).  ``wire_format`` (or an explicit
    ``transport_opts["wire_format"]``) selects the frame-accounting /
    latency profile (``"extoll"`` | ``"ethernet"``).  Returns
    f(words[(n_shards, N)], tables[stacked over shard dim]) -> ExchangeOut
    with a leading shard dimension.  ``tables`` is a RoutingTables whose
    arrays carry a leading (n_shards,) dim.  Link-flow-control state starts
    fresh each call (one-shot window; thread ``exchange_window`` manually
    for multi-window credit dynamics).
    """
    transport_opts = dict(transport_opts or {})
    transport_opts.setdefault("wire_format", wire_format)
    if transport in ("torus2d", "torus3d"):
        # a bucket row holds up to `capacity` events; the backend raises
        # if link_credits could never admit a full row (livelock guard)
        transport_opts.setdefault("max_row_events", capacity)
    backend = tp.create(transport, n_shards=n_shards, **transport_opts)

    def body(words, dest_t, guid_t, mcast_t):
        tables = RoutingTables(dest_t[0], guid_t[0], mcast_t[0])
        return exchange_window(
            words[0], tables, axis_name=axis_name, n_shards=n_shards,
            capacity=capacity, n_links=n_links, impl=impl,
            transport=backend,
        )

    spec = P(axis_name)
    fn = jax.shard_map(
        lambda w, d, g, m: jax.tree_util.tree_map(
            lambda x: x[None], body(w, d, g, m)
        ),
        mesh=mesh,
        in_specs=(spec, spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )

    @jax.jit
    def run(words, tables: RoutingTables):
        return fn(words, tables.dest_of_addr, tables.guid_of_addr,
                  tables.mcast_of_guid)

    return run
