"""Windowed multi-shard SNN simulation over the bucket-exchange fabric.

The simulation advances in *flush windows* of ``window`` dt-steps, with
``window <= min axonal delay`` so every spike generated inside a window can
still reach its destination before its timestamp deadline — this is exactly
the deadline-flush condition of the paper's buckets, applied at the system
level (the same trick NEST/SpiNNaker use: communicate every min-delay).

The window loop is a **software-pipelined ``lax.scan``**: the carry holds,
besides the neuron/ring state, the *pending* aggregated buckets of the
previous window, a double-buffered overflow **residue**, and the transport
backend's link flow-control state.  Iteration k:

  1. exchange+decode window k-1's pending buckets through the configured
     transport (``cfg.transport``: ``"alltoall"`` ships ONE packed
     collective per window; ``"torus2d"`` / ``"torus3d"`` walk
     dimension-ordered neighbor ``ppermute`` hops over a 2-D / 3-D device
     torus under hop-by-hop credit-based link flow control — see
     ``repro.transport``) and scatter their weighted input
     into the delay ring; this happens at the same systemtime as the
     unpipelined formulation (the start of window k == the end of window
     k-1), so deadline semantics are unchanged.  Bucket rows refused at
     their source egress link are *deferred*: their events re-enter this
     window's aggregation ahead of everything else.  Rows refused at a
     TRANSIT link park in the fabric's transit buffers (``FabricState``)
     and resume from their current hop in a later window — the fabric,
     not the caller, keeps custody of their wire words,
  2. ``lax.scan`` the LIF dynamics ``window`` steps off the ring,
  3. compact spikes into packed events, append the transport-deferred
     events and the residue deferred from window k-1 (the FPGA's
     back-pressure on the HICANN links), and run the fused route+aggregate
     kernel (``repro.kernels.fused_route_bucket``); the new buckets +
     residue become the pending half of the carry.

Because stage 3 of window k is data-independent of stage 1's collective
result, the route/aggregate of window k can overlap the decode of window
k-1 on hardware with async collectives.  After the scan, one drain step
flushes the final window's buckets.

Conservation (no spike lost, none applied at the wrong step) is asserted in
tests against a monolithic single-device reference simulation; the residue
chain is externally checkable from ``WindowStats`` (see the identity in
``tests``: sum(offered) - re-offered == sum(sent) + final deferred +
dropped).
"""
from __future__ import annotations

import contextlib
import functools
import threading
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import transport as tp
from repro import wire
from repro.core import aggregator, events as ev
from repro.fabric import faults as fabric_faults
from repro.obs import recorder as obs_recorder
from repro.obs import spans as obs_spans
from repro.core.routing import RoutingTables
from repro.snn import lif, network


class SimConfig(NamedTuple):
    n_shards: int
    per_shard: int            # neurons per shard
    max_fan: int              # max destination shards per source
    window: int = 8           # dt steps per flush window (<= min delay)
    ring_len: int = 32        # delay ring slots (> max delay + window)
    e_max: int = 512          # spike-compaction buffer per window
    capacity: int = 256       # bucket capacity (events per dest per window)
    params: lif.LIFParams = lif.LIFParams()
    residue: int = 256        # deferred-event carry buffer (re-offered)
    transport: str = "alltoall"   # flush-window backend (see repro.transport)
    torus_nx: int = 0         # torus mesh shape (0 = auto-factorize)
    torus_ny: int = 0
    torus_nz: int = 0         # wafer (Z) axis — torus3d only
    link_credits: int = 0     # per-window events per egress link (0 = off;
                              #   spent on EVERY hop of a row's route)
    notify_latency: int = 2   # windows before spent link credits return
    wire_format: str = "extoll"   # frame/latency profile (repro.wire:
                              #   "extoll" | "ethernet") for bytes_on_wire
                              #   and the per-event latency model
    step_us: float = 0.1      # wall-clock per dt step on the accelerated
                              #   substrate (BrainScaleS ~1000x: 0.1 ms
                              #   biological -> 0.1 us hardware); converts
                              #   window-quantized waiting into the wire
                              #   latency unit


class ShardState(NamedTuple):
    neuron: lif.LIFState      # per-shard neurons
    ring_exc: jax.Array       # (ring_len, per) scheduled exc current
    ring_inh: jax.Array       # (ring_len, per) scheduled inh current
    t: jax.Array              # () i32 global step
    key: jax.Array            # PRNG for background drive


class PendingWindow(NamedTuple):
    """The pipelined half of the scan carry: window k's aggregated buckets,
    exchanged+decoded at the start of iteration k+1, plus the deferred
    events re-offered into window k+1's aggregation.

    ``meta``/``residue_meta`` carry each event's *injection systemtime
    step* alongside it — through the buckets, the 64-bit wire words of
    the exchange, transport deferral and residue re-offers — so the
    decode side can charge exact waiting time (``WindowStats.latency``).
    """

    data: jax.Array           # (n_shards, capacity) u32 bucketed events
    meta: jax.Array           # (n_shards, capacity) i32 injection steps
    counts: jax.Array         # (n_shards,) i32 accepted per destination
    residue: jax.Array        # (residue,) u32 deferred events (INVALID pad)
    residue_meta: jax.Array   # (residue,) i32 their injection steps


class WindowStats(NamedTuple):
    spikes: jax.Array         # () i32 local spikes this window
    events_sent: jax.Array    # () i32 events shipped (incl. replicas)
    overflow: jax.Array       # () i32 events dropped (compaction + residue)
    wire_bytes: jax.Array     # () i32 Extoll bytes of THIS window's fresh
                              # buckets, single-shipment crossbar model
                              # (re-offered deferrals count again; for the
                              # torus per-hop wire model of what actually
                              # crossed links, read link.forwarded_bytes)
    deadline_miss: jax.Array  # () i32 events landing past their deadline;
                              # NOTE pipelining shifts attribution: row k
                              # counts the decode of window k-1's buckets
                              # (row 0 is always 0, the final window's
                              # misses land on the last row via the drain).
                              # Totals over a run are exact.
    offered: jax.Array        # () i32 routed events offered (incl. re-offers)
    deferred: jax.Array       # () i32 events carried to the next window
    link: tp.LinkStats        # transport-level stats for the exchange run
                              # at the START of this iteration (window k-1's
                              # buckets; same one-row shift as deadline_miss;
                              # its deferred_events re-enter THIS row's
                              # `offered`)
    latency: wire.LatencySummary  # per-event wire latency of the events
                              # DELIVERED by that same exchange (window
                              # k-1's buckets; row 0 is zero, the drain's
                              # deliveries are discarded like `link`):
                              # window-quantized waiting since each event's
                              # injection step (deferral/residue rounds
                              # accumulate) + per traversed link one switch
                              # latency + one frame-train serialization of
                              # the row (repro.wire.latency)


def _simulate_steps(state: ShardState, cfg: SimConfig, bg_rate: jax.Array,
                    bg_w: float):
    """Run `window` LIF steps off the delay ring; returns spikes (w, per)."""

    def step(carry, _):
        st = carry
        slot = st.t % cfg.ring_len
        key, sub = jax.random.split(st.key)
        exc_in = st.ring_exc[slot] + lif.poisson_input(
            sub, cfg.per_shard, bg_rate, bg_w, cfg.params.dt)
        inh_in = st.ring_inh[slot]
        neuron, spk = lif.step(st.neuron, cfg.params, exc_in, inh_in)
        # clear the consumed slot so the ring can be reused
        ring_exc = st.ring_exc.at[slot].set(0.0)
        ring_inh = st.ring_inh.at[slot].set(0.0)
        st = ShardState(neuron, ring_exc, ring_inh, st.t + 1, key)
        return st, spk

    state, spikes = jax.lax.scan(step, state, None, length=cfg.window)
    return state, spikes


def _spikes_to_events(spikes: jax.Array, t0: jax.Array, delays: jax.Array,
                      cfg: SimConfig):
    """Compact (window, per) spike raster into <= e_max packed event words.

    Each spike yields `max_fan` replica events (addr = id*fan + k); invalid
    replicas are dropped by the routing LUT (NO_ROUTE).  Also returns each
    replica's absolute injection step (``t0 + step``, un-wrapped i32) — the
    meta value the wire layer threads to the decode side for the latency
    model.
    """
    w, per = spikes.shape
    flat = spikes.reshape(-1)                                 # (w*per,)
    step_of = jnp.repeat(jnp.arange(w), per)
    id_of = jnp.tile(jnp.arange(per), w)
    # stable compaction: spiking slots first, original order preserved
    order = jnp.argsort(~flat, stable=True)[: cfg.e_max]
    sel = flat[order]
    sel_step = step_of[order]
    sel_id = id_of[order]
    lost = jnp.maximum(jnp.sum(flat) - cfg.e_max, 0)
    ts = (t0 + sel_step + delays[sel_id]) & ev.TS_MASK
    # replicate per fan slot
    k = jnp.arange(cfg.max_fan)
    addr = (sel_id[:, None] * cfg.max_fan + k[None, :]).reshape(-1)
    words = ev.pack(addr, jnp.repeat(ts, cfg.max_fan),
                    valid=jnp.repeat(sel, cfg.max_fan))
    inject = jnp.repeat((t0 + sel_step).astype(jnp.int32), cfg.max_fan)
    return words, inject, lost.astype(jnp.int32)


def _apply_events(state: ShardState, words: jax.Array, counts: jax.Array,
                  w_rows_exc: jax.Array, w_rows_inh: jax.Array,
                  cfg: SimConfig, src_shard: jax.Array):
    """Scatter weighted input of received events into the delay ring.

    words: (n_shards, C) events from each source shard; counts (n_shards,).
    w_rows_*: (n_total, >= per) source-major local weights split by
    source sign: row j holds source j's synapses onto this shard's
    neurons, in its first ``per`` columns (see :func:`_source_major`).
    An event gathers its source's row; gathering a column of a
    target-major matrix instead makes XLA re-lay out the whole matrix
    on every call.
    Returns (state, deadline_misses).
    """
    S, C = words.shape
    slot_idx = jnp.arange(C)[None, :]
    live = slot_idx < counts[:, None]
    addr = ev.address(words).astype(jnp.int32)
    ts = ev.timestamp(words).astype(jnp.int32)
    src_local = addr // cfg.max_fan
    src_global = src_shard[:, None] * cfg.per_shard + src_local   # (S, C)
    # deadline check: event must land at ts >= current time
    slack = ev.ts_slack(ts, state.t & ev.TS_MASK)
    miss = jnp.sum(jnp.where(live & (slack < 0), 1, 0))
    slot = (state.t + jnp.maximum(slack, 0)) % cfg.ring_len        # (S, C)

    flat_live = live.reshape(-1)
    flat_src = jnp.where(flat_live, src_global.reshape(-1), 0)
    flat_slot = slot.reshape(-1)
    # one-hot over ring slots x gathered weight rows; whole rows, as the
    # TPU gathers them natively (a gather of part of a row is expanded
    # into a loop of one-row slices), cut to ``per`` after the product
    exc_rows = w_rows_exc[flat_src] * flat_live[:, None]          # (S*C, W)
    inh_rows = w_rows_inh[flat_src] * flat_live[:, None]
    onehot = jax.nn.one_hot(flat_slot, cfg.ring_len, dtype=exc_rows.dtype)
    per = cfg.per_shard
    ring_exc = state.ring_exc + jnp.einsum("el,ep->lp", onehot,
                                           exc_rows)[:, :per]
    ring_inh = state.ring_inh + jnp.einsum("el,ep->lp", onehot,
                                           inh_rows)[:, :per]
    return state._replace(ring_exc=ring_exc, ring_inh=ring_inh), miss


# the TPU's vector lanes: an array's minor dimension is laid out in
# whole multiples of this
LANES = 128


def weight_width(per: int) -> int:
    """Columns of the apply's source-major weights: ``per`` rounded up to
    whole lanes."""
    return -(-per // LANES) * LANES


def _source_major(w_local: np.ndarray, keep: np.ndarray,
                  block: int = 512) -> np.ndarray:
    """(S, per, N) target-major weights -> (S, N, weight_width(per))
    source-major float32, the rows of sources outside ``keep`` and the
    columns past ``per`` zero.  One pass, in blocks of target columns so
    both sides of the transpose stay in cache.

    The width in whole lanes is what makes the TPU's default layout of a
    shard's 2-D block row-major, the layout the apply's row gather
    reads: by default the TPU puts minor whichever dimension pads less
    (a (16204, 4051) block would be column-major), and lays a leading
    axis of one out in (1, 128) tiles.  XLA would copy the whole matrix
    into the gather's layout on every call of the segment program."""
    S, per, n = w_local.shape
    out = np.zeros((S, n, weight_width(per)), np.float32)
    for s in range(S):
        rows = out[s, :, :per]
        for j in range(0, per, block):
            np.copyto(rows[:, j:j + block], w_local[s, j:j + block].T,
                      where=keep[:, None])
    return out


def make_pipeline_fns(cfg: SimConfig, *, axis_name: str | None,
                      fault_schedule: fabric_faults.FaultSchedule | None
                      = None, recorder=None):
    """Build the pipelined per-window machinery (axis_name=None -> single
    shard, no collective).

    ``fault_schedule`` (torus + credits only) injects link/node failures:
    each window's exchange runs with that window's dead-link mask stamped
    onto the fabric state (``FabricState.link_down``), so the transport
    reroutes around failures and the latency model charges each delivered
    event its ACTUAL traversed links (detours included) instead of the
    static shortest-route hop count — see ``docs/architecture.md``.

    ``recorder`` (a ``repro.obs.RecorderConfig``) enables the device-side
    flight recorder: the scan carry gains a ``TelemetryRing`` 4th element
    and each window appends its window index, LinkStats deltas, credit /
    parked_by_link occupancy and latency-histogram delta.  Credited torus
    backends are additionally built with ``stall_attribution=True`` so
    the ring's per-link congestion lane is populated.  ``None`` (the
    default) compiles the EXACT pre-observability program — carry pytree
    and HLO are pinned bit-identical by ``tests/test_obs.py``.

    Returns ``(init_pending, init_link, body, drain, init_ring)``:
      init_pending()          -> empty PendingWindow carry half
      init_link()             -> transport flow-control state carry half
      body((state, pending, link[, ring]), ...)
                              -> ((state, pending', link'[, ring']),
                                  WindowStats)
      drain(state, pending, link, ...)  -> (state, deadline_misses) flushing
                                            the final window's buckets after
                                            the scan (credits bypassed: the
                                            fabric quiesces).
      init_ring               -> empty TelemetryRing carry element, or
                                 None when the recorder is disabled
    """
    if axis_name is not None:
        opts = {"wire_format": cfg.wire_format}
        if cfg.transport in ("torus2d", "torus3d"):
            opts.update(nx=cfg.torus_nx, ny=cfg.torus_ny,
                        link_credits=cfg.link_credits,
                        notify_latency=cfg.notify_latency,
                        max_row_events=cfg.capacity)  # livelock guard
            if cfg.transport == "torus3d":
                opts["nz"] = cfg.torus_nz
            if recorder is not None and cfg.link_credits > 0:
                opts["stall_attribution"] = True
        backend = tp.create(cfg.transport, n_shards=cfg.n_shards, **opts)
    else:
        backend = tp.Transport(cfg.n_shards, wire_format=cfg.wire_format)
        # state-only stub (no collective; crossbar route_hops)
    # can the transport ever refuse a bucket?  (static: gates the
    # deferred-word re-offer plumbing out of the alltoall/uncredited path)
    can_defer = (axis_name is not None
                 and cfg.transport in ("torus2d", "torus3d")
                 and cfg.link_credits > 0)
    if fault_schedule is not None and not can_defer:
        raise ValueError(
            "fault injection needs a credit-throttled torus transport "
            "(transport='torus2d'/'torus3d' with link_credits > 0): an "
            "uncredited fabric has no admission point to reroute at")

    def init_pending() -> PendingWindow:
        return PendingWindow(
            data=jnp.zeros((cfg.n_shards, cfg.capacity), jnp.uint32),
            meta=jnp.zeros((cfg.n_shards, cfg.capacity), jnp.int32),
            counts=jnp.zeros((cfg.n_shards,), jnp.int32),
            residue=jnp.full((cfg.residue,), ev.INVALID_EVENT),
            residue_meta=jnp.zeros((cfg.residue,), jnp.int32),
        )

    def init_link() -> tp.LinkState:
        # the wire payload is lane-planar 64-bit words: 2 u32 per bucket
        # slot (repro.wire.codec) — the width the in-fabric transit
        # buffers must hold to keep custody of a parked row
        return backend.init_state(2 * cfg.capacity)

    def _exchange(pend: PendingWindow, lstate: tp.LinkState, *,
                  enforce_credits: bool):
        """Ship window k-1's buckets through the transport backend.

        Each (event, injection-step) pair travels as one 64-bit wire word
        (``repro.wire.codec``), lane-planar in the u32 payload.  The last
        tuple element is the queueing-dwell column of the rows delivered
        to this shard (the congestion term of the latency model).
        """
        if axis_name is None:
            full = jnp.ones((cfg.n_shards,), bool)
            return (pend.data, pend.meta, pend.counts, full,
                    tp.zero_link_stats(), lstate,
                    jnp.zeros((cfg.n_shards,), jnp.float32), None)
        payload = wire.encode_planar(pend.data, pend.meta)
        out = backend.exchange(lstate, payload, pend.counts,
                               axis_name=axis_name,
                               enforce_credits=enforce_credits)
        recv_events, recv_meta = wire.decode_planar(out.recv_payload)
        me = jax.lax.axis_index(axis_name)
        links_row = (out.links_used[:, me]
                     if out.links_used is not None else None)
        return (recv_events, recv_meta, out.recv_counts, out.sent_mask,
                out.stats, out.state, out.queue_us[:, me], links_row)

    def _decode(state: ShardState, recv, counts, w_exc, w_inh):
        src_shard = jnp.arange(cfg.n_shards)
        return _apply_events(state, recv, counts, w_exc, w_inh, cfg,
                             src_shard)

    fmt = backend.wire_fmt

    def _window_latency(state: ShardState, recv_meta, counts, queue_us,
                        links_row=None):
        """Wire latency of the events just delivered: waiting since each
        event's injection step (state.t == the decoded window's end, so
        deferral, residue AND in-fabric park rounds accumulate whole
        windows) + the row's per-link switch + frame-serialization
        charges + the queueing dwell behind traffic parked along its
        route (the congestion term; zero on an uncontended fabric).

        ``links_row`` (fault injection only) is the per-source count of
        links each delivered row ACTUALLY traversed — detour hops are
        charged honestly instead of assuming the shortest route."""
        me = (jax.lax.axis_index(axis_name) if axis_name is not None
              else jnp.int32(0))
        slot = jnp.arange(cfg.capacity)[None, :]
        live = slot < counts[:, None]
        wait_us = (state.t - recv_meta).astype(jnp.float32) * cfg.step_us
        hops_row = (backend.route_hops()[me] if links_row is None
                    else links_row)
        hop_us = wire.hop_latency_us(fmt, counts, hops_row) + queue_us
        lat = jnp.maximum(wait_us, 0.0) + hop_us[:, None]
        return wire.summarize_latency(lat, live.astype(jnp.int32))

    def body(carry, tables: RoutingTables, w_exc, w_inh, delays, bg_rate,
             bg_w):
        if recorder is not None:
            state, pend, lstate, ring = carry
            # the exchange below ships window k-1's buckets: at entry
            # state.t sits at window k's start, so the record is stamped
            # with the EXCHANGED window's absolute index (row 0 is the
            # empty bootstrap exchange, index -1 — the same one-row shift
            # WindowStats carries)
            win_rec = state.t // cfg.window - 1
        else:
            state, pend, lstate = carry
        # 1. exchange + decode window k-1 (same systemtime as unpipelined:
        #    state.t here == that window's end); the route/aggregate below
        #    never reads the collective's result, so the two can overlap.
        #    Under fault injection, stamp this window's dead-link mask on
        #    the fabric state first (exchange resets it to None, so the
        #    scan carry stays structurally stable).
        if fault_schedule is not None:
            lstate = lstate._replace(link_down=fabric_faults.mask_at(
                fault_schedule, state.t // cfg.window))
        recv, rmeta, counts, sent_mask, lstats, lstate, qcol, lrow = \
            _exchange(pend, lstate, enforce_credits=True)
        latency = _window_latency(state, rmeta, counts, qcol, lrow)
        state, miss = _decode(state, recv, counts, w_exc, w_inh)
        # 2. simulate window k
        t0 = state.t
        state, spikes = _simulate_steps(state, cfg, bg_rate, bg_w)
        # 3. fused route+aggregate of window k's spikes + deferred events;
        #    transport-deferred buckets go FIRST, then the residue, then
        #    fresh spikes — oldest deadlines win bucket slots (FIFO
        #    back-pressure, no starvation under sustained overflow).  Each
        #    event's injection step rides along as i32 meta (the guids
        #    operand) so latency accumulates across re-offers.
        words, inject, lost = _spikes_to_events(spikes, t0, delays, cfg)
        if can_defer:
            slot = jnp.arange(cfg.capacity)[None, :]
            held = (~sent_mask[:, None]) & (slot < pend.counts[:, None])
            deferred_words = jnp.where(held, pend.data,
                                       ev.INVALID_EVENT).reshape(-1)
            deferred_meta = jnp.where(held, pend.meta, 0).reshape(-1)
            words = jnp.concatenate([deferred_words, pend.residue, words])
            inject = jnp.concatenate([deferred_meta, pend.residue_meta,
                                      inject])
        else:
            words = jnp.concatenate([pend.residue, words])
            inject = jnp.concatenate([pend.residue_meta, inject])
        from repro.kernels import fused_route_bucket as frb
        addr = ev.address(words).astype(jnp.int32)
        dest = jnp.take(tables.dest_of_addr,
                        jnp.minimum(addr, tables.dest_of_addr.shape[0] - 1))
        fw = frb.fused_aggregate(
            words, dest, inject, cfg.n_shards, cfg.capacity,
            residue_len=cfg.residue, with_residue_meta=True)
        b = fw.buckets
        if axis_name is not None:
            my = jax.lax.axis_index(axis_name)
            off = jnp.where(jnp.arange(cfg.n_shards) == my, 0, b.counts)
        else:
            off = jnp.zeros_like(b.counts)
        cost = aggregator.window_cost(off)
        stats = WindowStats(
            spikes=jnp.sum(spikes).astype(jnp.int32),
            events_sent=jnp.sum(b.counts),
            overflow=(lost + fw.dropped).astype(jnp.int32),
            wire_bytes=cost.bytes,
            deadline_miss=miss.astype(jnp.int32),
            offered=fw.offered,
            deferred=fw.deferred,
            link=lstats,
            latency=latency,
        )
        pend_out = PendingWindow(b.data, b.guids, b.counts, fw.residue,
                                 fw.residue_meta)
        if recorder is not None:
            ring = obs_recorder.record(ring, win_rec, lstats, lstate,
                                       latency.hist)
            return (state, pend_out, lstate, ring), stats
        return (state, pend_out, lstate), stats

    def drain(state: ShardState, pend: PendingWindow, lstate: tp.LinkState,
              w_exc, w_inh):
        """Flush the fabric AND the last window's buckets (their decode
        slot is the step after the scan ends; the final residue stays
        deferred and is reported via the last window's ``deferred``).
        The walk order matches event age: first ``drain_fabric`` delivers
        every row still parked in an in-fabric transit buffer (resuming
        from its current hop, held credits released), then the final
        uncredited exchange ships the pending buckets — so no event is
        stranded mid-route or in a stalled bucket.  The drain exchanges'
        LinkStats and latency digests are intentionally discarded:
        folding them into the last row would break the per-row identities
        (offered_k == events_sent_{k-1}, offered == sent + deferred +
        parked) that tests pin, so per-run link totals cover the
        n_windows scanned exchanges only (deadline misses, a pure
        accumulator with no such identity, ARE folded in)."""
        miss_total = jnp.zeros((), jnp.int32)
        if can_defer:       # implies axis_name is not None
            fab = backend.drain_fabric(lstate, axis_name=axis_name)
            recv_f, _ = wire.decode_planar(fab.recv_payload)
            state, miss_f = _decode(state, recv_f, fab.recv_counts,
                                    w_exc, w_inh)
            miss_total = miss_total + miss_f.astype(jnp.int32)
            lstate = fab.state
        recv, _, counts, _, _, _, _, _ = _exchange(pend, lstate,
                                                   enforce_credits=False)
        state, miss = _decode(state, recv, counts, w_exc, w_inh)
        return state, miss_total + miss.astype(jnp.int32)

    if recorder is not None:
        def init_ring():
            lst = init_link()
            return obs_recorder.ring_init(
                recorder.depth, lst, (), (wire.N_LATENCY_BINS,),
                lst.bank.credits.shape[0])
    else:
        init_ring = None

    return init_pending, init_link, body, drain, init_ring


# what ``jax.monitoring`` calls one XLA compilation of a program (also a
# persistent-cache hit), in ``jax._src.dispatch``
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


@contextlib.contextmanager
def counting_compiles():
    """Count the XLA compilations the calling thread makes inside the
    block; yields a one-element list that holds the count.  The
    ``jax.monitoring`` listener is registered only for the block."""
    n, me = [0], threading.get_ident()

    def listen(event: str, duration_s: float, **_):
        if event == BACKEND_COMPILE_EVENT and threading.get_ident() == me:
            n[0] += 1

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        yield n
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)


class SimCarry(NamedTuple):
    """Resumable between-segment state of a sharded simulation: everything
    the window pipeline threads through ``lax.scan`` — neuron/ring state,
    the pipelined pending buckets + residue, and the fabric's link
    flow-control state (credits, pending notifies, parked rows).  All
    leaves are stacked with a leading ``n_shards`` axis (``P(axis)``).

    ``ring`` is the flight recorder's telemetry ring — present only when
    the simulator is built with ``recorder=RecorderConfig(...)``; the
    default ``None`` is a leafless pytree node, so uninstrumented carries
    keep the exact pre-observability structure (pinned by
    ``tests/test_obs.py``)."""

    state: ShardState
    pending: PendingWindow
    link: tp.LinkState
    ring: obs_recorder.TelemetryRing | None = None


def build_sharded_segments(mesh, axis_name: str, cfg: SimConfig,
                           part: network.Partition, bg_rates: np.ndarray,
                           bg_weight: float = 87.8,
                           fault_schedule: fabric_faults.FaultSchedule |
                           None = None,
                           recorder=None, tracer=None):
    """Segment-granular jitted simulator over a device mesh.

    The whole-run scan of :func:`build_sharded_sim` is a special case of
    this entry point; the serving engine is the general one — it needs to
    run *bounded segments* of windows with the pipeline state resumable
    between dispatches (so the host can overlap staging/ingestion with
    device work and decide, between segments, whether to keep serving or
    quiesce).

    The per-shard operands are placed on the mesh once, shard s on
    device s.  The routing tables (``dest_of_addr``, ``guid_of_addr``,
    ``mcast_of_guid``, padded to a common length), the axonal delays
    ``(S, per)`` and the background rates ``(S, per)`` are stacked over
    a leading shard axis.  The excitatory and inhibitory weights are
    source-major, each shard's ``(n_total, weight_width(per))`` block
    stacked along the rows into ``(S * n_total, weight_width(per))``:
    the apply gathers a row per event (see :func:`_source_major`).

    Returns ``(init, run_segment, finish)``:
      init(seed)                    -> SimCarry (fresh neurons, empty
                                       buckets, full credits)
      run_segment(carry, n_windows) -> (SimCarry, stacked WindowStats) —
                                       compiled once per distinct
                                       ``n_windows`` and cached
      finish(carry)                 -> (stacked ShardState, (n_shards,)
                                       deadline misses) — drains parked
                                       fabric rows and flushes the final
                                       pending buckets via the transport's
                                       ``drain_fabric`` + one uncredited
                                       exchange; no event is lost between
                                       segment end and shutdown
      run_segment.fetch_stats(stats) -> the segment's WindowStats on the
                                       host

    ``tracer`` (a ``repro.obs.spans.Tracer``; ``None`` is the disabled
    ``NULL``) records the host's segment cycle, one ``seg`` number per
    segment: ``segment/dispatch`` around the jitted call (``n_windows``;
    ``compiles``, the XLA compilations inside the call), then, in
    ``fetch_stats``, ``segment/wait`` until the device has finished and
    ``segment/fetch`` while the statistics are copied.  The first fetch
    of each segment length also counts what it copies (``arrays``,
    device buffers; ``bytes``).  An enabled tracer keeps each segment's
    statistics with its number until ``fetch_stats`` takes them, so a
    caller may fetch one segment while the next is dispatched.  The
    spans are host-only: the lowered segment and the carry are the same
    with or without them.
    """
    tracer = obs_spans.NULL if tracer is None else tracer
    S, per = cfg.n_shards, cfg.per_shard
    n_tot = part.n_neurons
    spec = P(axis_name)
    # every stacked per-shard operand is placed once, shard s on device s
    shard = NamedSharding(mesh, spec)
    put = functools.partial(jax.device_put, device=shard)
    w_local, _fan, delay_local = network.shard_arrays(part)
    is_inh = part.is_inh
    # source-major, each shard's block stacked along the rows: see
    # _source_major
    w_exc = put(_source_major(w_local, ~is_inh).reshape(S * n_tot, -1))
    w_inh = put(_source_major(w_local, is_inh).reshape(S * n_tot, -1))
    delays = put(delay_local)
    tabs = [network.routing_tables_for_shard(part, s) for s in range(S)]
    # pad per-shard tables to a common size before stacking
    na = max(t.dest_of_addr.shape[0] for t in tabs)
    ng = max(t.mcast_of_guid.shape[0] for t in tabs)
    pad = lambda a, n, v=0: np.pad(np.asarray(a), (0, n - a.shape[0]),
                                   constant_values=v)
    dest_t = put(np.stack([pad(t.dest_of_addr, na, -1) for t in tabs]))
    guid_t = put(np.stack([pad(t.guid_of_addr, na) for t in tabs]))
    mcast_t = put(np.stack([pad(t.mcast_of_guid, ng) for t in tabs]))
    bg = put(np.pad(bg_rates, (0, n_tot - len(bg_rates))).reshape(S, per)
             .astype(np.float32))

    init_pending, init_link, body, drain, init_ring = make_pipeline_fns(
        cfg, axis_name=axis_name, fault_schedule=fault_schedule,
        recorder=recorder)

    def seg_fn(carry: SimCarry, dest, guid, mcast, w_e, w_i, dl, bgr,
               n_windows):
        tables = RoutingTables(dest[0], guid[0], mcast[0])
        c0 = jax.tree_util.tree_map(lambda x: x[0], carry)

        def win(c, _):
            return body(c, tables, w_e, w_i, dl[0], bgr[0],
                        bg_weight)

        if recorder is not None:
            scanned, stats = jax.lax.scan(
                win, (c0.state, c0.pending, c0.link, c0.ring), None,
                length=n_windows)
        else:
            scanned, stats = jax.lax.scan(
                win, (c0.state, c0.pending, c0.link), None,
                length=n_windows)
        return (jax.tree_util.tree_map(lambda x: x[None],
                                       SimCarry(*scanned)),
                jax.tree_util.tree_map(lambda x: x[None], stats))

    def fin_fn(carry: SimCarry, w_e, w_i):
        c0 = jax.tree_util.tree_map(lambda x: x[0], carry)
        st, miss_d = drain(c0.state, c0.pending, c0.link, w_e, w_i)
        return (jax.tree_util.tree_map(lambda x: x[None], st),
                miss_d[None])

    @functools.lru_cache(maxsize=None)
    def _compiled_segment(n_windows: int):
        fn = jax.shard_map(
            functools.partial(seg_fn, n_windows=n_windows),
            mesh=mesh, in_specs=(spec,) * 8, out_specs=(spec, spec),
            check_vma=False)
        return jax.jit(fn)

    operands = (dest_t, guid_t, mcast_t, w_exc, w_inh, delays, bg)
    # the segment cycle on the host: segments are numbered as dispatched;
    # with the tracer on, id(stats) -> (stats, seg, n_windows) until
    # fetch_stats takes them, and the segment lengths already counted
    cycle = {"seg": -1, "issued": {}, "sized": set()}

    def run_segment(carry: SimCarry, n_windows: int):
        cycle["seg"] += 1
        seg = cycle["seg"]
        with tracer.span("segment/dispatch", seg=seg,
                         n_windows=n_windows) as sp:
            with (counting_compiles() if tracer.enabled
                  else contextlib.nullcontext([0])) as n:
                out, stats = _compiled_segment(n_windows)(carry, *operands)
            sp.args["compiles"] = n[0]
        if tracer.enabled:
            cycle["issued"][id(stats)] = (stats, seg, n_windows)
        return out, stats

    def fetch_stats(stats: WindowStats) -> WindowStats:
        _, seg, n_windows = cycle["issued"].pop(id(stats), (None,) * 3)
        with tracer.span("segment/wait", seg=seg):
            # start every copy first, as jax.device_get does: each runs
            # as soon as the device has finished
            for x in jax.tree_util.tree_leaves(stats):
                x.copy_to_host_async()
            jax.block_until_ready(stats)
        with tracer.span("segment/fetch", seg=seg) as sp:
            host = jax.device_get(stats)
            # (only statistics an enabled tracer saw dispatched are known)
            if n_windows is not None and n_windows not in cycle["sized"]:
                cycle["sized"].add(n_windows)
                leaves = jax.tree_util.tree_leaves(host)
                # every leaf is split over the S shards (out_specs)
                sp.args.update(arrays=len(leaves) * S,
                               bytes=sum(x.nbytes for x in leaves))
        return host

    run_segment.fetch_stats = fetch_stats
    # the lowered segment, for checking what the compiler made of it
    run_segment.lower = lambda carry, n_windows: _compiled_segment(
        n_windows).lower(carry, *operands)

    fin = jax.jit(jax.shard_map(fin_fn, mesh=mesh, in_specs=(spec,) * 3,
                                out_specs=(spec, spec), check_vma=False))

    def finish(carry: SimCarry):
        return fin(carry, w_exc, w_inh)

    def init(seed: int = 0) -> SimCarry:
        keys = jax.random.split(jax.random.PRNGKey(seed), S)
        neuron = jax.vmap(lambda k: lif.init_state(per, cfg.params, k))(keys)
        state = ShardState(
            neuron=neuron,
            ring_exc=jnp.zeros((S, cfg.ring_len, per), jnp.float32),
            ring_inh=jnp.zeros((S, cfg.ring_len, per), jnp.float32),
            t=jnp.zeros((S,), jnp.int32),
            key=jax.vmap(jax.random.PRNGKey)(jnp.arange(S) + seed * 1000 + 7),
        )
        # pending/link start identical on every shard: broadcast host-side
        bcast = lambda a: jnp.broadcast_to(a[None], (S,) + a.shape)
        carry = SimCarry(state,
                         jax.tree_util.tree_map(bcast, init_pending()),
                         jax.tree_util.tree_map(bcast, init_link()),
                         (jax.tree_util.tree_map(bcast, init_ring())
                          if init_ring is not None else None))
        return jax.tree_util.tree_map(put, carry)

    return init, run_segment, finish


def build_sharded_sim(mesh, axis_name: str, cfg: SimConfig, part: network.Partition,
                      bg_rates: np.ndarray, bg_weight: float = 87.8,
                      fault_schedule: fabric_faults.FaultSchedule |
                      None = None,
                      recorder=None):
    """Jitted multi-window simulator over a device mesh (whole-run form,
    composed from :func:`build_sharded_segments`: one segment + finish).

    Returns (init_fn(seed) -> stacked ShardState, run_fn(state, n_windows)
    -> (state, stacked WindowStats over windows)).  With
    ``recorder=RecorderConfig(...)`` the run additionally returns the
    final flight-recorder ring: ``run`` yields ``(state, stats, ring)``
    (leading shard axis on every ring lane; decode with
    ``repro.obs.ring_shard`` + ``ring_rows``).
    """
    seg_init, run_segment, finish = build_sharded_segments(
        mesh, axis_name, cfg, part, bg_rates, bg_weight, fault_schedule,
        recorder=recorder)
    fresh = seg_init(0)        # pending/link halves are seed-independent

    def init(seed: int = 0):
        return seg_init(seed).state

    def run(state, n_windows: int):
        carry, stats = run_segment(
            SimCarry(state, fresh.pending, fresh.link, fresh.ring),
            n_windows)
        state, miss_d = finish(carry)
        if n_windows > 0:
            # the final flush's deadline misses land on the last window
            stats = stats._replace(
                deadline_miss=stats.deadline_miss.at[:, -1].add(miss_d))
        if recorder is not None:
            return state, stats, carry.ring
        return state, stats

    run.lower = lambda state, n_windows: run_segment.lower(
        SimCarry(state, fresh.pending, fresh.link, fresh.ring), n_windows)
    return init, run
