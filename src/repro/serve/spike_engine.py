"""Streaming multi-tenant spike serving engine.

Turns the batch simulator's fabric mechanics into a *serving system*: a
host-side ingestion thread feeds pinned double buffers, the device runs a
continuously repeating windowed ``lax.scan`` segment, and JAX's async
dispatch overlaps the two — the host encodes/stages segment ``k+1`` while
the device still exchanges segment ``k`` (the thread/queue/slot pattern
of MLPerf-style offline inference engines, applied to spike streams).

Data path, per flush window and tenant::

    ingest thread                     device (shard_map over the wafer axis)
    ─────────────                     ──────────────────────────────────────
    loadgen / client                  backlog-first merge -> bucket rows
      │  fill staging slot              │ encode_planar(words, inject-window)
      ▼                                 ▼
    staged queue (depth 2) ──asarray──> TenantTorusTransport.exchange
      ▲                                 │ deferred rows -> backlog carry
      └── free-slot queue <──────────── ▼ per-tenant latency digests

The engine is loss-accountable end to end: every generated event is
``delivered``, sitting in the ``backlog`` carry, parked ``in_fabric``, or
counted as ``shed`` (fresh arrivals beyond the bounded per-row backlog —
the open-loop overload response, measured instead of silently dropped).
``stop(drain=True)`` quiesces by running zero-traffic segments until
backlog and fabric empty (credits refund, parked rows resume), then a
final walk that reuses ``drain_fabric`` plus one uncredited flush — after
which ``injected == delivered + shed`` holds per tenant, i.e. no event is
lost across engine stop.  Latency attribution runs on the receiver from
the injection-window meta lane each event carries, so deferral, backlog
dwell and park windows all show up in the per-tenant digests.
"""
from __future__ import annotations

import queue
import threading
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.fabric import faults as fabric_faults
from repro.obs import recorder as obs_recorder
from repro.obs import spans as obs_spans
from repro.serve import tenancy
from repro.wire import codec
from repro.wire import latency as wire_latency


class EngineConfig(NamedTuple):
    """Static engine parameters.

    capacity:      C — max events per (tenant, dst) bucket row per window;
                   also the per-row backlog bound (one deferred row)
    link_credits:  per-link credit budget split by the tenant partition
    notify_latency: windows before a spent credit re-arms
    window_us:     modeled wall-clock per flush window (latency unit)
    seg_windows:   windows per dispatched device segment
    queue_depth:   staging slots (2 = classic double buffer)
    max_drain_segments: zero-traffic segments allowed before the final
                   uncredited walk (bounds shutdown under pathology)
    """

    capacity: int = 128
    link_credits: int = 64
    notify_latency: int = 2
    window_us: float = 100.0
    seg_windows: int = 8
    nx: int = 0
    ny: int = 0
    nz: int = 0
    wire_format: str = "extoll"
    queue_depth: int = 2
    max_drain_segments: int = 64


class WindowServeStats(NamedTuple):
    """Per-window, per-tenant device-side serving stats (all (T,) except
    the nested latency summary, whose fields lead with (T,))."""

    offered: jax.Array
    sent: jax.Array
    deferred: jax.Array
    parked: jax.Array
    unparked: jax.Array
    delivered: jax.Array
    shed: jax.Array
    latency: wire_latency.LatencySummary


class EngineReport(NamedTuple):
    """What a bounded run (or a stop) hands back."""

    tenants: list                 # list[tenancy.TenantDigest]
    injected: np.ndarray          # (T,) events staged to the device
    delivered: np.ndarray         # (T,) events that reached their owners
    shed: np.ndarray              # (T,) fresh events beyond backlog bound
    clipped: np.ndarray           # (T,) generator-side over-capacity drop
    windows: int                  # served windows (excl. drain)
    drain_windows: int            # zero-traffic windows run to quiesce
    wall_s: float                 # ingest start -> last absorb
    events_per_s: float           # delivered.sum() / wall_s
    conservation_checked: bool    # True iff drained and ledger verified


class SpikeEngine:
    """Multi-tenant streaming engine over one credit-partitioned fabric.

    ``source`` must provide ``next_window(window) -> WindowTraffic``
    (``repro.serve.loadgen.PoissonLoadGen`` is the reference); tenants
    and QoS come from ``tenancy.TenantSpec``.  Use :meth:`run` for a
    bounded number of segments or :meth:`start`/:meth:`stop` for
    continuous serving.
    """

    def __init__(self, mesh, axis_name: str,
                 tenants: Sequence[tenancy.TenantSpec],
                 cfg: EngineConfig, source,
                 fault_schedule: fabric_faults.FaultSchedule | None = None,
                 recorder: obs_recorder.RecorderConfig | None = None,
                 tracer: obs_spans.Tracer | None = None):
        self.mesh = mesh
        self.axis_name = axis_name
        self.tenants = tuple(tenants)
        self.cfg = cfg
        self.source = source
        self.fault_schedule = fault_schedule
        # Observability is strictly opt-in: with recorder=None the device
        # carry is the same 4-tuple (and lowers to the same HLO) as an
        # uninstrumented build; the NULL tracer appends nothing.
        self.recorder = recorder
        self.tracer = tracer if tracer is not None else obs_spans.NULL
        S = int(np.prod([mesh.shape[a] for a in mesh.shape]))
        T = len(self.tenants)
        if getattr(source, "n_tenants", T) != T:
            raise ValueError(f"source generates {source.n_tenants} "
                             f"tenants, engine serves {T}")
        if getattr(source, "capacity", cfg.capacity) != cfg.capacity:
            raise ValueError("source row capacity != engine capacity")
        if getattr(source, "n_shards", S) != S:
            raise ValueError("source n_shards != mesh size")
        self.n_shards, self.n_tenants = S, T
        self.transport = tenancy.build_fabric(
            S, self.tenants, link_credits=cfg.link_credits,
            notify_latency=cfg.notify_latency, nx=cfg.nx, ny=cfg.ny,
            nz=cfg.nz, max_row_events=cfg.capacity,
            wire_format=cfg.wire_format,
            stall_attribution=recorder is not None)
        self.ledger = tenancy.TenantLedger([t.name for t in self.tenants])
        self._build_device_fns()
        self._reset_runtime()

    # -- device functions --------------------------------------------------
    def _build_device_fns(self):
        S, T, C = self.n_shards, self.n_tenants, self.cfg.capacity
        nw = self.cfg.seg_windows
        ax = self.axis_name
        transport, cfg = self.transport, self.cfg
        fmt = transport.wire_fmt
        hops = transport.route_hops()                      # (n, n) const
        sched = self.fault_schedule
        pos = jnp.arange(C)[None, None, :]

        def attribute(out, win_abs):
            """Receiver-side per-event latency for one window's arrivals:
            whole-window waiting from the injection meta lane (covers
            deferral, backlog dwell AND park windows) + per-row wire time
            + queueing dwell behind parked traffic on the route.  Under
            fault injection, rows are charged the links they ACTUALLY
            traversed (detours included), not the shortest route."""
            me = lax.axis_index(ax)
            _, r_meta = codec.decode_planar(out.recv_payload)
            live = pos < out.recv_counts[..., None]        # (T, n, C)
            wait = ((win_abs - r_meta).astype(jnp.float32)
                    * jnp.float32(cfg.window_us))
            hops_row = (hops[:, me][None, :]
                        if out.links_used is None
                        else out.links_used[:, :, me])     # (T, n)
            row_us = (wire_latency.hop_latency_us(
                fmt, out.recv_counts, hops_row)
                + out.queue_us[:, :, me])                  # (T, n)
            lat = wait + row_us[..., None]
            summary = jax.vmap(wire_latency.summarize_latency)(
                lat.reshape(T, -1), live.reshape(T, -1).astype(jnp.int32))
            return summary, jnp.sum(out.recv_counts, axis=-1)

        rec = self.recorder is not None

        def seg_fn(state, bw, bm, bc, *rest):
            # rest is (ring, fw, fc_, win0) when the flight recorder rides
            # the carry, (fw, fc_, win0) otherwise — recorder=None keeps
            # the traced arity (and the lowered HLO) of an uninstrumented
            # build.
            state = jax.tree.map(lambda a: a[0], state)
            bw, bm, bc = bw[0], bm[0], bc[0]
            ring = jax.tree.map(lambda a: a[0], rest[0]) if rec else None
            fw, fc_ = rest[-3][0], rest[-2][0]  # (nw, T, n, C) / (nw, T, n)
            win0 = rest[-1]

            def window(carry, x):
                if rec:
                    state, bw, bm, bc, ring = carry
                else:
                    state, bw, bm, bc = carry
                fw_w, fc_w, i = x
                win_abs = win0 + i
                # FIFO merge: backlog (last window's deferred row) first,
                # fresh arrivals behind it, overflow beyond C is shed
                b = bc[..., None]
                sel_b = pos < b
                fw_g = jnp.take_along_axis(
                    fw_w, jnp.clip(pos - b, 0, C - 1), axis=-1)
                take_f = ~sel_b & (pos - b < fc_w[..., None])
                words = jnp.where(sel_b, bw,
                                  jnp.where(take_f, fw_g, jnp.uint32(0)))
                meta = jnp.where(sel_b, bm,
                                 jnp.where(take_f, win_abs, 0))
                cnt = jnp.minimum(bc + fc_w, C)
                shed = bc + fc_w - cnt
                payload = codec.encode_planar(words,
                                              meta.astype(jnp.int32))
                if sched is not None:
                    state = state._replace(
                        link_down=fabric_faults.mask_at(sched, win_abs))
                out = transport.exchange(state, payload, cnt,
                                         axis_name=ax)
                keep = ~out.sent_mask
                carry = (out.state,
                         jnp.where(keep[..., None], words, jnp.uint32(0)),
                         jnp.where(keep[..., None], meta, 0),
                         jnp.where(keep, cnt, 0))
                summary, delivered = attribute(out, win_abs)
                st = out.stats
                if rec:
                    carry = carry + (obs_recorder.record(
                        ring, win_abs, st, out.state, summary.hist),)
                ws = WindowServeStats(
                    offered=st.offered_events, sent=st.sent_events,
                    deferred=st.deferred_events,
                    parked=st.parked_events, unparked=st.unparked_events,
                    delivered=delivered.astype(jnp.int32),
                    shed=jnp.sum(shed, axis=-1).astype(jnp.int32),
                    latency=summary)
                return carry, ws

            init = (state, bw, bm, bc) + ((ring,) if rec else ())
            carry, ws = lax.scan(window, init, (fw, fc_, jnp.arange(nw)))
            lift = lambda t: jax.tree.map(lambda a: a[None], t)
            return lift(carry), lift(ws)

        def drain_fn(state, bw, bm, bc, win0):
            """Final walk: one uncredited flush of the backlog plus the
            transit-buffer drain — reuses ``drain_fabric`` so nothing the
            fabric still holds is lost across engine stop."""
            state = jax.tree.map(lambda a: a[0], state)
            bw, bm, bc = bw[0], bm[0], bc[0]
            payload = codec.encode_planar(bw, bm.astype(jnp.int32))
            out1 = transport.exchange(state, payload, bc, axis_name=ax,
                                      enforce_credits=False)
            s1, d1 = attribute(out1, win0)
            out2 = transport.drain_fabric(out1.state, axis_name=ax)
            s2, d2 = attribute(out2, win0)
            lift = lambda t: jax.tree.map(lambda a: a[None], t)
            return (lift(out2.state),
                    lift((s1, d1.astype(jnp.int32),
                          s2, d2.astype(jnp.int32))))

        spec = P(ax)
        n_carry = 5 if rec else 4
        self._seg = jax.jit(jax.shard_map(
            seg_fn, mesh=self.mesh,
            in_specs=(spec,) * n_carry + (spec, spec, P()),
            out_specs=(spec, spec), check_vma=False))
        self._drain_walk = jax.jit(jax.shard_map(
            drain_fn, mesh=self.mesh,
            in_specs=(spec, spec, spec, spec, P()),
            out_specs=(spec, spec), check_vma=False))

    # -- runtime state -----------------------------------------------------
    def _reset_runtime(self):
        S, T, C = self.n_shards, self.n_tenants, self.cfg.capacity
        nw, depth = self.cfg.seg_windows, self.cfg.queue_depth
        W = 2 * C                        # planar wire words per row
        # every per-shard operand lives on its own shard's device
        self._shard = NamedSharding(self.mesh, P(self.axis_name))
        put = lambda t: jax.device_put(t, self._shard)
        state0 = self.transport.init_state(W)
        bcast = lambda a: jnp.broadcast_to(a[None], (S,) + a.shape)
        self._carry = put((jax.tree.map(bcast, state0),
                           jnp.zeros((S, T, S, C), jnp.uint32),
                           jnp.zeros((S, T, S, C), jnp.int32),
                           jnp.zeros((S, T, S), jnp.int32)))
        if self.recorder is not None:
            # the flight-recorder ring rides as the 5th carry element;
            # credit lanes carry partition slots ((T+1)*K), the stall
            # lane stays physical (K directed links)
            ring0 = obs_recorder.ring_init(
                self.recorder.depth, state0, (T,),
                (T, wire_latency.N_LATENCY_BINS),
                S * self.transport.n_links)
            self._carry = self._carry + (put(jax.tree.map(bcast, ring0)),)
        # pinned staging pair: preallocated, filled in place by the
        # ingestion thread, handed to the devices by device_put (the
        # host->device copy from these fixed host buffers)
        self._words_buf = np.zeros((depth, S, nw, T, S, C), np.uint32)
        self._counts_buf = np.zeros((depth, S, nw, T, S), np.int32)
        self._zero_fw = put(jnp.zeros((S, nw, T, S, C), jnp.uint32))
        self._zero_fc = put(jnp.zeros((S, nw, T, S), jnp.int32))
        self._free_q: queue.Queue = queue.Queue()
        for i in range(depth):
            self._free_q.put(i)
        self._staged_q: queue.Queue = queue.Queue(maxsize=depth)
        self._stop_evt = threading.Event()
        self._ingest_t = self._device_t = None
        self._errors: list[Exception] = []     # host-thread failures
        self._max_segments = None
        self._win = 0
        self._windows = 0
        self._drain_windows = 0
        self._t0 = self._t1 = 0.0

    # -- host threads ------------------------------------------------------
    def _fill_segment(self, slot: int, seg: int):
        nw = self.cfg.seg_windows
        wbuf, cbuf = self._words_buf[slot], self._counts_buf[slot]
        inj = np.zeros((self.n_tenants,), np.int64)
        clip = np.zeros((self.n_tenants,), np.int64)
        with self.tracer.span("ingest/fill", track="spike-ingest",
                              seg=seg, win0=seg * nw):
            for i in range(nw):
                tr = self.source.next_window(seg * nw + i)
                # shard s offers rows (tenant, dst) = traffic[:, s, :]
                cbuf[:, i] = tr.counts.transpose(1, 0, 2)
                wbuf[:, i] = tr.words.transpose(1, 0, 2, 3)
                inj += tr.counts.astype(np.int64).sum((1, 2))
                clip += tr.clipped
        return inj, clip

    def _ingest_loop(self):
        seg = 0
        try:
            while not self._stop_evt.is_set():
                if (self._max_segments is not None
                        and seg >= self._max_segments):
                    break
                t0 = self.tracer.now_us()
                try:
                    slot = self._free_q.get(timeout=0.05)
                except queue.Empty:
                    continue
                self.tracer.complete("ingest/slot_wait", t0,
                                     self.tracer.now_us() - t0,
                                     track="spike-ingest", cat="host",
                                     slot=slot)
                inj, clip = self._fill_segment(slot, seg)
                self._staged_q.put((slot, inj, clip))
                seg += 1
        except Exception as e:           # re-raised by stop()
            self._errors.append(e)
        finally:
            self._staged_q.put(None)

    def _device_loop(self):
        prev = None
        ended = False                    # the ingest thread's None seen
        try:
            while True:
                with self.tracer.span("device/staged_wait",
                                      track="spike-device"):
                    item = self._staged_q.get()
                if item is None:
                    ended = True
                    break
                slot, inj, clip = item
                # the host copy matters: device_put may alias the host
                # buffer or read it after returning, and the ingest thread
                # refills the slot as soon as it is freed below
                with self.tracer.span("device/h2d", track="spike-device",
                                      slot=slot):
                    fw, fc_ = jax.device_put(
                        (self._words_buf[slot].copy(),
                         self._counts_buf[slot].copy()), self._shard)
                self._free_q.put(slot)   # staging slot reusable: the
                #                          host->device copy is done
                win0 = self._win
                with self.tracer.span("device/dispatch",
                                      track="spike-device", win0=win0):
                    self._carry, ws = self._seg(*self._carry, fw, fc_,
                                                jnp.int32(self._win))
                self._win += self.cfg.seg_windows
                self._windows += self.cfg.seg_windows
                self.ledger.add_injected(inj, clip)
                if prev is not None:     # absorb k-1 while k runs
                    self._absorb(*prev)
                prev = (ws, win0)
            if prev is not None:
                self._absorb(*prev)
        except Exception as e:           # e.g. a compile error; re-raised
            self._errors.append(e)       # by stop()
            self._stop_evt.set()
            while not ended:             # unblock the ingest thread
                ended = self._staged_q.get() is None
        self._t1 = self.tracer.now_us()

    def _absorb(self, ws: WindowServeStats, win0: int | None = None):
        t0 = self.tracer.now_us()
        ws = jax.tree.map(np.asarray, ws)        # blocks until ready
        self.ledger.add_windows(ws.delivered, ws.shed, ws.latency.hist,
                                ws.latency.max_us, ws.latency.mean_us)
        if self.tracer.enabled and win0 is not None:
            # the absorb block is where the host observes the async
            # segment completing; its bounds stand in for the device
            # segment on the trace, and the per-window instants carry
            # the same absolute window indices the wire words' meta lane
            # (and the flight-recorder ring) are stamped with
            nw = self.cfg.seg_windows
            self.tracer.complete("device/segment", t0,
                                 self.tracer.now_us() - t0, track="device",
                                 win0=win0, windows=nw)
            delivered = ws.delivered.sum(axis=(0, 2))      # (nw,)
            for i in range(nw):
                self.tracer.instant("window", track="device", cat="device",
                                    window=win0 + i,
                                    delivered=int(delivered[i]))

    # -- lifecycle ---------------------------------------------------------
    def start(self, max_segments: int | None = None):
        """Spawn the ingestion + device threads (continuous serving when
        ``max_segments`` is None)."""
        if self._ingest_t is not None:
            raise RuntimeError("engine already started")
        self._max_segments = max_segments
        self._t0 = self.tracer.now_us()
        self._ingest_t = threading.Thread(target=self._ingest_loop,
                                          name="spike-ingest", daemon=True)
        self._device_t = threading.Thread(target=self._device_loop,
                                          name="spike-device", daemon=True)
        self._ingest_t.start()
        self._device_t.start()

    def warmup(self) -> None:
        """Compile the segment + drain-walk functions with a zero-traffic
        dry run (both are pure; engine state is not mutated) so a bench's
        sustained-rate window excludes JIT time."""
        out = self._seg(*self._carry, self._zero_fw, self._zero_fc,
                        jnp.int32(0))
        jax.tree_util.tree_leaves(out)[0].block_until_ready()
        out = self._drain_walk(*self._carry[:4], jnp.int32(0))
        jax.tree_util.tree_leaves(out)[0].block_until_ready()

    def backlog_events(self) -> int:
        return int(np.asarray(self._carry[3]).sum())

    def in_fabric_events(self) -> int:
        pc = np.asarray(self._carry[0].parked_count)
        return int(pc[0].sum()) if pc.size else 0

    def recorder_rows(self, shard: int | None = None) -> list[dict]:
        """Decode the flight-recorder ring (requires ``recorder=``).

        ``shard=None`` returns global per-window rows (counter/hist lanes
        summed across shards); an integer returns that shard's raw view.
        """
        if self.recorder is None:
            raise RuntimeError("engine was built without a flight "
                               "recorder (pass recorder=RecorderConfig())")
        ring = self._carry[4]
        if shard is None:
            return obs_recorder.global_rows(ring, self.n_shards)
        return obs_recorder.ring_rows(obs_recorder.ring_shard(ring, shard))

    def _drain(self):
        """Quiesce: zero-traffic segments until backlog and fabric empty
        (bounded), then the final uncredited walk via ``drain_fabric``."""
        nw = self.cfg.seg_windows
        for _ in range(self.cfg.max_drain_segments):
            if self.backlog_events() == 0 and self.in_fabric_events() == 0:
                break
            win0 = self._win
            self._carry, ws = self._seg(*self._carry, self._zero_fw,
                                        self._zero_fc, jnp.int32(self._win))
            self._win += nw
            self._drain_windows += nw
            self._absorb(ws, win0)
        with self.tracer.span("drain/walk", track="spike-device",
                              win0=self._win):
            state, (s1, d1, s2, d2) = self._drain_walk(*self._carry[:4],
                                                       jnp.int32(self._win))
            zero = np.zeros_like(np.asarray(d1))
            for s, d in ((s1, d1), (s2, d2)):
                self.ledger.add_windows(np.asarray(d), zero,
                                        np.asarray(s.hist),
                                        np.asarray(s.max_us),
                                        np.asarray(s.mean_us))
        # the flight-recorder ring (carry[4:], when enabled) survives the
        # reset so post-run decoding sees the full served history
        self._carry = (state,
                       jnp.zeros_like(self._carry[1]),
                       jnp.zeros_like(self._carry[2]),
                       jnp.zeros_like(self._carry[3])) + self._carry[4:]

    def stop(self, drain: bool = True, timeout: float = 120.0
             ) -> EngineReport:
        """Graceful shutdown: stop ingestion, finish staged segments,
        drain the fabric, verify per-tenant conservation, report.

        An exception raised in the ingest or device thread (a source
        error, a compile failure on the device) is re-raised here."""
        if self._ingest_t is None:
            raise RuntimeError("engine not started")
        self._stop_evt.set()
        self._ingest_t.join(timeout)
        self._device_t.join(timeout)
        if self._ingest_t.is_alive() or self._device_t.is_alive():
            raise RuntimeError("engine threads failed to stop in time "
                               "(ingest alive=%s device alive=%s)" % (
                                   self._ingest_t.is_alive(),
                                   self._device_t.is_alive()))
        if self._errors:
            err = self._errors[0]
            self._errors.clear()
            self._ingest_t = self._device_t = None
            raise err
        if drain:
            self._drain()
            self.ledger.check_conservation()
        wall = max((self._t1 - self._t0) / 1e6, 1e-9)
        report = EngineReport(
            tenants=self.ledger.digests(),
            injected=self.ledger.injected.copy(),
            delivered=self.ledger.delivered.copy(),
            shed=self.ledger.shed.copy(),
            clipped=self.ledger.clipped.copy(),
            windows=self._windows,
            drain_windows=self._drain_windows,
            wall_s=wall,
            events_per_s=float(self.ledger.delivered.sum()) / wall,
            conservation_checked=bool(drain),
        )
        self._ingest_t = self._device_t = None
        return report

    def run(self, n_segments: int, drain: bool = True,
            timeout: float = 300.0) -> EngineReport:
        """Bounded serving run: ``n_segments`` segments, then stop."""
        self.start(max_segments=n_segments)
        self._device_t.join(timeout)
        return self.stop(drain=drain, timeout=timeout)
