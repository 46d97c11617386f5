"""Torus-faithful transport: dimension-ordered neighbor hops with
hop-by-hop credit flow control (paper §1 + §2.1, on the jitted hot path).

The Extoll fabric is a 3-D torus with dimension-ordered routing — a packet
walks its X ring to the destination column, then the Y ring, then the
Z ring (the wafer axis), taking the shortest signed direction on each ring
(the same walk ``repro.core.torus.Torus.route`` enumerates on the host).
This module reproduces that on a device mesh: the ``n_shards`` shards of
the 1-D shard_map axis are laid onto an (n0, .., n_{d-1}) logical torus
(``shard s -> (c0 = s % n0, c1 = (s // n0) % n1, ...)``, matching
``Torus.coords`` with (x, y, z) = (c0, c1, c2)) and each flush window
travels exclusively via ``jax.lax.ppermute`` *neighbor* hops — the lowered
HLO contains only collective-permutes, never an all-to-all or all-gather.

Per ring phase the algorithm is a bidirectional store-and-forward rotate:
every node seeds two in-transit buffers (one per ring direction) indexed by
absolute target coordinate, each hop ships the whole buffer one neighbor
over, the arriving node absorbs the bundle addressed to it and forwards the
rest.  After ``floor(n/2)`` forward and ``floor((n-1)/2)`` backward hops
every bundle has been delivered via its shortest path, so hop counts equal
``Torus.hops`` and per-window wire bytes decompose into per-link terms —
the quantities ``core.torus.link_loads`` models on the host become
measurable (``LinkStats``) in the jitted path.

Flow control is the credit discipline of ``repro.core.flow_control``,
**hop by hop**: the carried :class:`~repro.transport.base.FabricState`
holds a per-link credit bank for every egress link of every node (a
vectorized ``n_shards * 2 * ndim`` bank — links ordered (x+, x-, y+, y-,
z+, z-) per node, the same direction columns as ``core.torus.link_loads``)
plus bounded in-fabric **transit buffers**.  Admitting a bucket row spends
its event count on every link of its dimension-ordered route as it crosses
it, and spent credits only return ``notify_latency`` windows later (the
notification delay line).  A row that runs out of credits mid-route —
hop ``h >= 1`` — is NOT ejected back to the source: like a real Extoll
switch it **parks** in the store-and-forward buffer it already reached,
holding the arrival link's credit (``FabricState.parked_by_link``), and
the next window's admission drains parked rows *from their current hop*
ahead of every fresh offer.  Only a row refused at hop 0 — its own source
egress link — is *deferred*: reported through ``sent_mask`` so the caller
re-offers it via the overflow-residue machinery.  ``LinkStats`` separates
the two (``deferred/stalled_by_hop`` vs ``parked/unparked/parked_by_hop``)
and the conservation identities extend to
``offered == sent + deferred + parked`` per window and
``credits + pending + parked_by_link == limit`` per link, so mid-route
congestion is a measured, conserved quantity rather than averaged away.
Queueing dwell behind parked traffic feeds the wire-latency model
(``TransportOut.queue_us``, from ``repro.wire.latency.queueing_latency_us``).

Admission is computed identically on every shard (each shard carries the
same global bank): the per-shard offered counts are first replicated with
a dimension-wise ring all-gather built from the SAME neighbor ``ppermute``
rotations (nx-1 + ny-1 + nz-1 extra hops of a tiny (n, n) i32 matrix —
the Extoll notification traffic riding the data links), then every node
deterministically replays the same canonical-order admission, so the
distributed credit state never diverges.  When ``link_credits == 0`` the
fabric is unthrottled and the all-gather is compiled out entirely.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from typing import NamedTuple

from repro.core import aggregator
from repro.core import flow_control as fc
from repro.core.torus import Torus
from repro.transport import base
from repro.wire import framing as wire_framing
from repro.wire import latency as wire_latency


class AdmissionOut(NamedTuple):
    """Result of one window's deterministic admission replay (all shards
    compute the identical value from the replicated ``FabricState`` and
    the all-gathered counts matrix).  (n, n) fields are (src, dst)."""

    fresh_complete: jax.Array    # bool — fresh rows delivered this window
    fresh_park: jax.Array        # bool — fresh rows newly parked mid-route
    resumed_complete: jax.Array  # bool — parked rows that finished delivery
    resume_age: jax.Array        # i32 — windows the resumed rows had spent
                                 #   parked (0 for everything else)
    stall_hop: jax.Array         # i32 — blocking hop of DEFERRED rows, -1
    park_count: jax.Array        # i32 — post-window occupancy table
    park_hop: jax.Array          # i32 — post-window blocked-hop table
    park_age: jax.Array          # i32 — post-window ages (windows parked)
    parked_by_link: jax.Array    # (K,) i32 — post-window held units
    links_traversed: jax.Array   # i32 — links each row crossed THIS window
    spent: jax.Array             # (K,) i32 — subtracted from credits
    notify: jax.Array            # (K,) i32 — entering the delay line
    queue_events: jax.Array      # i32 — parked events queued ahead on the
                                 #   row's route at window start
    rerouted: jax.Array          # i32 — events delivered via a fault
                                 #   detour this window (0 when healthy)
    links_done: jax.Array        # i32 — route length (detours included)
                                 #   of rows DELIVERED this window, 0 else
                                 #   (the honest per-event hop charge)
    stalled_by_link: jax.Array | None = None  # (K,) i32 — deferred events
                                 #   per refusing PHYSICAL egress link
                                 #   (stall_attribution builds only; sums
                                 #   to the global deferred total)

def hop_histogram(hop: jax.Array, weight: jax.Array,
                  n_hops: int) -> jax.Array:
    """Sum ``weight`` into bins ``clip(hop, 0, n_hops - 1)`` along the
    last axis -> (..., n_hops) i32.

    A one-hot sum, not a scatter-add: when ``hop`` and ``weight`` fold to
    the same constant (an uncredited fabric never stalls), the TPU
    compiler's scatter emitter aborts on the merged operands.
    """
    bins = jnp.clip(hop, 0, n_hops - 1)[..., None] == jnp.arange(n_hops)
    return jnp.sum(jnp.where(bins, weight[..., None], 0),
                   axis=-2).astype(jnp.int32)


def default_shape(n_shards: int) -> tuple[int, int]:
    """Most-square (nx, ny) factorization with nx <= ny (8 -> (2, 4),
    matching the paper's 2x4 concentrator face per wafer)."""
    nx = max(int(math.isqrt(n_shards)), 1)
    while n_shards % nx:
        nx -= 1
    return nx, n_shards // nx


def default_shape3d(n_shards: int) -> tuple[int, int, int]:
    """Most-cubic (nx, ny, nz) factorization with nx <= ny <= nz
    (8 -> (2, 2, 2), 16 -> (2, 2, 4)).  Wafer-stacked setups that want the
    paper's (2, 4, n_wafers) arrangement pass nx/ny/nz explicitly."""
    best = (1, 1, n_shards)
    for nx in range(1, int(round(n_shards ** (1 / 3))) + 1):
        if n_shards % nx:
            continue
        ny, nz = default_shape(n_shards // nx)
        if ny >= nx:
            best = (nx, ny, nz)
    return best


class TorusTransport(base.Transport):
    """Dimension-ordered torus exchange with hop-by-hop per-link credits.

    ``prod(dims)`` must equal ``n_shards``.  ``link_credits=0`` disables
    throttling (links are provisioned far beyond any window's traffic);
    a positive value is the per-window event budget of EACH directed
    egress link in the fabric — injection *and* transit — replenished
    ``notify_latency`` windows after being spent.  Credits never exceed
    their initial limit, so ``link_credits`` must stay at or above the
    largest possible bucket row — a bigger row could never be admitted
    and would head-of-line-block its route forever.  Callers that know
    their row bound pass it as ``max_row_events`` (the bucket capacity;
    ``make_exchange`` and the simulator do) and construction fails fast
    on a livelock-able configuration.

    Admission discipline (canonical order, replayed identically on every
    node): rows are considered source-major, destination-minor, with the
    source order ROTATED by the bank's progress epoch (round-robin
    arbitration: the top-priority source advances one step on every
    window that spent credits, so two sources contending for the same
    saturated link alternate over progress rounds instead of the
    lower-index one winning forever — bounded starvation, worst-case
    ``n_shards`` progress rounds to reach top priority).  The epoch
    advances on progress rather than wall-clock windows so the rotation
    cannot phase-lock with the ``notify_latency`` refund cycle.  Parked
    rows resume first (from their current hop — see ``_admit_global``);
    then a fresh row is admitted iff its (src, dst) transit slot is free,
    its source egress FIFO is not already blocked this window, and it can
    cross at least its first link — completing if every route link has
    ``count`` credits, parking at the first short transit link otherwise.
    A row refused at hop 0 blocks every later row on the same source
    egress link (a hardware link FIFO cannot reorder its queue), even if
    a smaller row would still fit — the same head-of-line semantics the
    first-hop-only model had.  Parked rows hold their arrival link's
    credits, so buffer occupancy is bounded by ``link_credits`` per link
    and sustained overload spreads back-pressure upstream hop by hop
    (tree saturation) instead of dropping or unboundedly buffering data.
    Dimension-ordered routing breaks cross-dimension cycles, but — as on
    real credit fabrics without virtual channels — held buffers on one
    ring can in principle form a cyclic wait; the end-of-run
    :meth:`drain_fabric` walk always clears the fabric regardless.

    Memory note: the admission tables hold only the *active-route
    footprint* — the hop-ordered link sequence ``_link_seq`` of every
    (src, dst) pair, (n², max_hops) i32 with ``max_hops = sum(d // 2)``
    (~n^(1/ndim)) — NOT the dense (n², n·2·ndim) 0/1 route-incidence
    tensor an earlier revision materialized (cubic in shard count; the
    per-link need is recovered in-scan by gathering ``remaining`` at the
    route's links).  n=64 in 3-D is now 98 KiB instead of 3 MiB; a test
    pins the bound.  Thousand-node host-side studies still belong to
    ``core.torus.link_loads``.
    """

    name = "torus"

    def __init__(self, n_shards: int, dims: tuple[int, ...], *,
                 link_credits: int = 0, notify_latency: int = 2,
                 max_row_events: int = 0,
                 wire_format: str | wire_framing.WireFormat = "extoll",
                 stall_attribution: bool = False):
        super().__init__(n_shards, wire_format=wire_format)
        # per-link deferred-demand attribution for the flight recorder
        # (repro.obs) — a python-level static flag: False compiles the
        # exact pre-observability program (LinkStats.stalled_by_link
        # stays None, so stats pytree and lowered HLO are unchanged)
        self.stall_attribution = bool(stall_attribution)
        if 0 < link_credits < max_row_events:
            raise ValueError(
                f"link_credits ({link_credits}) must be >= the largest "
                f"bucket row ({max_row_events} events): credits never "
                f"exceed their initial limit, so an oversized row would "
                f"head-of-line-block its route forever")
        dims = tuple(int(d) for d in dims)
        if math.prod(dims) != n_shards:
            raise ValueError(f"mesh {dims} != n_shards {n_shards}")
        if not 1 <= len(dims) <= 3:
            raise ValueError(f"1..3 torus dimensions supported, got {dims}")
        self.dims = dims
        self.ndim = len(dims)
        self.n_links = 2 * self.ndim                  # per node
        self.link_credits = int(link_credits)
        self.notify_latency = int(notify_latency)
        # single source of truth for shard <-> coordinate mapping: the
        # host-side model (unused axes padded to 1) — the ppermute rings,
        # the credit routes and core.torus analysis can never disagree
        pad = dims + (1,) * (3 - self.ndim)
        self._host = Torus(nx=pad[0], ny=pad[1], nz=pad[2])
        self._perm = [
            (self._ring_perm(a, +1), self._ring_perm(a, -1))
            for a in range(self.ndim)
        ]
        self._build_routes()

    # -- static topology ---------------------------------------------------
    def _ring_perm(self, a: int, step: int):
        """(src, dst) pairs moving every shard one step along ring ``a``."""
        ids = np.arange(self.n_shards)
        c = list(self._host.coords(ids))
        c[a] = (c[a] + step) % self.dims[a]
        dst = self._host.node_id(*c)
        return list(zip(ids.tolist(), dst.astype(int).tolist()))

    def _build_routes(self):
        """Host-side precompute of the per-pair dimension-ordered routes.

        ``_link_seq[s*n+d]`` is the route s -> d as hop-ordered egress
        link ids (link id = node * n_links + direction, -1 pad; row 0 is
        all -1 for local rows) — the active-route footprint, (n²,
        max_hops) i32, which is ALL the admission scan needs: per-link
        credit needs are gathered/scattered at these ids instead of
        multiplying a dense (n², n·2·ndim) incidence tensor.  Derived
        from ``core.torus.Torus.route`` so the data path, the credit path
        and the host model can never disagree on a route.
        ``_hops_matrix`` is the host model's per-pair hop count, served
        to the wire-latency model via :meth:`route_hops`.
        """
        n, nl = self.n_shards, self.n_links
        host = self._host
        self.max_hops = max(sum(d // 2 for d in self.dims), 1)
        seq = np.full((n * n, self.max_hops), -1, np.int32)
        for s in range(n):
            for d in range(n):
                if s == d:
                    continue
                links = host.route_links(s, d)
                for h, (u, dir_) in enumerate(links):
                    seq[s * n + d, h] = u * nl + dir_
        self._link_seq = jnp.asarray(seq)
        self._route_len = jnp.asarray((seq >= 0).sum(-1).astype(np.int32))
        ids = np.arange(n)
        self._hops_matrix = jnp.asarray(
            host.hops(ids[:, None], ids[None, :]).astype(np.int32))

        # fault detours: for every subset of axes walking their ring the
        # long way around (combo bit a set = axis a detours), the full
        # hop-ordered link sequence, plus each axis' short/long segment
        # link sets — what the per-window reroute decision (dirty short
        # arc & clean long arc -> flip that axis) gathers the dead-link
        # mask over.  A long arc is at most ``d - 1`` hops, so these
        # tables are 2^ndim * (H_alt / H) bigger than ``_link_seq`` —
        # still the active-route footprint, never a dense incidence
        # tensor (n=64 in 3-D: ~1.2 MiB).
        self.max_hops_alt = max(sum(d - 1 for d in self.dims), 1)
        n_combo = 1 << self.ndim
        alt = np.full((n_combo, n * n, self.max_hops_alt), -1, np.int32)
        seg_len = max(max(d - 1 for d in self.dims), 1)
        seg = np.full((self.ndim, 2, n * n, seg_len), -1, np.int32)
        pad3 = (False,) * (3 - self.ndim)
        for s in range(n):
            for d in range(n):
                if s == d:
                    continue
                for a in range(self.ndim):
                    for var in (0, 1):
                        links = host.axis_segment_links(s, d, a,
                                                        longway=bool(var))
                        for h, (u, dir_) in enumerate(links):
                            seg[a, var, s * n + d, h] = u * nl + dir_
                for combo in range(n_combo):
                    flips = tuple(bool(combo >> a & 1)
                                  for a in range(self.ndim)) + pad3
                    links = host.route_links_detour(s, d, flips)
                    for h, (u, dir_) in enumerate(links):
                        alt[combo, s * n + d, h] = u * nl + dir_
        self._link_seq_alt = jnp.asarray(alt)
        self._route_len_alt = jnp.asarray(
            (alt >= 0).sum(-1).astype(np.int32))
        self._seg_links = jnp.asarray(seg)

    def route_hops(self) -> jax.Array:
        return self._hops_matrix

    # -- flow-control state ------------------------------------------------
    def init_state(self, payload_width: int = 0) -> base.LinkState:
        """Global bank + empty transit buffers.

        The bank holds one entry per directed egress link of EVERY node,
        replicated on each shard; it stays consistent because admission
        is a deterministic function of the all-gathered counts (see
        module docstring).  The transit tables (``FabricState``) are
        likewise replicated — only ``parked_payload`` is per-shard (this
        shard's rows' wire words), so throttled callers must pass the u32
        ``payload_width`` of the rows they will offer."""
        limit = self.link_credits if self.link_credits > 0 else 1 << 30
        bank = fc.init_credits(self.n_shards * self.n_links, limit,
                               self.notify_latency)
        if self.link_credits <= 0:
            # unthrottled: nothing can ever park — zero-size tables
            return base.init_fabric_state(bank)
        return base.init_fabric_state(bank, self.n_shards, payload_width)

    # -- replicating the offered counts (neighbor permutes only) -----------
    def _allgather_counts(self, counts: jax.Array, me, axis_name: str):
        """(n,) per-shard offered counts -> (n, n) global matrix via a
        dimension-wise ring all-gather: pass-and-accumulate a token one
        neighbor over, ``size-1`` hops per ring phase — the notification
        side-channel of §2.1 riding the same links as the data."""
        n = self.n_shards
        acc = jnp.zeros((n, n), jnp.int32).at[me].set(counts)
        for a in range(self.ndim):
            token = acc
            perm_p, _ = self._perm[a]
            for _ in range(self.dims[a] - 1):
                token = lax.ppermute(token, axis_name, perm_p)
                acc = acc + token
        return acc

    # -- canonical hop-by-hop admission with transit buffers ---------------
    def _stall_attr(self, stall_hop_flat: jax.Array,
                    counts_flat: jax.Array) -> jax.Array | None:
        """(K,) deferred events per refusing PHYSICAL egress link — the
        flight recorder's per-link congestion lane — or None unless built
        with ``stall_attribution=True`` (None keeps uninstrumented stats
        pytrees unchanged).

        Every deferral is a hop-0 refusal under the transit-buffer model
        (transit shortfalls park instead of deferring), so the blame
        lands on the row's first egress link.  Computed from the
        replicated admission replay, so the table is global — identical
        on every shard and summing to the GLOBAL deferred total.  Row
        axes longer than n² (the tenant replay's ``T*n²``) map onto
        physical pairs modulo n².
        """
        if not self.stall_attribution:
            return None
        n2 = self.n_shards * self.n_shards
        K = self.n_shards * self.n_links
        pair = jnp.arange(stall_hop_flat.shape[0]) % n2
        fl = self._link_seq[:, 0][pair]
        return jnp.zeros((K,), jnp.int32).at[jnp.maximum(fl, 0)].add(
            jnp.where((stall_hop_flat >= 0) & (fl >= 0), counts_flat, 0))

    def _admit_global(self, state: base.FabricState,
                      counts_all: jax.Array) -> AdmissionOut:
        """Replay the canonical two-phase admission over the global state.

        Pure function of (FabricState, counts_all): every shard computes
        the identical result, keeping the replicated bank AND transit
        tables consistent without extra synchronization.  Both phases
        process rows source-major, rotated by ``bank.epoch`` (round-robin
        arbitration over progress rounds, see class docstring):

        **Phase A — drain the fabric first.**  Every parked row tries to
        resume from its blocked hop ``h``: it advances over hops whose
        links still have ``count`` credits, stopping at the first short
        one.  A row that reaches the end of its route *completes* (its
        source injects the custody payload into this window's rotation);
        one that advances but blocks again re-parks at the new hop —
        releasing the old arrival link's held credit into the delay line
        and holding the new one's; one that cannot move keeps holding.

        **Phase B — fresh offers.**  A routed row whose (src, dst) slot
        is free and whose source FIFO is not head-of-line blocked walks
        its route the same way: all links free → admitted and delivered;
        short at hop ``h >= 1`` → enters the fabric, crosses hops
        ``0..h-1`` and parks at ``h`` (the arrival link's credit is held,
        the earlier hops' spends enter the delay line normally); short at
        hop 0 → never enters the fabric: *deferred* at the sender
        (``stall_hop = 0``) and its egress FIFO head-of-line blocks every
        later row this window.
        """
        n, K, H = self.n_shards, self.n_shards * self.n_links, self.max_hops
        flat = counts_all.reshape(-1)
        pc0 = state.parked_count.reshape(-1)
        ph0 = state.parked_hop.reshape(-1)
        pa0 = state.parked_age.reshape(-1)
        r_all = jnp.arange(n * n)
        rows = ((r_all // n + state.bank.epoch) % n) * n + r_all % n
        hop_idx = jnp.arange(H)

        # congestion snapshot: events already parked in the buffers along
        # each row's REMAINING route at window start (the queueing-latency
        # term).  A parked row's gather starts at its blocked hop, which
        # excludes both its own held events (they sit on the arrival link
        # at hop h-1) and traffic parked behind it — a lone row resuming
        # through an otherwise empty fabric charges exactly zero.
        valid_all = self._link_seq >= 0
        idx_all = jnp.maximum(self._link_seq, 0)
        start_hop = jnp.where(pc0 > 0, ph0, 0)[:, None]       # (n², 1)
        queue_events = jnp.sum(
            jnp.where(valid_all & (jnp.arange(H)[None, :] >= start_hop),
                      state.parked_by_link[idx_all], 0),
            axis=-1).reshape(n, n)

        def resume(carry, r):
            remaining, notify, pbl = carry
            c, h = pc0[r], ph0[r]
            active = c > 0
            seq = self._link_seq[r]                     # (H,) hop-ordered
            idx = jnp.maximum(seq, 0)
            valid = seq >= 0
            L = self._route_len[r]
            rem_at = remaining[idx]
            short = valid & (hop_idx >= h) & (rem_at < c)
            h_new = jnp.min(jnp.where(short, hop_idx, H))
            complete = active & (h_new >= L)
            h_stop = jnp.maximum(jnp.where(complete, L, h_new), h)
            moved = active & (h_stop > h)
            trav = valid & (hop_idx >= h) & (hop_idx < h_stop) & active
            remaining = remaining.at[idx].add(-jnp.where(trav, c, 0))
            # the last traversed link becomes the new hold when re-parking
            new_hold = moved & ~complete
            at_hold = new_hold & (hop_idx == h_stop - 1)
            notify = notify.at[idx].add(jnp.where(trav & ~at_hold, c, 0))
            pbl = pbl.at[idx].add(jnp.where(at_hold, c, 0))
            # departing the old park spot releases its held arrival credit
            # (hop-0 parks — fault evictions that failed to retry — hold
            # nothing, so nothing to release)
            oh = jnp.maximum(seq[jnp.maximum(h - 1, 0)], 0)
            rel = jnp.where(moved & (h >= 1), c, 0)
            notify = notify.at[oh].add(rel)
            pbl = pbl.at[oh].add(-rel)
            out = (complete, jnp.where(complete, 0, c),
                   jnp.where(active & ~complete, h_stop, 0),
                   jnp.where(complete, pa0[r], 0),
                   jnp.where(active & ~complete, pa0[r] + 1, 0),
                   jnp.sum(trav.astype(jnp.int32)))
            return (remaining, notify, pbl), out

        carry = (state.bank.credits, jnp.zeros((K,), jnp.int32),
                 state.parked_by_link)
        carry, (res_c, pc_a, ph_a, age_res, age_a, trav_a) = lax.scan(
            resume, carry, rows)

        def offer(carry, r):
            remaining, notify, pbl, blocked = carry
            c = flat[r]
            seq = self._link_seq[r]
            idx = jnp.maximum(seq, 0)
            valid = seq >= 0
            L = self._route_len[r]
            fl = seq[0]
            routed = (fl >= 0) & (c > 0)
            slot_busy = pc0[r] > 0          # in-order per (src, dst) flow
            hol = blocked[jnp.maximum(fl, 0)]
            rem_at = remaining[idx]
            short = valid & (rem_at < c)
            h_block = jnp.min(jnp.where(short, hop_idx, H))
            ok = routed & ~slot_busy & ~hol
            admit_c = ok & (h_block >= L)
            admit_p = ok & (h_block < L) & (h_block >= 1)
            defer = routed & ~admit_c & ~admit_p
            h_stop = jnp.where(admit_c, L, jnp.where(admit_p, h_block, 0))
            trav = valid & (hop_idx < h_stop)
            remaining = remaining.at[idx].add(-jnp.where(trav, c, 0))
            at_hold = admit_p & (hop_idx == h_stop - 1)
            notify = notify.at[idx].add(jnp.where(trav & ~at_hold, c, 0))
            pbl = pbl.at[idx].add(jnp.where(at_hold, c, 0))
            blocked = blocked.at[jnp.maximum(fl, 0)].set(
                blocked[jnp.maximum(fl, 0)] | defer)
            # a deferred row never left the source: every deferral is a
            # hop-0 (egress FIFO) stall under the transit-buffer model
            out = (admit_c, admit_p, jnp.where(defer, 0, -1), h_stop,
                   jnp.sum(trav.astype(jnp.int32)))
            return (remaining, notify, pbl, blocked), out

        carry = (*carry, jnp.zeros((K,), bool))
        (remaining, notify, pbl, _), (adm_c, adm_p, stall, hp_b, trav_b) = \
            lax.scan(offer, carry, rows)

        # un-rotate: scan outputs are in processing order, rows[i] -> i
        def unrot(x, fill, dtype):
            return jnp.full((n * n,), fill, dtype).at[rows].set(x)

        fresh_complete = unrot(adm_c, False, bool)
        fresh_park = unrot(adm_p, False, bool)
        resumed_complete = unrot(res_c, False, bool)
        stall_hop = unrot(stall, -1, jnp.int32)
        hp_fresh = unrot(hp_b, 0, jnp.int32)
        park_count = jnp.where(fresh_park, flat, unrot(pc_a, 0, jnp.int32))
        park_hop = jnp.where(fresh_park, hp_fresh, unrot(ph_a, 0, jnp.int32))
        # a freshly parked row enters at age 1: by the earliest window it
        # can resume it will have waited one full window
        park_age = jnp.where(fresh_park, 1, unrot(age_a, 0, jnp.int32))
        links_traversed = (unrot(trav_a, 0, jnp.int32)
                           + unrot(trav_b, 0, jnp.int32))
        return AdmissionOut(
            fresh_complete=fresh_complete.reshape(n, n),
            fresh_park=fresh_park.reshape(n, n),
            resumed_complete=resumed_complete.reshape(n, n),
            resume_age=unrot(age_res, 0, jnp.int32).reshape(n, n),
            stall_hop=stall_hop.reshape(n, n),
            park_count=park_count.reshape(n, n),
            park_hop=park_hop.reshape(n, n),
            park_age=park_age.reshape(n, n),
            parked_by_link=pbl,
            links_traversed=links_traversed.reshape(n, n),
            spent=state.bank.credits - remaining,
            notify=notify,
            queue_events=queue_events,
            rerouted=jnp.zeros((n, n), jnp.int32),
            links_done=jnp.where(
                fresh_complete | resumed_complete,
                self._route_len, 0).astype(jnp.int32).reshape(n, n),
            stalled_by_link=self._stall_attr(stall_hop, flat),
        )

    # -- fault-aware admission ---------------------------------------------
    def _admit_global_faulted(self, state: base.FabricState,
                              counts_all: jax.Array,
                              link_down: jax.Array) -> AdmissionOut:
        """The canonical two-phase replay under a per-window dead-link
        mask.  Same deterministic structure as :meth:`_admit_global`,
        with three fault rules layered on:

        * **Reroute** — per row and axis, if the short ring arc crosses a
          dead link and the long arc is clean, that axis walks the long
          way around (``_link_seq_alt``); if both arcs are dead the row
          is *unroutable* this window (deferred without blocking its
          egress FIFO — it never reaches the link queue).  The per-axis
          flip rule keeps the decision local to each ring phase, so the
          rotation makes the identical choice from the same mask.
        * **Eviction** — a parked row whose REMAINING route touches a
          dead link, or whose held arrival link died under it, abandons
          its progress: the held credit releases into the delay line and
          the row retries from hop 0 on the current detour route (phase
          A, ahead of fresh offers).  A failed retry leaves it parked at
          hop 0 holding nothing; its custody payload stays in the fabric.
        * **All-or-nothing detours** — a detoured row (flip combo != 0)
          either completes or stays put: parking mid-route is only
          meaningful against the *default* route (the mask changes every
          window, so a detour hop index would dangle).  Rows on the
          default route park/resume exactly as in the healthy replay.

        Dead links admit nothing because every chosen route is clean by
        construction — no spend, hold or notify ever touches one, and
        eviction clears ``parked_by_link`` on a dying link the window it
        dies.
        """
        n, K = self.n_shards, self.n_shards * self.n_links
        H2 = self.max_hops_alt
        flat = counts_all.reshape(-1)
        pc0 = state.parked_count.reshape(-1)
        ph0 = state.parked_hop.reshape(-1)
        pa0 = state.parked_age.reshape(-1)
        r_all = jnp.arange(n * n)
        rows = ((r_all // n + state.bank.epoch) % n) * n + r_all % n
        hop_idx = jnp.arange(H2)
        down = link_down

        # per-pair reroute decision from the window's mask
        seg = self._seg_links                          # (ndim, 2, n², Hs)
        seg_dirty = (down[jnp.maximum(seg, 0)] & (seg >= 0)).any(-1)
        flip = seg_dirty[:, 0] & ~seg_dirty[:, 1]      # (ndim, n²)
        routable_all = ~(seg_dirty[:, 0] & seg_dirty[:, 1]).any(0)
        combo = jnp.sum(flip.astype(jnp.int32)
                        * (1 << jnp.arange(self.ndim))[:, None], axis=0)
        seq_eff_all = self._link_seq_alt[combo, r_all]  # (n², H2)
        len_eff_all = self._route_len_alt[combo, r_all]
        detour_all = combo != 0
        seq0_all = jnp.pad(self._link_seq,
                           ((0, 0), (0, H2 - self.max_hops)),
                           constant_values=-1)

        # eviction set: parked rows whose remaining default route died,
        # whose held arrival link died, or already sitting at hop 0 from
        # an earlier failed retry (they re-run the retry path each
        # window until credits + a clean route let them through)
        valid0 = seq0_all >= 0
        rem_dirty = (valid0 & (hop_idx[None, :] >= ph0[:, None])
                     & down[jnp.maximum(seq0_all, 0)]).any(-1)
        held_link = jnp.take_along_axis(
            seq0_all, jnp.maximum(ph0 - 1, 0)[:, None], axis=1)[:, 0]
        held_dead = (ph0 >= 1) & down[jnp.maximum(held_link, 0)]
        ev_all = (pc0 > 0) & ((ph0 == 0) | rem_dirty | held_dead)

        # congestion snapshot over the routes rows will actually take
        pbl0 = state.parked_by_link
        seq_q = jnp.where((pc0 > 0)[:, None], seq0_all, seq_eff_all)
        start_hop = jnp.where((pc0 > 0) & ~ev_all, ph0, 0)[:, None]
        queue_events = jnp.sum(
            jnp.where((seq_q >= 0) & (hop_idx[None, :] >= start_hop),
                      pbl0[jnp.maximum(seq_q, 0)], 0),
            axis=-1).reshape(n, n)

        def resume(carry, r):
            remaining, notify, pbl = carry
            c, h = pc0[r], ph0[r]
            active = c > 0
            ev = ev_all[r]
            # branch 1 — undisturbed resume on the default route
            seq = seq0_all[r]
            idx = jnp.maximum(seq, 0)
            valid = seq >= 0
            L = self._route_len[r]
            rem_at = remaining[idx]
            short = valid & (hop_idx >= h) & (rem_at < c)
            h_new = jnp.min(jnp.where(short, hop_idx, H2))
            act1 = active & ~ev
            complete1 = act1 & (h_new >= L)
            h_stop1 = jnp.maximum(jnp.where(complete1, L, h_new), h)
            moved1 = act1 & (h_stop1 > h)
            trav1 = valid & (hop_idx >= h) & (hop_idx < h_stop1) & act1
            remaining = remaining.at[idx].add(-jnp.where(trav1, c, 0))
            at_hold1 = moved1 & ~complete1 & (hop_idx == h_stop1 - 1)
            notify = notify.at[idx].add(jnp.where(trav1 & ~at_hold1, c, 0))
            pbl = pbl.at[idx].add(jnp.where(at_hold1, c, 0))
            # branch 2 — evicted retry from hop 0 on the detour route
            seq2 = seq_eff_all[r]
            idx2 = jnp.maximum(seq2, 0)
            valid2 = seq2 >= 0
            L2 = len_eff_all[r]
            act2 = active & ev & routable_all[r]
            short2 = valid2 & (remaining[idx2] < c)
            h_block = jnp.min(jnp.where(short2, hop_idx, H2))
            complete2 = act2 & (h_block >= L2)
            park2 = (act2 & ~detour_all[r] & (h_block < L2)
                     & (h_block >= 1))
            h_stop2 = jnp.where(complete2, L2, jnp.where(park2, h_block, 0))
            trav2 = valid2 & (hop_idx < h_stop2)
            remaining = remaining.at[idx2].add(-jnp.where(trav2, c, 0))
            at_hold2 = park2 & (hop_idx == h_stop2 - 1)
            notify = notify.at[idx2].add(jnp.where(trav2 & ~at_hold2, c, 0))
            pbl = pbl.at[idx2].add(jnp.where(at_hold2, c, 0))
            # departing (or being evicted from) the old park spot
            # releases its held arrival credit into the delay line
            oh = jnp.maximum(seq[jnp.maximum(h - 1, 0)], 0)
            rel = jnp.where(moved1 | (active & ev & (h >= 1)), c, 0)
            notify = notify.at[oh].add(rel)
            pbl = pbl.at[oh].add(-rel)
            complete = complete1 | complete2
            keep = active & ~complete
            h_keep = jnp.where(ev, jnp.where(park2, h_block, 0), h_stop1)
            out = (complete, jnp.where(complete, 0, c),
                   jnp.where(keep, h_keep, 0),
                   jnp.where(complete, pa0[r], 0),
                   jnp.where(keep, pa0[r] + 1, 0),
                   jnp.sum(trav1.astype(jnp.int32))
                   + jnp.sum(trav2.astype(jnp.int32)),
                   jnp.where(complete2 & detour_all[r], c, 0),
                   jnp.where(complete1, L, 0) + jnp.where(complete2, L2, 0))
            return (remaining, notify, pbl), out

        carry = (state.bank.credits, jnp.zeros((K,), jnp.int32),
                 state.parked_by_link)
        carry, (res_c, pc_a, ph_a, age_res, age_a, trav_a, rer_a,
                done_a) = lax.scan(resume, carry, rows)

        def offer(carry, r):
            remaining, notify, pbl, blocked = carry
            c = flat[r]
            seq = seq_eff_all[r]
            idx = jnp.maximum(seq, 0)
            valid = seq >= 0
            L = len_eff_all[r]
            rt = routable_all[r]
            fl = seq[0]
            routed = (fl >= 0) & (c > 0) & rt
            slot_busy = pc0[r] > 0
            hol = blocked[jnp.maximum(fl, 0)]
            rem_at = remaining[idx]
            short = valid & (rem_at < c)
            h_block = jnp.min(jnp.where(short, hop_idx, H2))
            ok = routed & ~slot_busy & ~hol
            admit_c = ok & (h_block >= L)
            # parking mid-route only against the default route; a
            # detoured offer is all-or-nothing
            admit_p = (ok & ~detour_all[r] & (h_block < L)
                       & (h_block >= 1))
            defer = ((fl >= 0) & (c > 0)) & ~admit_c & ~admit_p
            h_stop = jnp.where(admit_c, L, jnp.where(admit_p, h_block, 0))
            trav = valid & (hop_idx < h_stop)
            remaining = remaining.at[idx].add(-jnp.where(trav, c, 0))
            at_hold = admit_p & (hop_idx == h_stop - 1)
            notify = notify.at[idx].add(jnp.where(trav & ~at_hold, c, 0))
            pbl = pbl.at[idx].add(jnp.where(at_hold, c, 0))
            # an unroutable row never reaches its egress FIFO, so it
            # cannot head-of-line-block the rows behind it
            blocked = blocked.at[jnp.maximum(fl, 0)].set(
                blocked[jnp.maximum(fl, 0)] | (defer & rt))
            out = (admit_c, admit_p, jnp.where(defer, 0, -1), h_stop,
                   jnp.sum(trav.astype(jnp.int32)),
                   jnp.where(admit_c & detour_all[r], c, 0),
                   jnp.where(admit_c, L, 0))
            return (remaining, notify, pbl, blocked), out

        carry = (*carry, jnp.zeros((K,), bool))
        (remaining, notify, pbl, _), (adm_c, adm_p, stall, hp_b, trav_b,
                                      rer_b, done_b) = lax.scan(
            offer, carry, rows)

        def unrot(x, fill, dtype):
            return jnp.full((n * n,), fill, dtype).at[rows].set(x)

        fresh_complete = unrot(adm_c, False, bool)
        fresh_park = unrot(adm_p, False, bool)
        resumed_complete = unrot(res_c, False, bool)
        stall_hop = unrot(stall, -1, jnp.int32)
        hp_fresh = unrot(hp_b, 0, jnp.int32)
        park_count = jnp.where(fresh_park, flat, unrot(pc_a, 0, jnp.int32))
        park_hop = jnp.where(fresh_park, hp_fresh, unrot(ph_a, 0, jnp.int32))
        park_age = jnp.where(fresh_park, 1, unrot(age_a, 0, jnp.int32))
        links_traversed = (unrot(trav_a, 0, jnp.int32)
                           + unrot(trav_b, 0, jnp.int32))
        return AdmissionOut(
            fresh_complete=fresh_complete.reshape(n, n),
            fresh_park=fresh_park.reshape(n, n),
            resumed_complete=resumed_complete.reshape(n, n),
            resume_age=unrot(age_res, 0, jnp.int32).reshape(n, n),
            stall_hop=stall_hop.reshape(n, n),
            park_count=park_count.reshape(n, n),
            park_hop=park_hop.reshape(n, n),
            park_age=park_age.reshape(n, n),
            parked_by_link=pbl,
            links_traversed=links_traversed.reshape(n, n),
            spent=state.bank.credits - remaining,
            notify=notify,
            queue_events=queue_events,
            rerouted=(unrot(rer_a, 0, jnp.int32)
                      + unrot(rer_b, 0, jnp.int32)).reshape(n, n),
            links_done=(unrot(done_a, 0, jnp.int32)
                        + unrot(done_b, 0, jnp.int32)).reshape(n, n),
            stalled_by_link=self._stall_attr(stall_hop, flat),
        )

    # -- one bidirectional ring phase --------------------------------------
    def _ring_phase(self, bundles, axis_name, my_c, n, perm_p, perm_m,
                    acc: dict, phase: int,
                    count_cols: tuple[int, ...] = (-1,),
                    fault=None):
        """Rotate (n, B, W1) count-packed bundles (indexed by target ring
        coordinate) to their owners; returns them indexed by *source* ring
        coordinate.  ``acc`` accumulates LinkStats terms across phases.

        ``count_cols`` names the bitcast-i32 count columns inside each
        bundle row: a single-tenant row is one frame train with its count
        in the last column; a multi-tenant row concatenates one
        count-packed sub-row per tenant, each its own frame train on the
        wire (tenants are separate logical streams), so byte/occupancy
        accounting sums over every tenant's count column.

        ``fault`` is ``None`` on a healthy fabric (the unchanged fast
        path), or ``(down_plus, down_minus)`` — per ring coordinate, is
        that node's +/- link of this axis dead this window.  Each node
        then flips the bundles whose short arc crosses a dead link to
        the long way around (matching the admission replay's per-axis
        rule — same mask, same links, same decision), both direction
        loops extend to ``n - 1`` hops, and absorption accumulates
        (a source delivers via + or via -, never both).  A bundle slot
        crossing a dead link is zero by construction: any row still
        aboard at that hop has the dead link in its arc and was either
        flipped to the other direction or refused by admission.
        """
        coord = jnp.arange(n)
        fwd = (coord - my_c) % n
        short_plus = fwd <= n // 2
        if fault is None:
            plus = (fwd >= 1) & short_plus
            minus = fwd > n // 2
            hops_p, hops_m = n // 2, (n - 1) // 2
        else:
            down_p, down_m = fault
            # OR of the first k links walking +/- from my coordinate
            cum_p = jnp.cumsum(
                down_p[(my_c + coord) % n].astype(jnp.int32))
            cum_m = jnp.cumsum(
                down_m[(my_c - coord) % n].astype(jnp.int32))

            def arc_dirty(cum, k):
                return (k >= 1) & (cum[jnp.maximum(k - 1, 0)] > 0)

            dirty_p = arc_dirty(cum_p, fwd)
            dirty_m = arc_dirty(cum_m, (n - fwd) % n)
            short_dirty = jnp.where(short_plus, dirty_p, dirty_m)
            long_dirty = jnp.where(short_plus, dirty_m, dirty_p)
            flip = short_dirty & ~long_dirty
            use_plus = jnp.logical_xor(short_plus, flip)
            plus = (fwd >= 1) & use_plus
            minus = (fwd >= 1) & ~use_plus
            hops_p = hops_m = n - 1
        vp = jnp.where(plus[:, None, None], bundles, jnp.uint32(0))
        vm = jnp.where(minus[:, None, None], bundles, jnp.uint32(0))
        recv = jnp.zeros_like(bundles)
        recv = recv.at[my_c].set(jnp.take(bundles, my_c, axis=0))
        cols = jnp.asarray(
            np.asarray(count_cols, np.int32) % bundles.shape[-1])

        def bundle_counts(v):        # (n, B, n_cols) i32
            return lax.bitcast_convert_type(v[:, :, cols], jnp.int32)

        def live_events(v):
            return jnp.sum(bundle_counts(v))

        def wire(v):
            return aggregator.window_cost(bundle_counts(v).reshape(-1)).bytes

        def owire(v):
            # exact frame-level bytes of this hop: every count-packed
            # sub-row is one frame train of the backend's WireFormat
            return jnp.sum(wire_framing.frame_bytes(self.wire_fmt,
                                                    bundle_counts(v)))

        for direction, v, perm, n_hops in (
            ("+", vp, perm_p, hops_p),
            ("-", vm, perm_m, hops_m),
        ):
            for h in range(1, n_hops + 1):
                acc["bytes"] += wire(v)
                acc["owire"] += owire(v)
                v = lax.ppermute(v, axis_name, perm)
                src = (my_c - h) % n if direction == "+" else (my_c + h) % n
                got = jnp.take(v, my_c, axis=0)
                if fault is None:
                    recv = recv.at[src].set(got)
                else:
                    recv = recv.at[src].add(got)
                v = v.at[my_c].set(jnp.uint32(0))
                acc["hops"] += 1
                occ = live_events(v)
                acc["in_flight"] = jnp.maximum(acc["in_flight"], occ)
                acc["in_flight_phase"][phase] = jnp.maximum(
                    acc["in_flight_phase"][phase], occ)
        # everything within shortest (or detour) distance was absorbed
        return recv

    def _phase_fault(self, down, a: int, me, my_c):
        """Slice the (K,) dead-link mask into this node's axis-``a`` ring
        view: per ring coordinate, is that node's +/- link of the axis
        dead.  Node at ring coordinate c is ``me + (c - my_c) * stride``
        (only the axis-a digit of the flattened id changes)."""
        if down is None:
            return None
        stride = int(np.prod(self.dims[:a], dtype=np.int64)) if a else 1
        ring_nodes = me + (jnp.arange(self.dims[a]) - my_c) * stride
        return (down[ring_nodes * self.n_links + 2 * a],
                down[ring_nodes * self.n_links + 2 * a + 1])

    # -- phase reshapes ----------------------------------------------------
    # The (n, W1) buffer keeps a fixed layout: flattened index
    # c0 + n0*c1 + n0*n1*c2 where axis-a's coordinate is the DESTINATION
    # coordinate before phase a has run and the SOURCE coordinate after.
    def _phase_perm(self, a: int):
        nd = self.ndim
        lead = nd - 1 - a            # axis of dim ``a`` in the reshaped view
        perm = (lead, *(i for i in range(nd) if i != lead), nd)
        return perm, tuple(int(i) for i in np.argsort(perm))

    def _to_phase(self, buf: jax.Array, a: int) -> jax.Array:
        w1 = buf.shape[-1]
        t = buf.reshape(*reversed(self.dims), w1)
        perm, _ = self._phase_perm(a)
        return t.transpose(perm).reshape(self.dims[a], -1, w1)

    def _from_phase(self, recv: jax.Array, a: int) -> jax.Array:
        w1 = recv.shape[-1]
        perm, inv = self._phase_perm(a)
        other = [d for i, d in enumerate(reversed(self.dims))
                 if i != self.ndim - 1 - a]
        t = recv.reshape(self.dims[a], *other, w1).transpose(inv)
        return t.reshape(self.n_shards, w1)

    # -- the full window ---------------------------------------------------
    def exchange(self, state: base.LinkState, payload: jax.Array,
                 counts: jax.Array, *, axis_name: str,
                 enforce_credits: bool = True) -> base.TransportOut:
        n = self.n_shards
        me = lax.axis_index(axis_name)
        counts = counts.astype(jnp.int32)
        is_local = jnp.arange(n) == me
        zero_q = jnp.zeros((n, n), jnp.float32)
        down = state.link_down      # per-window fault mask (usually None)

        # 1. injection: hop-by-hop credit admission over the whole route,
        #    transit buffers drained first (compiled out when unthrottled
        #    — no all-gather, no scan, no tables)
        throttled = enforce_credits and self.link_credits > 0
        if down is not None and not throttled:
            raise ValueError(
                "fault injection (FabricState.link_down) requires credit "
                "flow control: an unthrottled fabric has no per-link "
                "admission to refuse at a dead link (set link_credits > 0)")
        if throttled:
            if state.parked_payload.shape != payload.shape:
                raise ValueError(
                    f"FabricState payload buffer {state.parked_payload.shape}"
                    f" != offered payload {payload.shape}: initialize with "
                    f"init_state(payload_width=W) so parked rows keep "
                    f"custody of their wire words")
            counts_all = self._allgather_counts(counts, me, axis_name)
            adm = (self._admit_global_faulted(state, counts_all, down)
                   if down is not None
                   else self._admit_global(state, counts_all))
            fresh_c = adm.fresh_complete[me]
            fresh_p = adm.fresh_park[me]
            resumed = adm.resumed_complete[me]
            stall_hop = adm.stall_hop[me]
            pc0_me = state.parked_count[me]
            # rotation rows: fresh completions ship the caller's payload,
            # resumed rows ship the fabric's custody copy (disjoint per
            # destination — a fresh row behind a parked one is deferred)
            ship_fresh = fresh_c | (is_local & (counts > 0))
            cnt_in = (jnp.where(ship_fresh, counts, 0)
                      + jnp.where(resumed, pc0_me, 0))
            row_payload = jnp.where(
                resumed[:, None], state.parked_payload,
                jnp.where(ship_fresh[:, None], payload, jnp.uint32(0)))
            # advance the carried fabric state: custody payload slots of
            # newly parked rows are overwritten, completed slots expire
            # with their zeroed counts
            bank = fc.credit_tick(state.bank, adm.spent, notify=adm.notify)
            state = base.FabricState(
                bank=bank,
                parked_count=adm.park_count,
                parked_hop=adm.park_hop,
                parked_age=adm.park_age,
                parked_by_link=adm.parked_by_link,
                parked_payload=jnp.where(fresh_p[:, None], payload,
                                         state.parked_payload),
                parked_hold_shared=jnp.zeros_like(adm.park_count),
            )
            sent_mask = fresh_c | fresh_p | is_local | (counts == 0)
            sent_now = fresh_c | is_local | (counts == 0)
            queue_us = wire_latency.queueing_latency_us(
                self.wire_fmt, adm.queue_events)
            # park dwell of the rows delivered from the fabric: per window
            # parked, one link credit budget had to drain ahead of them
            park_wait_us = wire_latency.queueing_latency_us(
                self.wire_fmt, adm.resume_age * self.link_credits)
        else:
            fresh_p = resumed = jnp.zeros((n,), bool)
            pc0_me = jnp.zeros((n,), jnp.int32)
            stall_hop = jnp.full((n,), -1, jnp.int32)
            cnt_in = counts
            row_payload = payload
            state = state._replace(bank=fc.credit_tick(
                state.bank, jnp.zeros_like(state.bank.credits)),
                link_down=None)
            sent_mask = sent_now = jnp.ones((n,), bool)
            queue_us = park_wait_us = zero_q
        packed = base.pack_payload(row_payload, cnt_in)

        acc = {"bytes": jnp.int32(0), "owire": jnp.int32(0), "hops": 0,
               "in_flight": jnp.int32(0),
               "in_flight_phase": [jnp.int32(0)] * self.ndim}

        # 2. dimension-ordered phases: rotate along each axis' rings
        my_c = self._coords_of(me)
        buf = packed
        for a in range(self.ndim):
            bundles = self._to_phase(buf, a)
            perm_p, perm_m = self._perm[a]
            recv = self._ring_phase(bundles, axis_name, my_c[a],
                                    self.dims[a], perm_p, perm_m, acc,
                                    phase=a,
                                    fault=self._phase_fault(down, a, me,
                                                            my_c[a]))
            buf = self._from_phase(recv, a)
        recv_payload, recv_counts = base.unpack_payload(buf)

        # 3. stats: deferred rows histogrammed by their blocking hop,
        #    parked rows by the hop they wait at
        stalled_by_hop = hop_histogram(
            stall_hop, jnp.where(stall_hop >= 0, counts, 0), self.max_hops)
        offered = jnp.sum(counts).astype(jnp.int32)
        if throttled:
            sent = jnp.sum(jnp.where(sent_now, counts, 0)).astype(jnp.int32)
            parked = jnp.sum(jnp.where(fresh_p, counts, 0)).astype(jnp.int32)
            unparked = jnp.sum(
                jnp.where(resumed, pc0_me, 0)).astype(jnp.int32)
            pk_cnt, pk_hop = state.parked_count[me], state.parked_hop[me]
            parked_by_hop = hop_histogram(pk_hop, pk_cnt, self.max_hops)
            # frame-exact bytes: each row pays one frame-train
            # re-serialization per link it crossed THIS window, so across
            # park/resume windows every route link is counted exactly once
            c_row = jnp.where(resumed, pc0_me, counts)
            owire = jnp.sum(wire_framing.frame_bytes(self.wire_fmt, c_row)
                            * adm.links_traversed[me]).astype(jnp.int32)
            dwell = jnp.sum(jnp.where(
                fresh_c | resumed, queue_us[me] + park_wait_us[me],
                0.0)).astype(jnp.float32)
            rerouted = jnp.sum(adm.rerouted[me]).astype(jnp.int32)
        else:
            sent = jnp.sum(cnt_in).astype(jnp.int32)
            parked = unparked = jnp.zeros((), jnp.int32)
            parked_by_hop = jnp.zeros((self.max_hops,), jnp.int32)
            owire = acc["owire"].astype(jnp.int32)
            dwell = jnp.zeros((), jnp.float32)
            rerouted = jnp.zeros((), jnp.int32)
        stats = base.LinkStats(
            offered_events=offered,
            sent_events=sent,
            deferred_events=offered - sent - parked,
            delivered_events=jnp.sum(recv_counts).astype(jnp.int32),
            credit_stalls=jnp.sum(stall_hop >= 0).astype(jnp.int32),
            hops=jnp.int32(acc["hops"]),
            forwarded_bytes=acc["bytes"].astype(jnp.int32),
            bytes_on_wire=owire,
            max_in_flight=acc["in_flight"].astype(jnp.int32),
            stalled_by_hop=stalled_by_hop,
            max_in_flight_by_phase=jnp.stack(acc["in_flight_phase"]),
            parked_events=parked,
            unparked_events=unparked,
            in_fabric_events=jnp.sum(state.parked_count[me]).astype(
                jnp.int32) if throttled else jnp.zeros((), jnp.int32),
            parked_by_hop=parked_by_hop,
            queue_dwell_us=dwell,
            rerouted=rerouted,
            stalled_by_link=adm.stalled_by_link if throttled else None,
        )
        return base.TransportOut(
            state=state,
            recv_payload=recv_payload,
            recv_counts=recv_counts,
            sent_mask=sent_mask,
            stats=stats,
            sent_now=sent_now,
            queue_us=queue_us,
            unparked_now=jnp.where(resumed, pc0_me, 0),
            park_wait_us=park_wait_us,
            links_used=adm.links_done if down is not None else None,
        )

    # -- end-of-run fabric walk --------------------------------------------
    def drain_fabric(self, state: base.LinkState, *, axis_name: str,
                     payload_width: int | None = None) -> base.TransportOut:
        """Walk the transit buffers until the fabric is empty.

        Every parked row resumes from its blocked hop and completes —
        credits are ignored (the end-of-run flush quiesces the fabric, so
        downstream buffer space is guaranteed to free up) and every held
        credit is released into the notification delay line, restoring
        ``credits + pending == limit`` on every link.  With at most one
        parked row per (src, dst) pair a single rotation sweep delivers
        everything; the returned state has empty tables, which tests pin.
        Byte accounting charges each row's REMAINING links only, so a
        route is still counted exactly once across its lifetime.
        """
        n = self.n_shards
        me = lax.axis_index(axis_name)
        if state.parked_count.size == 0:    # unthrottled: nothing parked
            return super().drain_fabric(state, axis_name=axis_name,
                                        payload_width=payload_width)
        pc_me = state.parked_count[me]
        ph_me = state.parked_hop[me]
        packed = base.pack_payload(
            jnp.where((pc_me > 0)[:, None], state.parked_payload,
                      jnp.uint32(0)), pc_me)

        acc = {"bytes": jnp.int32(0), "owire": jnp.int32(0), "hops": 0,
               "in_flight": jnp.int32(0),
               "in_flight_phase": [jnp.int32(0)] * self.ndim}
        my_c = self._coords_of(me)
        buf = packed
        for a in range(self.ndim):
            bundles = self._to_phase(buf, a)
            perm_p, perm_m = self._perm[a]
            recv = self._ring_phase(bundles, axis_name, my_c[a],
                                    self.dims[a], perm_p, perm_m, acc,
                                    phase=a)
            buf = self._from_phase(recv, a)
        recv_payload, recv_counts = base.unpack_payload(buf)

        bank = fc.credit_tick(state.bank,
                              jnp.zeros_like(state.bank.credits),
                              notify=state.parked_by_link)
        new_state = base.FabricState(
            bank=bank,
            parked_count=jnp.zeros_like(state.parked_count),
            parked_hop=jnp.zeros_like(state.parked_hop),
            parked_age=jnp.zeros_like(state.parked_age),
            parked_by_link=jnp.zeros_like(state.parked_by_link),
            parked_payload=jnp.zeros_like(state.parked_payload),
            parked_hold_shared=jnp.zeros_like(state.parked_hold_shared),
        )
        remaining_links = jnp.maximum(self._hops_matrix[me] - ph_me, 0)
        owire = jnp.sum(
            wire_framing.frame_bytes(self.wire_fmt, pc_me)
            * jnp.where(pc_me > 0, remaining_links, 0)).astype(jnp.int32)
        unparked = jnp.sum(pc_me).astype(jnp.int32)
        stats = base.zero_link_stats(self.max_hops, self.ndim)._replace(
            delivered_events=jnp.sum(recv_counts).astype(jnp.int32),
            unparked_events=unparked,
            hops=jnp.int32(acc["hops"]),
            forwarded_bytes=acc["bytes"].astype(jnp.int32),
            bytes_on_wire=owire,
            max_in_flight=acc["in_flight"].astype(jnp.int32),
            max_in_flight_by_phase=jnp.stack(acc["in_flight_phase"]),
        )
        return base.TransportOut(
            state=new_state,
            recv_payload=recv_payload,
            recv_counts=recv_counts,
            sent_mask=jnp.ones((n,), bool),
            stats=stats,
            sent_now=jnp.ones((n,), bool),
            queue_us=jnp.zeros((n, n), jnp.float32),
            unparked_now=pc_me,
            park_wait_us=jnp.zeros((n, n), jnp.float32),
        )

    def _coords_of(self, me):
        """Traced shard index -> per-dimension ring coordinates."""
        out = []
        for d in self.dims:
            out.append(me % d)
            me = me // d
        return out


class Torus2DTransport(TorusTransport):
    """(nx, ny) torus — the per-wafer concentrator face (2x4 for 8)."""

    name = "torus2d"

    def __init__(self, n_shards: int, *, nx: int = 0, ny: int = 0,
                 link_credits: int = 0, notify_latency: int = 2,
                 max_row_events: int = 0,
                 wire_format: str | wire_framing.WireFormat = "extoll",
                 stall_attribution: bool = False):
        if not nx and not ny:
            nx, ny = default_shape(n_shards)
        elif not ny:
            ny = n_shards // max(nx, 1)
        elif not nx:
            nx = n_shards // max(ny, 1)
        super().__init__(n_shards, (nx, ny), link_credits=link_credits,
                         notify_latency=notify_latency,
                         max_row_events=max_row_events,
                         wire_format=wire_format,
                         stall_attribution=stall_attribution)
        self.nx, self.ny = nx, ny


class Torus3DTransport(TorusTransport):
    """(nx, ny, nz) torus — wafer faces stacked along the Z (wafer) axis,
    the paper's full Extoll arrangement (``core.torus.wafer_topology``)."""

    name = "torus3d"

    def __init__(self, n_shards: int, *, nx: int = 0, ny: int = 0,
                 nz: int = 0, link_credits: int = 0, notify_latency: int = 2,
                 max_row_events: int = 0,
                 wire_format: str | wire_framing.WireFormat = "extoll",
                 stall_attribution: bool = False):
        known = [d for d in (nx, ny, nz) if d]
        if not known:
            nx, ny, nz = default_shape3d(n_shards)
        elif len(known) == 1:
            # one axis pinned (typically nz = wafer count): most-square
            # factorization of the rest onto the remaining face
            rest = n_shards // known[0]
            if nz:
                nx, ny = default_shape(rest)
            elif ny:
                nx, nz = default_shape(rest)
            else:
                ny, nz = default_shape(rest)
        elif len(known) == 2:
            missing = n_shards // max(math.prod(known), 1)
            nx, ny, nz = (nx or missing, ny or missing, nz or missing)
        super().__init__(n_shards, (nx, ny, nz), link_credits=link_credits,
                         notify_latency=notify_latency,
                         max_row_events=max_row_events,
                         wire_format=wire_format,
                         stall_attribution=stall_attribution)
        self.nx, self.ny, self.nz = nx, ny, nz


# ---------------------------------------------------------------------------
# Multi-tenant torus: N concurrent experiments on one fabric with per-tenant
# QoS credit partitioning (the serving substrate of ``repro.serve``).
# ---------------------------------------------------------------------------

class TenantAdmissionOut(NamedTuple):
    """Tenant-axis admission replay result; (T, n, n) fields are
    (tenant, src, dst), slot arrays are ``(T+1)*K``."""

    fresh_complete: jax.Array
    fresh_park: jax.Array
    resumed_complete: jax.Array
    resume_age: jax.Array
    stall_hop: jax.Array
    park_count: jax.Array
    park_hop: jax.Array
    park_age: jax.Array
    hold_shared: jax.Array       # (T, n, n) post-window shared-pool holds
    parked_by_link: jax.Array    # ((T+1)*K,) post-window held units per slot
    links_traversed: jax.Array
    spent: jax.Array             # ((T+1)*K,)
    notify: jax.Array            # ((T+1)*K,)
    queue_events: jax.Array      # (T, n, n) parked events queued ahead
    rerouted: jax.Array          # (T, n, n) events delivered via detour
    links_done: jax.Array        # (T, n, n) delivered-route link counts
    stalled_by_link: jax.Array | None = None  # (K,) deferred events per
                                 #   refusing PHYSICAL link, all tenants
                                 #   pooled (stall_attribution builds)


class TenantTorusTransport(TorusTransport):
    """Torus exchange multiplexing T tenants with partitioned credits.

    Same fabric, same dimension-ordered routes, same store-and-forward
    ring phases — but every physical link's credit budget is split by a
    :class:`repro.core.flow_control.CreditPartition` into one guaranteed
    slice per tenant plus a shared best-effort pool, realised as a bank
    of ``(T+1) * K`` slots that ``credit_tick`` advances unmodified.

    Admission discipline on top of the single-tenant rules (see
    :class:`TorusTransport`):

    * **Reserved-first spending** — a row of tenant ``t`` crossing link
      ``l`` draws ``min(count, slice)`` from slot ``t*K + l`` and the
      remainder from the shared slot ``T*K + l``; it is admitted across a
      link iff slice + shared cover the full count.  No tenant can draw
      another tenant's slice, so tenant ``t`` is guaranteed
      ``reserve[t] // max(notify_latency, 1)`` events per link per window
      of sustained admission regardless of co-tenant congestion — the
      QoS floor ``BENCH_serve.json`` pins.
    * **(tenant, source) round-robin rotation** — the canonical order
      walks rows combined-index-major, ``(t*n + s)`` rotated by the
      bank's progress epoch, so priority alternates over tenants as well
      as sources: bounded starvation in both axes.
    * **Per-tenant egress FIFOs** — a deferred row head-of-line blocks
      only its OWN tenant's later rows on that egress link (each tenant
      has its own injection queue at the NIC, as with Extoll VPIDs); the
      co-tenant's traffic on the same link is judged purely on credits.
    * **Holds release to the right slot** — a parked row's held
      arrival-link credit remembers its reserved/shared split
      (``FabricState.parked_hold_shared``) and refunds accordingly on
      departure, so per-slot conservation
      ``credits + pending + parked_by_link == slot_limit`` holds for all
      ``(T+1)*K`` slots.

    Payloads/counts carry a leading tenant axis — ``payload (T, n, W)``,
    ``counts (T, n)`` — and every ``TransportOut`` field comes back with
    the same leading axis (``stats`` fields are per-tenant; fabric-level
    fields that have no per-tenant decomposition — hops, forwarded_bytes,
    max_in_flight — are attributed to tenant slot 0 so tenant-axis sums
    remain physical).  On the wire the T tenants' sub-rows of one
    destination travel in the same ring-phase bundle but as separate
    count-packed frame trains (separate logical streams).
    """

    name = "torus_tenant"

    def __init__(self, n_shards: int, dims: tuple[int, ...], *,
                 partition: fc.CreditPartition, notify_latency: int = 2,
                 max_row_events: int = 0,
                 wire_format: str | wire_framing.WireFormat = "extoll",
                 stall_attribution: bool = False):
        if partition.limit <= 0:
            raise ValueError("tenant partitioning needs link_credits > 0 "
                             "(an unthrottled fabric has nothing to split)")
        if max_row_events > 0:
            for t, r in enumerate(partition.reserve):
                if r + partition.shared < max_row_events:
                    raise ValueError(
                        f"tenant {t}: reserve ({r}) + shared "
                        f"({partition.shared}) < largest bucket row "
                        f"({max_row_events}): its biggest row could never "
                        f"be admitted and would head-of-line-block forever")
        super().__init__(n_shards, dims, link_credits=partition.limit,
                         notify_latency=notify_latency,
                         max_row_events=max_row_events,
                         wire_format=wire_format,
                         stall_attribution=stall_attribution)
        self.partition = partition
        self.n_tenants = partition.n_tenants

    # -- flow-control state ------------------------------------------------
    def init_state(self, payload_width: int = 0) -> base.LinkState:
        """Partitioned bank + tenant-axis transit tables."""
        T, n = self.n_tenants, self.n_shards
        K = n * self.n_links
        bank = fc.init_partitioned_credits(self.partition, K,
                                           self.notify_latency)
        return base.FabricState(
            bank=bank,
            parked_count=jnp.zeros((T, n, n), jnp.int32),
            parked_hop=jnp.zeros((T, n, n), jnp.int32),
            parked_age=jnp.zeros((T, n, n), jnp.int32),
            parked_by_link=jnp.zeros(((T + 1) * K,), jnp.int32),
            parked_payload=jnp.zeros((T, n, payload_width), jnp.uint32),
            parked_hold_shared=jnp.zeros((T, n, n), jnp.int32),
        )

    def _allgather_counts_mt(self, counts: jax.Array, me, axis_name: str):
        """(T, n) per-shard offered counts -> (T, n, n) global tensor,
        same dimension-wise ring all-gather as the single-tenant path."""
        n, T = self.n_shards, self.n_tenants
        acc = jnp.zeros((n, T, n), jnp.int32).at[me].set(counts)
        for a in range(self.ndim):
            token = acc
            perm_p, _ = self._perm[a]
            for _ in range(self.dims[a] - 1):
                token = lax.ppermute(token, axis_name, perm_p)
                acc = acc + token
        return acc.transpose(1, 0, 2)

    # -- tenant-aware canonical admission ----------------------------------
    def _admit_tenants(self, state: base.FabricState,
                       counts_all: jax.Array) -> TenantAdmissionOut:
        """Deterministic replay over ``T * n^2`` rows with reserved-first
        spending.  Same two phases as ``_admit_global`` — parked rows
        resume first, fresh offers second — with three per-tenant twists:
        availability on a link is ``slice + shared``, spends/holds are
        split reserved-first across the two slots, and the HOL ``blocked``
        array is per (tenant, egress link).
        """
        n, T, H = self.n_shards, self.n_tenants, self.max_hops
        K = n * self.n_links
        flat = counts_all.reshape(-1)                   # (T*n²,)
        pc0 = state.parked_count.reshape(-1)
        ph0 = state.parked_hop.reshape(-1)
        pa0 = state.parked_age.reshape(-1)
        hs0 = state.parked_hold_shared.reshape(-1)
        r_all = jnp.arange(T * n * n)
        # round-robin over the combined (tenant, source) index
        comb = (r_all // n + state.bank.epoch) % (T * n)
        rows = comb * n + r_all % n
        hop_idx = jnp.arange(H)

        # congestion snapshot over PHYSICAL links (a queued event delays
        # everyone crossing that link, whatever slot funded it)
        pbl_phys = state.parked_by_link.reshape(T + 1, K).sum(0)
        valid_all = self._link_seq >= 0                  # (n², H)
        idx_all = jnp.maximum(self._link_seq, 0)
        pair_all = jnp.arange(T * n * n) % (n * n)
        start_hop = jnp.where(pc0 > 0, ph0, 0)[:, None]
        queue_events = jnp.sum(
            jnp.where(valid_all[pair_all]
                      & (jnp.arange(H)[None, :] >= start_hop),
                      pbl_phys[idx_all[pair_all]], 0),
            axis=-1).reshape(T, n, n)

        def split_spend(remaining, t, idx, trav, c):
            """Reserved-first draw of ``c`` units at each traversed link;
            returns (remaining', take_r, take_s) with per-hop splits."""
            slot_r = t * K + idx
            slot_s = T * K + idx
            take_r = jnp.where(trav, jnp.minimum(c, remaining[slot_r]), 0)
            take_s = jnp.where(trav, c - take_r, 0)
            remaining = remaining.at[slot_r].add(-take_r)
            remaining = remaining.at[slot_s].add(-take_s)
            return remaining, take_r, take_s

        def resume(carry, r):
            remaining, notify, pbl = carry
            t = r // (n * n)
            pair = r % (n * n)
            c, h, hs = pc0[r], ph0[r], hs0[r]
            active = c > 0
            seq = self._link_seq[pair]
            idx = jnp.maximum(seq, 0)
            valid = seq >= 0
            L = self._route_len[pair]
            avail = remaining[t * K + idx] + remaining[T * K + idx]
            short = valid & (hop_idx >= h) & (avail < c)
            h_new = jnp.min(jnp.where(short, hop_idx, H))
            complete = active & (h_new >= L)
            h_stop = jnp.maximum(jnp.where(complete, L, h_new), h)
            moved = active & (h_stop > h)
            trav = valid & (hop_idx >= h) & (hop_idx < h_stop) & active
            remaining, take_r, take_s = split_spend(remaining, t, idx,
                                                    trav, c)
            new_hold = moved & ~complete
            at_hold = new_hold & (hop_idx == h_stop - 1)
            notify = notify.at[t * K + idx].add(
                jnp.where(at_hold, 0, take_r))
            notify = notify.at[T * K + idx].add(
                jnp.where(at_hold, 0, take_s))
            pbl = pbl.at[t * K + idx].add(jnp.where(at_hold, take_r, 0))
            pbl = pbl.at[T * K + idx].add(jnp.where(at_hold, take_s, 0))
            hs_new = jnp.sum(jnp.where(at_hold, take_s, 0))
            # departing the old park spot releases its held arrival
            # credit back to the slots that funded it (hop-0 parks from
            # fault evictions hold nothing)
            oh = jnp.maximum(seq[jnp.maximum(h - 1, 0)], 0)
            held = moved & (h >= 1)
            rel_s = jnp.where(held, hs, 0)
            rel_r = jnp.where(held, c, 0) - rel_s
            notify = notify.at[t * K + oh].add(rel_r)
            notify = notify.at[T * K + oh].add(rel_s)
            pbl = pbl.at[t * K + oh].add(-rel_r)
            pbl = pbl.at[T * K + oh].add(-rel_s)
            keep = active & ~complete
            out = (complete, jnp.where(complete, 0, c),
                   jnp.where(keep, h_stop, 0),
                   jnp.where(complete, pa0[r], 0),
                   jnp.where(keep, pa0[r] + 1, 0),
                   jnp.sum(trav.astype(jnp.int32)),
                   jnp.where(keep, jnp.where(moved, hs_new, hs), 0))
            return (remaining, notify, pbl), out

        S = (T + 1) * K
        carry = (state.bank.credits, jnp.zeros((S,), jnp.int32),
                 state.parked_by_link)
        carry, (res_c, pc_a, ph_a, age_res, age_a, trav_a, hs_a) = lax.scan(
            resume, carry, rows)

        def offer(carry, r):
            remaining, notify, pbl, blocked = carry
            t = r // (n * n)
            pair = r % (n * n)
            c = flat[r]
            seq = self._link_seq[pair]
            idx = jnp.maximum(seq, 0)
            valid = seq >= 0
            L = self._route_len[pair]
            fl = seq[0]
            routed = (fl >= 0) & (c > 0)
            slot_busy = pc0[r] > 0
            bl_idx = t * K + jnp.maximum(fl, 0)
            hol = blocked[bl_idx]
            avail = remaining[t * K + idx] + remaining[T * K + idx]
            short = valid & (avail < c)
            h_block = jnp.min(jnp.where(short, hop_idx, H))
            ok = routed & ~slot_busy & ~hol
            admit_c = ok & (h_block >= L)
            admit_p = ok & (h_block < L) & (h_block >= 1)
            defer = routed & ~admit_c & ~admit_p
            h_stop = jnp.where(admit_c, L, jnp.where(admit_p, h_block, 0))
            trav = valid & (hop_idx < h_stop)
            remaining, take_r, take_s = split_spend(remaining, t, idx,
                                                    trav, c)
            at_hold = admit_p & (hop_idx == h_stop - 1)
            notify = notify.at[t * K + idx].add(
                jnp.where(at_hold, 0, take_r))
            notify = notify.at[T * K + idx].add(
                jnp.where(at_hold, 0, take_s))
            pbl = pbl.at[t * K + idx].add(jnp.where(at_hold, take_r, 0))
            pbl = pbl.at[T * K + idx].add(jnp.where(at_hold, take_s, 0))
            blocked = blocked.at[bl_idx].set(hol | defer)
            out = (admit_c, admit_p, jnp.where(defer, 0, -1), h_stop,
                   jnp.sum(trav.astype(jnp.int32)),
                   jnp.sum(jnp.where(at_hold, take_s, 0)))
            return (remaining, notify, pbl, blocked), out

        carry = (*carry, jnp.zeros((T * K,), bool))
        (remaining, notify, pbl, _), \
            (adm_c, adm_p, stall, hp_b, trav_b, hs_b) = lax.scan(
                offer, carry, rows)

        def unrot(x, fill, dtype):
            return jnp.full((T * n * n,), fill, dtype).at[rows].set(x)

        fresh_complete = unrot(adm_c, False, bool)
        fresh_park = unrot(adm_p, False, bool)
        park_count = jnp.where(fresh_park, flat, unrot(pc_a, 0, jnp.int32))
        park_hop = jnp.where(fresh_park, unrot(hp_b, 0, jnp.int32),
                             unrot(ph_a, 0, jnp.int32))
        park_age = jnp.where(fresh_park, 1, unrot(age_a, 0, jnp.int32))
        hold_shared = jnp.where(fresh_park, unrot(hs_b, 0, jnp.int32),
                                unrot(hs_a, 0, jnp.int32))
        links_traversed = (unrot(trav_a, 0, jnp.int32)
                           + unrot(trav_b, 0, jnp.int32))
        shape3 = (T, n, n)
        return TenantAdmissionOut(
            fresh_complete=fresh_complete.reshape(shape3),
            fresh_park=fresh_park.reshape(shape3),
            resumed_complete=unrot(res_c, False, bool).reshape(shape3),
            resume_age=unrot(age_res, 0, jnp.int32).reshape(shape3),
            stall_hop=unrot(stall, -1, jnp.int32).reshape(shape3),
            park_count=park_count.reshape(shape3),
            park_hop=park_hop.reshape(shape3),
            park_age=park_age.reshape(shape3),
            hold_shared=hold_shared.reshape(shape3),
            parked_by_link=pbl,
            links_traversed=links_traversed.reshape(shape3),
            spent=state.bank.credits - remaining,
            notify=notify,
            queue_events=queue_events,
            rerouted=jnp.zeros(shape3, jnp.int32),
            links_done=jnp.where(
                fresh_complete.reshape(shape3)
                | unrot(res_c, False, bool).reshape(shape3),
                self._route_len.reshape(n, n)[None], 0).astype(jnp.int32),
            stalled_by_link=self._stall_attr(
                unrot(stall, -1, jnp.int32), flat),
        )

    def _admit_tenants_faulted(self, state: base.FabricState,
                               counts_all: jax.Array,
                               link_down: jax.Array) -> TenantAdmissionOut:
        """Tenant-axis admission under a dead-link mask: the fault rules
        of :meth:`_admit_global_faulted` (per-axis reroute, eviction back
        to hop 0, all-or-nothing detours) with the reserved-first
        spending and split-exact hold refunds of :meth:`_admit_tenants`.
        """
        n, T = self.n_shards, self.n_tenants
        K = n * self.n_links
        H2 = self.max_hops_alt
        flat = counts_all.reshape(-1)
        pc0 = state.parked_count.reshape(-1)
        ph0 = state.parked_hop.reshape(-1)
        pa0 = state.parked_age.reshape(-1)
        hs0 = state.parked_hold_shared.reshape(-1)
        r_all = jnp.arange(T * n * n)
        comb = (r_all // n + state.bank.epoch) % (T * n)
        rows = comb * n + r_all % n
        hop_idx = jnp.arange(H2)
        down = link_down
        pair_of = r_all % (n * n)

        # per-PAIR reroute decision (shared by every tenant: the mask is
        # physical, not per slot)
        seg = self._seg_links
        seg_dirty = (down[jnp.maximum(seg, 0)] & (seg >= 0)).any(-1)
        flip = seg_dirty[:, 0] & ~seg_dirty[:, 1]
        routable_pair = ~(seg_dirty[:, 0] & seg_dirty[:, 1]).any(0)
        combo = jnp.sum(flip.astype(jnp.int32)
                        * (1 << jnp.arange(self.ndim))[:, None], axis=0)
        pair_idx = jnp.arange(n * n)
        seq_eff_pair = self._link_seq_alt[combo, pair_idx]   # (n², H2)
        len_eff_pair = self._route_len_alt[combo, pair_idx]
        detour_pair = combo != 0
        seq0_pair = jnp.pad(self._link_seq,
                            ((0, 0), (0, H2 - self.max_hops)),
                            constant_values=-1)

        # eviction set over the (T, n, n) row tables
        seq0_rows = seq0_pair[pair_of]                       # (Tn², H2)
        rem_dirty = ((seq0_rows >= 0)
                     & (hop_idx[None, :] >= ph0[:, None])
                     & down[jnp.maximum(seq0_rows, 0)]).any(-1)
        held_link = jnp.take_along_axis(
            seq0_rows, jnp.maximum(ph0 - 1, 0)[:, None], axis=1)[:, 0]
        held_dead = (ph0 >= 1) & down[jnp.maximum(held_link, 0)]
        ev_all = (pc0 > 0) & ((ph0 == 0) | rem_dirty | held_dead)

        # congestion snapshot over PHYSICAL links on the actual routes
        pbl_phys = state.parked_by_link.reshape(T + 1, K).sum(0)
        seq_q = jnp.where((pc0 > 0)[:, None], seq0_rows,
                          seq_eff_pair[pair_of])
        start_hop = jnp.where((pc0 > 0) & ~ev_all, ph0, 0)[:, None]
        queue_events = jnp.sum(
            jnp.where((seq_q >= 0) & (hop_idx[None, :] >= start_hop),
                      pbl_phys[jnp.maximum(seq_q, 0)], 0),
            axis=-1).reshape(T, n, n)

        def split_spend(remaining, t, idx, trav, c):
            slot_r = t * K + idx
            slot_s = T * K + idx
            take_r = jnp.where(trav, jnp.minimum(c, remaining[slot_r]), 0)
            take_s = jnp.where(trav, c - take_r, 0)
            remaining = remaining.at[slot_r].add(-take_r)
            remaining = remaining.at[slot_s].add(-take_s)
            return remaining, take_r, take_s

        def resume(carry, r):
            remaining, notify, pbl = carry
            t = r // (n * n)
            pair = r % (n * n)
            c, h, hs = pc0[r], ph0[r], hs0[r]
            active = c > 0
            ev = ev_all[r]
            # branch 1 — undisturbed resume on the default route
            seq = seq0_pair[pair]
            idx = jnp.maximum(seq, 0)
            valid = seq >= 0
            L = self._route_len[pair]
            avail = remaining[t * K + idx] + remaining[T * K + idx]
            short = valid & (hop_idx >= h) & (avail < c)
            h_new = jnp.min(jnp.where(short, hop_idx, H2))
            act1 = active & ~ev
            complete1 = act1 & (h_new >= L)
            h_stop1 = jnp.maximum(jnp.where(complete1, L, h_new), h)
            moved1 = act1 & (h_stop1 > h)
            trav1 = valid & (hop_idx >= h) & (hop_idx < h_stop1) & act1
            remaining, take_r1, take_s1 = split_spend(remaining, t, idx,
                                                      trav1, c)
            at_hold1 = moved1 & ~complete1 & (hop_idx == h_stop1 - 1)
            notify = notify.at[t * K + idx].add(
                jnp.where(at_hold1, 0, take_r1))
            notify = notify.at[T * K + idx].add(
                jnp.where(at_hold1, 0, take_s1))
            pbl = pbl.at[t * K + idx].add(jnp.where(at_hold1, take_r1, 0))
            pbl = pbl.at[T * K + idx].add(jnp.where(at_hold1, take_s1, 0))
            hs_new1 = jnp.sum(jnp.where(at_hold1, take_s1, 0))
            # branch 2 — evicted retry from hop 0 on the detour route
            seq2 = seq_eff_pair[pair]
            idx2 = jnp.maximum(seq2, 0)
            valid2 = seq2 >= 0
            L2 = len_eff_pair[pair]
            act2 = active & ev & routable_pair[pair]
            avail2 = remaining[t * K + idx2] + remaining[T * K + idx2]
            short2 = valid2 & (avail2 < c)
            h_block = jnp.min(jnp.where(short2, hop_idx, H2))
            complete2 = act2 & (h_block >= L2)
            park2 = (act2 & ~detour_pair[pair] & (h_block < L2)
                     & (h_block >= 1))
            h_stop2 = jnp.where(complete2, L2,
                                jnp.where(park2, h_block, 0))
            trav2 = valid2 & (hop_idx < h_stop2)
            remaining, take_r2, take_s2 = split_spend(remaining, t, idx2,
                                                      trav2, c)
            at_hold2 = park2 & (hop_idx == h_stop2 - 1)
            notify = notify.at[t * K + idx2].add(
                jnp.where(at_hold2, 0, take_r2))
            notify = notify.at[T * K + idx2].add(
                jnp.where(at_hold2, 0, take_s2))
            pbl = pbl.at[t * K + idx2].add(jnp.where(at_hold2, take_r2, 0))
            pbl = pbl.at[T * K + idx2].add(jnp.where(at_hold2, take_s2, 0))
            hs_new2 = jnp.sum(jnp.where(at_hold2, take_s2, 0))
            # release the old hold: on normal advance OR on eviction
            oh = jnp.maximum(seq[jnp.maximum(h - 1, 0)], 0)
            release = moved1 | (active & ev & (h >= 1))
            rel_s = jnp.where(release, hs, 0)
            rel_r = jnp.where(release, c, 0) - rel_s
            notify = notify.at[t * K + oh].add(rel_r)
            notify = notify.at[T * K + oh].add(rel_s)
            pbl = pbl.at[t * K + oh].add(-rel_r)
            pbl = pbl.at[T * K + oh].add(-rel_s)
            complete = complete1 | complete2
            keep = active & ~complete
            h_keep = jnp.where(ev, jnp.where(park2, h_block, 0), h_stop1)
            hs_keep = jnp.where(
                ev, jnp.where(park2, hs_new2, 0),
                jnp.where(moved1, hs_new1, hs))
            out = (complete, jnp.where(complete, 0, c),
                   jnp.where(keep, h_keep, 0),
                   jnp.where(complete, pa0[r], 0),
                   jnp.where(keep, pa0[r] + 1, 0),
                   jnp.sum(trav1.astype(jnp.int32))
                   + jnp.sum(trav2.astype(jnp.int32)),
                   jnp.where(keep, hs_keep, 0),
                   jnp.where(complete2 & detour_pair[pair], c, 0),
                   jnp.where(complete1, L, 0) + jnp.where(complete2, L2, 0))
            return (remaining, notify, pbl), out

        S = (T + 1) * K
        carry = (state.bank.credits, jnp.zeros((S,), jnp.int32),
                 state.parked_by_link)
        carry, (res_c, pc_a, ph_a, age_res, age_a, trav_a, hs_a, rer_a,
                done_a) = lax.scan(resume, carry, rows)

        def offer(carry, r):
            remaining, notify, pbl, blocked = carry
            t = r // (n * n)
            pair = r % (n * n)
            c = flat[r]
            seq = seq_eff_pair[pair]
            idx = jnp.maximum(seq, 0)
            valid = seq >= 0
            L = len_eff_pair[pair]
            rt = routable_pair[pair]
            fl = seq[0]
            routed = (fl >= 0) & (c > 0) & rt
            slot_busy = pc0[r] > 0
            bl_idx = t * K + jnp.maximum(fl, 0)
            hol = blocked[bl_idx]
            avail = remaining[t * K + idx] + remaining[T * K + idx]
            short = valid & (avail < c)
            h_block = jnp.min(jnp.where(short, hop_idx, H2))
            ok = routed & ~slot_busy & ~hol
            admit_c = ok & (h_block >= L)
            admit_p = (ok & ~detour_pair[pair] & (h_block < L)
                       & (h_block >= 1))
            defer = ((fl >= 0) & (c > 0)) & ~admit_c & ~admit_p
            h_stop = jnp.where(admit_c, L, jnp.where(admit_p, h_block, 0))
            trav = valid & (hop_idx < h_stop)
            remaining, take_r, take_s = split_spend(remaining, t, idx,
                                                    trav, c)
            at_hold = admit_p & (hop_idx == h_stop - 1)
            notify = notify.at[t * K + idx].add(
                jnp.where(at_hold, 0, take_r))
            notify = notify.at[T * K + idx].add(
                jnp.where(at_hold, 0, take_s))
            pbl = pbl.at[t * K + idx].add(jnp.where(at_hold, take_r, 0))
            pbl = pbl.at[T * K + idx].add(jnp.where(at_hold, take_s, 0))
            # unroutable rows never reach the egress FIFO: no HOL block
            blocked = blocked.at[bl_idx].set(hol | (defer & rt))
            out = (admit_c, admit_p, jnp.where(defer, 0, -1), h_stop,
                   jnp.sum(trav.astype(jnp.int32)),
                   jnp.sum(jnp.where(at_hold, take_s, 0)),
                   jnp.where(admit_c & detour_pair[pair], c, 0),
                   jnp.where(admit_c, L, 0))
            return (remaining, notify, pbl, blocked), out

        carry = (*carry, jnp.zeros((T * K,), bool))
        (remaining, notify, pbl, _), \
            (adm_c, adm_p, stall, hp_b, trav_b, hs_b, rer_b,
             done_b) = lax.scan(offer, carry, rows)

        def unrot(x, fill, dtype):
            return jnp.full((T * n * n,), fill, dtype).at[rows].set(x)

        fresh_complete = unrot(adm_c, False, bool)
        fresh_park = unrot(adm_p, False, bool)
        park_count = jnp.where(fresh_park, flat, unrot(pc_a, 0, jnp.int32))
        park_hop = jnp.where(fresh_park, unrot(hp_b, 0, jnp.int32),
                             unrot(ph_a, 0, jnp.int32))
        park_age = jnp.where(fresh_park, 1, unrot(age_a, 0, jnp.int32))
        hold_shared = jnp.where(fresh_park, unrot(hs_b, 0, jnp.int32),
                                unrot(hs_a, 0, jnp.int32))
        links_traversed = (unrot(trav_a, 0, jnp.int32)
                           + unrot(trav_b, 0, jnp.int32))
        shape3 = (T, n, n)
        return TenantAdmissionOut(
            fresh_complete=fresh_complete.reshape(shape3),
            fresh_park=fresh_park.reshape(shape3),
            resumed_complete=unrot(res_c, False, bool).reshape(shape3),
            resume_age=unrot(age_res, 0, jnp.int32).reshape(shape3),
            stall_hop=unrot(stall, -1, jnp.int32).reshape(shape3),
            park_count=park_count.reshape(shape3),
            park_hop=park_hop.reshape(shape3),
            park_age=park_age.reshape(shape3),
            hold_shared=hold_shared.reshape(shape3),
            parked_by_link=pbl,
            links_traversed=links_traversed.reshape(shape3),
            spent=state.bank.credits - remaining,
            notify=notify,
            queue_events=queue_events,
            rerouted=(unrot(rer_a, 0, jnp.int32)
                      + unrot(rer_b, 0, jnp.int32)).reshape(shape3),
            links_done=(unrot(done_a, 0, jnp.int32)
                        + unrot(done_b, 0, jnp.int32)).reshape(shape3),
            stalled_by_link=self._stall_attr(
                unrot(stall, -1, jnp.int32), flat),
        )

    # -- tenant bundle packing ---------------------------------------------
    def _pack_tenants(self, row_payload: jax.Array,
                      cnt_in: jax.Array) -> jax.Array:
        """(T, n, W) payload + (T, n) counts -> (n, T*(W+1)) bundles:
        per destination row, T count-packed sub-rows side by side."""
        packed = base.pack_payload(row_payload, cnt_in)    # (T, n, W+1)
        n = packed.shape[1]
        return packed.transpose(1, 0, 2).reshape(n, -1)

    def _unpack_tenants(self, buf: jax.Array):
        """Inverse of :meth:`_pack_tenants` -> ((T, n, W), (T, n))."""
        n = buf.shape[0]
        T = self.n_tenants
        packed = buf.reshape(n, T, -1).transpose(1, 0, 2)
        return base.unpack_payload(packed)

    def _tenant_count_cols(self, width: int) -> tuple[int, ...]:
        return tuple(t * (width + 1) + width for t in range(self.n_tenants))

    def _ship_rotation(self, packed_bundles: jax.Array, me, axis_name: str,
                       acc: dict, count_cols: tuple[int, ...], down=None):
        my_c = self._coords_of(me)
        buf = packed_bundles
        for a in range(self.ndim):
            bundles = self._to_phase(buf, a)
            perm_p, perm_m = self._perm[a]
            recv = self._ring_phase(bundles, axis_name, my_c[a],
                                    self.dims[a], perm_p, perm_m, acc,
                                    phase=a, count_cols=count_cols,
                                    fault=self._phase_fault(down, a, me,
                                                            my_c[a]))
            buf = self._from_phase(recv, a)
        return self._unpack_tenants(buf)

    @staticmethod
    def _fresh_acc(ndim: int) -> dict:
        return {"bytes": jnp.int32(0), "owire": jnp.int32(0), "hops": 0,
                "in_flight": jnp.int32(0),
                "in_flight_phase": [jnp.int32(0)] * ndim}

    def _by_hop(self, hop: jax.Array, weight: jax.Array) -> jax.Array:
        """Sum (T, n) weights into (T, max_hops) hop histograms."""
        return hop_histogram(hop, weight, self.max_hops)

    def _fabric_level(self, acc: dict):
        """Fabric-wide (non-decomposable) stats attributed to tenant 0 so
        tenant-axis sums stay physical."""
        T = self.n_tenants
        z = jnp.zeros((T,), jnp.int32)
        return (z.at[0].set(acc["hops"]),
                z.at[0].set(acc["bytes"].astype(jnp.int32)),
                z.at[0].set(acc["in_flight"].astype(jnp.int32)),
                jnp.zeros((T, self.ndim), jnp.int32).at[0].set(
                    jnp.stack(acc["in_flight_phase"])))

    # -- the full multi-tenant window --------------------------------------
    def exchange(self, state: base.LinkState, payload: jax.Array,
                 counts: jax.Array, *, axis_name: str,
                 enforce_credits: bool = True) -> base.TransportOut:
        """Ship one window for every tenant: ``payload (T, n, W)``,
        ``counts (T, n)``; every output field has a leading tenant axis."""
        T, n, H = self.n_tenants, self.n_shards, self.max_hops
        me = lax.axis_index(axis_name)
        counts = counts.astype(jnp.int32)
        if payload.shape[:2] != (T, n) or counts.shape != (T, n):
            raise ValueError(
                f"tenant transport wants payload (T={T}, n={n}, W) and "
                f"counts (T, n); got {payload.shape} / {counts.shape}")
        is_local = (jnp.arange(n) == me)[None, :]
        zero_q = jnp.zeros((T, n, n), jnp.float32)
        down = state.link_down
        if down is not None and not enforce_credits:
            raise ValueError("fault injection (FabricState.link_down) "
                             "requires credit flow control; "
                             "enforce_credits=False cannot reroute")

        if enforce_credits:
            if state.parked_payload.shape != payload.shape:
                raise ValueError(
                    f"FabricState payload buffer "
                    f"{state.parked_payload.shape} != offered payload "
                    f"{payload.shape}: initialize with "
                    f"init_state(payload_width=W)")
            counts_all = self._allgather_counts_mt(counts, me, axis_name)
            adm = (self._admit_tenants_faulted(state, counts_all, down)
                   if down is not None
                   else self._admit_tenants(state, counts_all))
            fresh_c = adm.fresh_complete[:, me]          # (T, n)
            fresh_p = adm.fresh_park[:, me]
            resumed = adm.resumed_complete[:, me]
            stall_hop = adm.stall_hop[:, me]
            pc0_me = state.parked_count[:, me]
            ship_fresh = fresh_c | (is_local & (counts > 0))
            cnt_in = (jnp.where(ship_fresh, counts, 0)
                      + jnp.where(resumed, pc0_me, 0))
            row_payload = jnp.where(
                resumed[..., None], state.parked_payload,
                jnp.where(ship_fresh[..., None], payload, jnp.uint32(0)))
            bank = fc.credit_tick(state.bank, adm.spent, notify=adm.notify)
            state = base.FabricState(
                bank=bank,
                parked_count=adm.park_count,
                parked_hop=adm.park_hop,
                parked_age=adm.park_age,
                parked_by_link=adm.parked_by_link,
                parked_payload=jnp.where(fresh_p[..., None], payload,
                                         state.parked_payload),
                parked_hold_shared=adm.hold_shared,
            )
            sent_mask = fresh_c | fresh_p | is_local | (counts == 0)
            sent_now = fresh_c | is_local | (counts == 0)
            queue_us = wire_latency.queueing_latency_us(
                self.wire_fmt, adm.queue_events)
            park_wait_us = wire_latency.queueing_latency_us(
                self.wire_fmt, adm.resume_age * self.link_credits)
        else:
            fresh_p = resumed = jnp.zeros((T, n), bool)
            pc0_me = jnp.zeros((T, n), jnp.int32)
            stall_hop = jnp.full((T, n), -1, jnp.int32)
            cnt_in = counts
            row_payload = payload
            state = state._replace(
                bank=fc.credit_tick(state.bank,
                                    jnp.zeros_like(state.bank.credits)),
                link_down=None)
            sent_mask = sent_now = jnp.ones((T, n), bool)
            queue_us = park_wait_us = zero_q

        acc = self._fresh_acc(self.ndim)
        w = payload.shape[-1]
        recv_payload, recv_counts = self._ship_rotation(
            self._pack_tenants(row_payload, cnt_in), me, axis_name, acc,
            self._tenant_count_cols(w), down=down)

        stalled_by_hop = self._by_hop(
            stall_hop, jnp.where(stall_hop >= 0, counts, 0))
        offered = jnp.sum(counts, axis=-1)
        if enforce_credits:
            sent = jnp.sum(jnp.where(sent_now, counts, 0), axis=-1)
            parked = jnp.sum(jnp.where(fresh_p, counts, 0), axis=-1)
            unparked = jnp.sum(jnp.where(resumed, pc0_me, 0), axis=-1)
            pk_cnt, pk_hop = state.parked_count[:, me], state.parked_hop[:, me]
            parked_by_hop = self._by_hop(pk_hop, pk_cnt)
            c_row = jnp.where(resumed, pc0_me, counts)
            owire = jnp.sum(
                wire_framing.frame_bytes(self.wire_fmt, c_row)
                * adm.links_traversed[:, me], axis=-1).astype(jnp.int32)
            dwell = jnp.sum(jnp.where(
                fresh_c | resumed,
                queue_us[:, me] + park_wait_us[:, me], 0.0),
                axis=-1).astype(jnp.float32)
            in_fabric = jnp.sum(pk_cnt, axis=-1).astype(jnp.int32)
            rerouted = jnp.sum(adm.rerouted[:, me], axis=-1).astype(
                jnp.int32)
        else:
            sent = jnp.sum(cnt_in, axis=-1)
            parked = unparked = jnp.zeros((T,), jnp.int32)
            parked_by_hop = jnp.zeros((T, H), jnp.int32)
            owire = jnp.zeros((T,), jnp.int32).at[0].set(
                acc["owire"].astype(jnp.int32))
            dwell = jnp.zeros((T,), jnp.float32)
            in_fabric = (jnp.sum(state.parked_count[:, me], axis=-1)
                         .astype(jnp.int32) if state.parked_count.size
                         else jnp.zeros((T,), jnp.int32))
            rerouted = jnp.zeros((T,), jnp.int32)
        hops_f, bytes_f, inflight_f, inflight_ph = self._fabric_level(acc)
        stats = base.LinkStats(
            offered_events=offered.astype(jnp.int32),
            sent_events=sent.astype(jnp.int32),
            deferred_events=(offered - sent - parked).astype(jnp.int32),
            delivered_events=jnp.sum(recv_counts, axis=-1).astype(jnp.int32),
            credit_stalls=jnp.sum(stall_hop >= 0, axis=-1).astype(jnp.int32),
            hops=hops_f,
            forwarded_bytes=bytes_f,
            bytes_on_wire=owire,
            max_in_flight=inflight_f,
            stalled_by_hop=stalled_by_hop,
            max_in_flight_by_phase=inflight_ph,
            parked_events=parked.astype(jnp.int32),
            unparked_events=unparked.astype(jnp.int32),
            in_fabric_events=in_fabric,
            parked_by_hop=parked_by_hop,
            queue_dwell_us=dwell,
            rerouted=rerouted,
            stalled_by_link=(adm.stalled_by_link if enforce_credits
                             else None),
        )
        return base.TransportOut(
            state=state,
            recv_payload=recv_payload,
            recv_counts=recv_counts,
            sent_mask=sent_mask,
            stats=stats,
            sent_now=sent_now,
            queue_us=queue_us,
            unparked_now=jnp.where(resumed, pc0_me, 0),
            park_wait_us=park_wait_us,
            links_used=adm.links_done if down is not None else None,
        )

    # -- end-of-run fabric walk --------------------------------------------
    def drain_fabric(self, state: base.LinkState, *, axis_name: str,
                     payload_width: int | None = None) -> base.TransportOut:
        """Tenant-axis fabric walk: every parked row of every tenant
        resumes from its blocked hop and completes, all held credits
        (reserved AND shared) release into their slots' delay lines —
        per-slot conservation ``credits + pending == slot_limit`` is
        restored and the returned tables are empty."""
        T, n, H = self.n_tenants, self.n_shards, self.max_hops
        me = lax.axis_index(axis_name)
        pc_me = state.parked_count[:, me]                 # (T, n)
        ph_me = state.parked_hop[:, me]
        row_payload = jnp.where((pc_me > 0)[..., None],
                                state.parked_payload, jnp.uint32(0))

        acc = self._fresh_acc(self.ndim)
        w = state.parked_payload.shape[-1]
        recv_payload, recv_counts = self._ship_rotation(
            self._pack_tenants(row_payload, pc_me), me, axis_name, acc,
            self._tenant_count_cols(w))

        bank = fc.credit_tick(state.bank,
                              jnp.zeros_like(state.bank.credits),
                              notify=state.parked_by_link)
        new_state = base.FabricState(
            bank=bank,
            parked_count=jnp.zeros_like(state.parked_count),
            parked_hop=jnp.zeros_like(state.parked_hop),
            parked_age=jnp.zeros_like(state.parked_age),
            parked_by_link=jnp.zeros_like(state.parked_by_link),
            parked_payload=jnp.zeros_like(state.parked_payload),
            parked_hold_shared=jnp.zeros_like(state.parked_hold_shared),
        )
        remaining_links = jnp.maximum(
            self._hops_matrix[me][None, :] - ph_me, 0)
        owire = jnp.sum(
            wire_framing.frame_bytes(self.wire_fmt, pc_me)
            * jnp.where(pc_me > 0, remaining_links, 0),
            axis=-1).astype(jnp.int32)
        hops_f, bytes_f, inflight_f, inflight_ph = self._fabric_level(acc)
        z = jnp.zeros((T,), jnp.int32)
        stats = base.LinkStats(
            offered_events=z, sent_events=z, deferred_events=z,
            delivered_events=jnp.sum(recv_counts, axis=-1).astype(jnp.int32),
            credit_stalls=z,
            hops=hops_f, forwarded_bytes=bytes_f, bytes_on_wire=owire,
            max_in_flight=inflight_f,
            stalled_by_hop=jnp.zeros((T, H), jnp.int32),
            max_in_flight_by_phase=inflight_ph,
            parked_events=z,
            unparked_events=jnp.sum(pc_me, axis=-1).astype(jnp.int32),
            in_fabric_events=z,
            parked_by_hop=jnp.zeros((T, H), jnp.int32),
            queue_dwell_us=jnp.zeros((T,), jnp.float32),
            rerouted=z,
        )
        return base.TransportOut(
            state=new_state,
            recv_payload=recv_payload,
            recv_counts=recv_counts,
            sent_mask=jnp.ones((T, n), bool),
            stats=stats,
            sent_now=jnp.ones((T, n), bool),
            queue_us=jnp.zeros((T, n, n), jnp.float32),
            unparked_now=pc_me,
            park_wait_us=jnp.zeros((T, n, n), jnp.float32),
        )
