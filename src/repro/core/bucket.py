"""Cycle-level functional model of the event-aggregation buckets (paper §3.1).

This is the "simulation model of the event aggregation buckets" the paper
names as its next step.  It models, per FPGA:

* a **map table** binding network destinations to physical buckets,
* a **free-bucket list** (functionally: lowest-index free bucket),
* **bucket renaming**: when an event addresses a destination with no bound
  bucket and no bucket is free, the *most urgent* bucket is flushed and its
  binding is stolen (paper: "If no bucket is free the next appropriate one
  is flushed"),
* **deadline flushing**: a bucket is flushed when its most urgent timestamp
  deadline minus the configured margin is reached, or when it is full,
  or on external trigger,
* **concurrent flushing and aggregation** via the two-counter scheme: at
  flush-trigger time the accumulation side is handed to the drain engine and
  the bucket immediately continues accumulating from zero (the functional
  equivalent of swapping the increment/decrement counters),
* a serial **output port** that drains one packet at a time at the link
  datapath rate (16 B/cycle), which is what makes header overhead visible:
  un-aggregated single events drain at 1/2 event per cycle while input
  arrives at up to `events_per_cycle` per cycle.

Everything is pure-functional and `lax.scan`-able so the same model runs
under jit for long traffic traces, and serves as the oracle for the
vectorized aggregator and the Pallas kernel.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import events as ev

# numpy scalars: see events.INVALID_EVENT
NO_BUCKET = np.int32(-1)
NO_DEST = np.int32(-1)
_BIG = np.int32(1 << 20)


class BucketConfig(NamedTuple):
    n_buckets: int = 8
    capacity: int = ev.PACKET_MAX_EVENTS       # 124 events / 496 B
    n_dest: int = 64                            # destinations this shard talks to
    flush_margin: int = 64                      # systemtime units of slack kept
    queue: int = 4                              # flush requests the port can hold


class BucketState(NamedTuple):
    """All per-FPGA aggregation state. Shapes: B=n_buckets, C=capacity."""

    map_table: jax.Array      # (n_dest,) i32: dest -> bucket | NO_BUCKET
    bucket_dest: jax.Array    # (B,) i32: bucket -> dest | NO_DEST (free)
    fill: jax.Array           # (B,) i32 accumulation-side counter
    deadline: jax.Array       # (B,) i32 most urgent ts (ring); _BIG if empty
    storage: jax.Array        # (B, C) u32 packed events
    # drain engine: a small queue of triggered packets + port busy counter
    q_dest: jax.Array         # (Q,) i32
    q_count: jax.Array        # (Q,) i32
    q_events: jax.Array       # (Q, C) u32
    q_len: jax.Array          # () i32
    port_busy: jax.Array      # () i32 cycles until port free
    now: jax.Array            # () i32 systemtime


class CycleOut(NamedTuple):
    """Per-cycle observable outputs (for stats / verification)."""

    sent_dest: jax.Array      # () i32 dest of packet leaving the port (-1)
    sent_count: jax.Array     # () i32 events in that packet
    sent_events: jax.Array    # (C,) u32 its payload
    stalled: jax.Array        # () i32 input events refused this cycle
    deadline_miss: jax.Array  # () i32 events whose deadline passed pre-send


def init_state(cfg: BucketConfig) -> BucketState:
    B, C, Q = cfg.n_buckets, cfg.capacity, cfg.queue
    return BucketState(
        map_table=jnp.full((cfg.n_dest,), NO_BUCKET),
        bucket_dest=jnp.full((B,), NO_DEST),
        fill=jnp.zeros((B,), jnp.int32),
        deadline=jnp.full((B,), _BIG),
        storage=jnp.zeros((B, C), jnp.uint32),
        q_dest=jnp.full((Q,), NO_DEST),
        q_count=jnp.zeros((Q,), jnp.int32),
        q_events=jnp.zeros((Q, C), jnp.uint32),
        q_len=jnp.int32(0),
        port_busy=jnp.int32(0),
        now=jnp.int32(0),
    )


def _urgency(state: BucketState, cfg: BucketConfig) -> jax.Array:
    """Slack (in systemtime units) per bucket; empty buckets -> +BIG."""
    slack = ev.ts_slack(state.deadline & ev.TS_MASK, state.now & ev.TS_MASK)
    return jnp.where(state.fill > 0, slack, _BIG)


def _trigger_flush(state: BucketState, b: jax.Array, cfg: BucketConfig):
    """Hand bucket b's accumulation side to the drain queue ('counter swap').

    The bucket keeps its destination binding but restarts from fill=0, so
    aggregation continues concurrently with the drain — the observable
    behaviour of the paper's two-counter swap.  Returns (state, ok): ok is
    False when the drain queue is full (flush request must retry; the
    caller treats this as back-pressure).
    """
    q_free = state.q_len < state.q_dest.shape[0]
    do = q_free & (state.fill[b] > 0)

    slot = state.q_len
    q_dest = jnp.where(do, state.q_dest.at[slot].set(state.bucket_dest[b]), state.q_dest)
    q_count = jnp.where(do, state.q_count.at[slot].set(state.fill[b]), state.q_count)
    q_events = jnp.where(do, state.q_events.at[slot].set(state.storage[b]), state.q_events)
    q_len = jnp.where(do, state.q_len + 1, state.q_len)

    fill = jnp.where(do, state.fill.at[b].set(0), state.fill)
    deadline = jnp.where(do, state.deadline.at[b].set(_BIG), state.deadline)
    return state._replace(
        q_dest=q_dest, q_count=q_count, q_events=q_events, q_len=q_len,
        fill=fill, deadline=deadline,
    ), do | ~(state.fill[b] > 0)


def _unbind(state: BucketState, b: jax.Array) -> BucketState:
    """Release bucket b back to the free list."""
    old_dest = state.bucket_dest[b]
    map_table = jnp.where(
        old_dest >= 0,
        state.map_table.at[jnp.maximum(old_dest, 0)].set(NO_BUCKET),
        state.map_table,
    )
    return state._replace(
        map_table=map_table, bucket_dest=state.bucket_dest.at[b].set(NO_DEST)
    )


def _accept_event(state: BucketState, word: jax.Array, dest: jax.Array,
                  cfg: BucketConfig):
    """Route one event through map-table lookup / renaming / append.

    Returns (state, stalled:int32, full_flush_needed bucket id or -1).
    """
    valid = ev.is_valid(word) & (dest >= 0)
    dest_c = jnp.clip(dest, 0, cfg.n_dest - 1)
    b = state.map_table[dest_c]
    bound = valid & (b != NO_BUCKET)

    # --- renaming path: need a bucket for a new destination -------------
    free_mask = state.bucket_dest == NO_DEST
    any_free = jnp.any(free_mask)
    free_b = jnp.argmax(free_mask).astype(jnp.int32)          # lowest free

    # no free bucket: flush the most urgent bound one and steal it
    need_steal = valid & ~bound & ~any_free
    victim = jnp.argmin(_urgency(state, cfg)).astype(jnp.int32)
    state2, ok = _trigger_flush(state, victim, cfg)
    # steal only if the flush was accepted by the queue
    can_steal = need_steal & ok
    state2 = jax.lax.cond(can_steal, lambda s: _unbind(s, victim), lambda s: s, state2)
    state = jax.tree_util.tree_map(
        lambda a, c: jnp.where(need_steal, c, a), state, state2
    )
    free_after = jnp.where(can_steal, victim, free_b)
    have_bucket = bound | (valid & ~bound & (any_free | can_steal))
    tgt = jnp.where(bound, b, free_after)
    stalled = (valid & ~have_bucket).astype(jnp.int32)

    # --- bind if new ------------------------------------------------------
    newly = valid & ~bound & have_bucket
    map_table = jnp.where(
        newly, state.map_table.at[dest_c].set(tgt), state.map_table
    )
    bucket_dest = jnp.where(
        newly, state.bucket_dest.at[tgt].set(dest_c), state.bucket_dest
    )

    # --- append ----------------------------------------------------------
    tgt_c = jnp.clip(tgt, 0, cfg.n_buckets - 1)
    pos = jnp.clip(state.fill[tgt_c], 0, cfg.capacity - 1)
    do_app = have_bucket
    storage = jnp.where(
        do_app, state.storage.at[tgt_c, pos].set(word), state.storage
    )
    new_fill = state.fill[tgt_c] + 1
    fill = jnp.where(do_app, state.fill.at[tgt_c].set(new_fill), state.fill)
    ts = ev.timestamp(word).astype(jnp.int32)
    cur = state.deadline[tgt_c]
    more_urgent = (cur == _BIG) | ev.ts_before(ts, cur & ev.TS_MASK)
    deadline = jnp.where(
        do_app & more_urgent, state.deadline.at[tgt_c].set(ts), state.deadline
    )
    state = state._replace(
        map_table=map_table, bucket_dest=bucket_dest,
        storage=storage, fill=fill, deadline=deadline,
    )
    full_b = jnp.where(do_app & (new_fill >= cfg.capacity), tgt_c, NO_BUCKET)
    return state, stalled, full_b


def cycle(state: BucketState, words: jax.Array, dests: jax.Array,
          cfg: BucketConfig, force_flush: jax.Array | None = None):
    """Advance the model by one FPGA clock.

    words/dests: (E,) packed events + routed destinations arriving this
    cycle (invalid-flagged slots are ignored).  force_flush: optional ()
    bool external flush trigger (flushes the most urgent bucket).
    Returns (state, CycleOut).
    """
    stalled = jnp.int32(0)
    # 1. accept this cycle's arrivals (pipeline order, E is small+static)
    pending_full = jnp.full((words.shape[0],), NO_BUCKET)
    for i in range(words.shape[0]):
        state, s, fb = _accept_event(state, words[i], dests[i], cfg)
        stalled = stalled + s
        pending_full = pending_full.at[i].set(fb)

    # 2. flush triggers: full buckets first, then deadline, then external
    for i in range(pending_full.shape[0]):
        fb = pending_full[i]
        state = jax.lax.cond(
            fb >= 0,
            lambda s: _trigger_flush(s, jnp.maximum(fb, 0), cfg)[0],
            lambda s: s,
            state,
        )

    urg = _urgency(state, cfg)
    most_urgent = jnp.argmin(urg).astype(jnp.int32)
    deadline_due = urg[most_urgent] <= cfg.flush_margin
    ext = jnp.bool_(False) if force_flush is None else force_flush
    state = jax.lax.cond(
        deadline_due | ext,
        lambda s: _trigger_flush(s, most_urgent, cfg)[0],
        lambda s: s,
        state,
    )

    # 3. port: start next packet if idle, shift one datapath word per cycle
    def start(s: BucketState):
        n = s.q_count[0]
        out = CycleOut(
            sent_dest=s.q_dest[0], sent_count=n, sent_events=s.q_events[0],
            stalled=jnp.int32(0), deadline_miss=jnp.int32(0),
        )
        busy = ev.wire_cycles(n).astype(jnp.int32)
        s = s._replace(
            q_dest=jnp.roll(s.q_dest, -1, 0).at[-1].set(NO_DEST),
            q_count=jnp.roll(s.q_count, -1, 0).at[-1].set(0),
            q_events=jnp.roll(s.q_events, -1, 0).at[-1].set(0),
            q_len=s.q_len - 1,
            port_busy=busy,
        )
        return s, out

    def idle(s: BucketState):
        out = CycleOut(
            sent_dest=NO_DEST, sent_count=jnp.int32(0),
            sent_events=jnp.zeros((cfg.capacity,), jnp.uint32),
            stalled=jnp.int32(0), deadline_miss=jnp.int32(0),
        )
        return s, out

    can_start = (state.port_busy <= 0) & (state.q_len > 0)
    state, out = jax.lax.cond(can_start, start, idle, state)

    # deadline misses: events leaving the port later than their deadline
    miss = jnp.sum(
        jnp.where(
            (jnp.arange(cfg.capacity) < out.sent_count)
            & (ev.ts_slack(ev.timestamp(out.sent_events),
                           state.now & ev.TS_MASK) < 0),
            1, 0,
        )
    ).astype(jnp.int32)

    state = state._replace(
        port_busy=jnp.maximum(state.port_busy - 1, 0), now=state.now + 1
    )
    return state, out._replace(stalled=stalled, deadline_miss=miss)


def run_trace(cfg: BucketConfig, words: jax.Array, dests: jax.Array):
    """Scan the model over a (T, E) trace. Returns (final_state, CycleOut/T)."""
    state = init_state(cfg)

    def step(s, xs):
        w, d = xs
        return cycle(s, w, d, cfg)

    return jax.lax.scan(step, state, (words, dests))
