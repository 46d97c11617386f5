"""Device-side flight recorder: a static-shape per-window telemetry ring.

The fabric's end-of-run ``LinkStats`` aggregates answer *how much*
congestion a run saw; the scale-up and adaptive-routing work need to know
*when* and *where* it formed.  The flight recorder answers that without
leaving the device: a fixed-depth ring (:class:`TelemetryRing`) rides the
simulator / serving-engine ``lax.scan`` carry and, each flush window,
snapshots

* the absolute window index,
* the per-window deltas of the conservation-law counters
  (:data:`COUNTER_FIELDS` — offered/sent/deferred/delivered, credit
  stalls, park/unpark/in-fabric occupancy, reroutes),
* per-link credit occupancy (``FabricState.bank.credits``) and the
  ``parked_by_link`` hold table — the two sides of the per-link identity
  ``credits + pending + parked_by_link == limit``,
* per-link deferred-demand attribution (``LinkStats.stalled_by_link``,
  populated when the transport is built with ``stall_attribution=True``;
  an all-zero lane otherwise so the ring layout never varies),
* the latency-histogram delta of the window
  (``repro.wire.latency.N_LATENCY_BINS`` log-2 bins).

Everything is written with one dynamic-slot ``.at[slot].set`` per lane —
O(depth) memory, O(1) per window, shape-static, so the ring scans and
``shard_map``s like any other carry leaf.  Depth is configurable
(:class:`RecorderConfig`); a run longer than ``depth`` windows keeps the
most recent ``depth`` (true flight-recorder semantics — ``ring_rows``
reorders oldest→newest on the host and reports how many windows were
overwritten).

The recorder is **off by default**.  When disabled, nothing here is
imported into the scan body and the carry pytree / lowered HLO are
bit-identical to an uninstrumented build (pinned by
``tests/test_obs.py``).
"""
from __future__ import annotations

from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

#: LinkStats fields recorded per window — the subset that is uniformly
#: shaped across backends: scalar () in single-tenant stats, (T,) with a
#: leading tenant axis in the multi-tenant transport's stats.  (``hops``
#: and the byte counters are fabric-level in tenant stats, so they are
#: deliberately excluded; the metrics registry still exports their
#: run-level totals.)
COUNTER_FIELDS = (
    "offered_events",
    "sent_events",
    "deferred_events",
    "delivered_events",
    "credit_stalls",
    "parked_events",
    "unparked_events",
    "in_fabric_events",
    "rerouted",
)


class RecorderConfig(NamedTuple):
    """Flight-recorder knobs.  ``depth`` is the ring's window capacity —
    a run longer than ``depth`` windows keeps the most recent ``depth``."""

    depth: int = 64


class TelemetryRing(NamedTuple):
    """The carried ring.  ``cursor`` counts total records ever written;
    the write slot is ``cursor % depth``, so wrap-around is implicit and
    the host side can tell a partially-filled ring (``cursor < depth``)
    from a wrapped one.  ``window`` is initialized to -1: a slot still
    holding -1 was never written.

    Lane shapes (depth D, K directed links, C the counter shape — () or
    (T,) — and H the latency-histogram bins):

    * ``cursor``          ()          i32
    * ``window``          (D,)        i32  absolute flush-window index
    * ``counters``        (D, 9, *C)  i32  per-window COUNTER_FIELDS deltas
    * ``credits``         (D, *K')    i32  end-of-window credit occupancy
                                        (K' = partition slots when
                                        multi-tenant: ``(T+1)*K``)
    * ``parked_by_link``  (D, *K')    i32  end-of-window credit holds
    * ``stalled_by_link`` (D, K)      i32  deferred demand per physical
                                        egress link (zeros unless the
                                        transport attributes stalls)
    * ``hist``            (D, *H)     i32  latency-histogram delta
    """

    cursor: jax.Array
    window: jax.Array
    counters: jax.Array
    credits: jax.Array
    parked_by_link: jax.Array
    stalled_by_link: jax.Array
    hist: jax.Array

    @property
    def depth(self) -> int:
        return self.window.shape[-1]


def ring_init(depth: int, state, counter_shape: Sequence[int],
              hist_shape: Sequence[int], n_links: int) -> TelemetryRing:
    """Empty ring sized from a concrete ``FabricState``.

    ``counter_shape`` is the shape of one COUNTER_FIELDS entry (``()``
    single-tenant, ``(T,)`` multi-tenant), ``hist_shape`` the latency
    digest's histogram shape, ``n_links`` the PHYSICAL directed-link
    count K (the stall-attribution lane is always physical even when the
    credit lanes carry partition slots).
    """
    depth = int(depth)
    if depth < 1:
        raise ValueError(f"ring depth must be >= 1, got {depth}")
    kp = state.bank.credits.shape  # (K,) or ((T+1)*K,)
    return TelemetryRing(
        cursor=jnp.zeros((), jnp.int32),
        window=jnp.full((depth,), -1, jnp.int32),
        counters=jnp.zeros((depth, len(COUNTER_FIELDS), *counter_shape),
                           jnp.int32),
        credits=jnp.zeros((depth, *kp), jnp.int32),
        parked_by_link=jnp.zeros((depth, *kp), jnp.int32),
        stalled_by_link=jnp.zeros((depth, int(n_links)), jnp.int32),
        hist=jnp.zeros((depth, *hist_shape), jnp.int32),
    )


def record(ring: TelemetryRing, win, link_stats, state,
           hist) -> TelemetryRing:
    """Write one window's record at ``cursor % depth`` (jit/scan-safe).

    ``link_stats`` is the window's ``LinkStats`` delta, ``state`` the
    END-of-window ``FabricState`` (occupancy snapshot), ``hist`` the
    window's latency-histogram delta.
    """
    depth = ring.depth
    slot = jax.lax.rem(ring.cursor, jnp.int32(depth))
    counters = jnp.stack(
        [jnp.asarray(getattr(link_stats, f)).astype(jnp.int32)
         for f in COUNTER_FIELDS])
    sbl = getattr(link_stats, "stalled_by_link", None)
    if sbl is None:
        sbl = jnp.zeros(ring.stalled_by_link.shape[-1:], jnp.int32)
    return TelemetryRing(
        cursor=ring.cursor + 1,
        window=ring.window.at[slot].set(jnp.asarray(win, jnp.int32)),
        counters=ring.counters.at[slot].set(counters),
        credits=ring.credits.at[slot].set(
            state.bank.credits.astype(jnp.int32)),
        parked_by_link=ring.parked_by_link.at[slot].set(
            state.parked_by_link.astype(jnp.int32)),
        stalled_by_link=ring.stalled_by_link.at[slot].set(
            sbl.astype(jnp.int32)),
        hist=ring.hist.at[slot].set(jnp.asarray(hist).astype(jnp.int32)),
    )


def ring_shard(ring: TelemetryRing, s: int = 0) -> TelemetryRing:
    """Strip the leading shard axis ``shard_map``-returned rings carry.

    The descriptor lanes (credits, parked_by_link, stalled_by_link) are
    replicated global state, so any shard's view is THE view; the counter
    lanes are per-shard and callers wanting global totals sum them across
    shards before (or instead of) picking one.  The pick happens on the
    host: a Python-int index into a ``wafer``-sharded device array is
    refused under explicitly typed mesh axes.
    """
    return jax.tree_util.tree_map(lambda a: np.asarray(a)[s], ring)


def ring_rows(ring: TelemetryRing) -> list[dict]:
    """Host-side decode: ordered oldest→newest, wrap-aware.

    Returns one JSON-serializable dict per recorded window::

        {"window": int, "counters": {field: int | [int, ...]},
         "credits": [...], "parked_by_link": [...],
         "stalled_by_link": [...], "hist": [...], "overwritten": int}

    ``overwritten`` (same on every row) is how many older windows the
    ring dropped; 0 means the full run is present.
    """
    cursor = int(np.asarray(ring.cursor))
    depth = ring.depth
    n = min(cursor, depth)
    overwritten = cursor - n
    window = np.asarray(ring.window)
    counters = np.asarray(ring.counters)
    credits = np.asarray(ring.credits)
    pbl = np.asarray(ring.parked_by_link)
    sbl = np.asarray(ring.stalled_by_link)
    hist = np.asarray(ring.hist)
    if cursor <= depth:
        order = list(range(n))
    else:
        start = cursor % depth
        order = [(start + i) % depth for i in range(depth)]
    rows = []
    for slot in order:
        rows.append({
            "window": int(window[slot]),
            "counters": {
                f: (int(counters[slot, i]) if counters.ndim == 2
                    else counters[slot, i].astype(int).tolist())
                for i, f in enumerate(COUNTER_FIELDS)},
            "credits": credits[slot].astype(int).tolist(),
            "parked_by_link": pbl[slot].astype(int).tolist(),
            "stalled_by_link": sbl[slot].astype(int).tolist(),
            "hist": hist[slot].astype(int).tolist(),
            "overwritten": overwritten,
        })
    return rows


def global_rows(ring: TelemetryRing, n_shards: int) -> list[dict]:
    """Decode a ``shard_map``-returned ring (leading shard axis) into
    GLOBAL per-window rows: the per-shard counter and latency-histogram
    lanes are summed across shards; the replicated descriptor lanes
    (credits / parked_by_link / stalled_by_link) come from shard 0.
    This is what the run directory's ``recorder.jsonl`` stores."""
    per = [ring_rows(ring_shard(ring, s)) for s in range(int(n_shards))]
    rows = per[0]
    for other in per[1:]:
        for r, o in zip(rows, other):
            for f in COUNTER_FIELDS:
                r["counters"][f] = (
                    np.asarray(r["counters"][f], np.int64)
                    + np.asarray(o["counters"][f], np.int64)).tolist()
            r["hist"] = (np.asarray(r["hist"], np.int64)
                         + np.asarray(o["hist"], np.int64)).tolist()
    return rows


def counter_totals(rows: list[dict]) -> dict[str, np.ndarray]:
    """Sum each COUNTER_FIELDS lane over a row list — the quantity the
    conservation tests compare bit-exactly against the end-of-run
    ``LinkStats`` totals (valid when ``overwritten == 0``)."""
    if rows and rows[0]["overwritten"]:
        raise ValueError("ring wrapped: totals would undercount "
                         f"({rows[0]['overwritten']} windows dropped)")
    out: dict[str, np.ndarray] = {}
    for f in COUNTER_FIELDS:
        vals = [np.asarray(r["counters"][f], np.int64) for r in rows]
        out[f] = (np.sum(vals, axis=0) if vals
                  else np.zeros((), np.int64))
    return out
