"""Lookup-table routing: source event -> (network destination, GUID) and
GUID -> multicast mask (paper §3).

An event arriving at an FPGA carries only its local pulse address; it does
not define a destination in the overall network.  The *source* table is
indexed by pulse address and yields the 16-bit Extoll destination node plus
a Global Unique Identifier (GUID).  The GUID travels with the event.  At the
destination, a second table is indexed by GUID and yields a multicast mask
that selects which of the local HICANN links the event is replayed on.

Both tables are plain device arrays so lookups are ``jnp.take`` (gather) and
the whole path stays inside jit.  Builders construct the tables from a
population-level connectivity description.
"""
from __future__ import annotations

import dataclasses
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import events as ev

DEST_BITS = 16          # Extoll: 16-bit destination address in the header
MAX_DESTS = 1 << DEST_BITS
NO_ROUTE = np.int32(-1)          # numpy: see events.INVALID_EVENT


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class RoutingTables:
    """Device-resident routing state for one FPGA/shard.

    Attributes:
      dest_of_addr:  (n_addr,) int32 — network destination per source pulse
                     address, ``NO_ROUTE`` for unconnected sources.
      guid_of_addr:  (n_addr,) int32 — GUID transmitted with the event.
      mcast_of_guid: (n_guid,) uint32 — destination-side multicast mask,
                     bit i = replay on local HICANN link i (8 links/FPGA,
                     up to 32 modelled populations per shard here).
    """

    dest_of_addr: jax.Array
    guid_of_addr: jax.Array
    mcast_of_guid: jax.Array

    # -- pytree plumbing ------------------------------------------------
    def tree_flatten(self):
        return (self.dest_of_addr, self.guid_of_addr, self.mcast_of_guid), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    # -- lookups ---------------------------------------------------------
    def route(self, event_words: jax.Array):
        """Source-side lookup for a window of packed events.

        Returns (dest, guid, valid): invalid or unrouted events get
        dest == NO_ROUTE and valid False.
        """
        addr, _, valid = ev.unpack(event_words)
        idx = jnp.minimum(addr.astype(jnp.int32), self.dest_of_addr.shape[0] - 1)
        dest = jnp.take(self.dest_of_addr, idx, axis=0)
        guid = jnp.take(self.guid_of_addr, idx, axis=0)
        routed = valid & (dest != NO_ROUTE)
        return jnp.where(routed, dest, NO_ROUTE), guid, routed

    def multicast(self, guids: jax.Array) -> jax.Array:
        """Destination-side lookup: GUID -> multicast mask (uint32)."""
        idx = jnp.clip(guids, 0, self.mcast_of_guid.shape[0] - 1)
        mask = jnp.take(self.mcast_of_guid, idx, axis=0)
        return jnp.where(guids >= 0, mask, jnp.uint32(0))


@dataclasses.dataclass(frozen=True)
class Projection:
    """Population-level connection used to build routing tables.

    src_addr_lo/hi: half-open range of source pulse addresses on this shard.
    dest_node:      16-bit network destination (torus node id).
    dest_links:     which HICANN links at the destination replay the event.
    """

    src_addr_lo: int
    src_addr_hi: int
    dest_node: int
    dest_links: Sequence[int]


def build_tables(
    n_addr: int,
    projections: Sequence[Projection],
    *,
    n_guid: int | None = None,
) -> RoutingTables:
    """Build per-shard tables from projections (host-side, numpy).

    Each distinct (dest_node, dest_links) pair gets one GUID; sources in a
    projection share that GUID.  Later projections overwrite earlier ones on
    address overlap (same as reprogramming the FPGA LUT).
    """
    dest = np.full((n_addr,), -1, np.int32)
    guid = np.zeros((n_addr,), np.int32)
    guid_map: dict[tuple[int, tuple[int, ...]], int] = {}
    masks: list[int] = []
    for p in projections:
        links = tuple(sorted(set(p.dest_links)))
        key = (p.dest_node, links)
        if key not in guid_map:
            guid_map[key] = len(masks)
            masks.append(sum(1 << l for l in links))
        g = guid_map[key]
        dest[p.src_addr_lo : p.src_addr_hi] = p.dest_node
        guid[p.src_addr_lo : p.src_addr_hi] = g
    n_guid = n_guid or max(len(masks), 1)
    mcast = np.zeros((n_guid,), np.uint32)
    mcast[: len(masks)] = np.asarray(masks, np.uint32)
    return RoutingTables(
        dest_of_addr=jnp.asarray(dest),
        guid_of_addr=jnp.asarray(guid),
        mcast_of_guid=jnp.asarray(mcast),
    )


def expand_multicast(event_words: jax.Array, masks: jax.Array, n_links: int):
    """Replay events onto local links per multicast mask.

    Returns (n_links, window) event words: link i receives the event iff
    bit i of its mask is set; other slots are INVALID_EVENT.
    """
    bits = (masks[None, :] >> jnp.arange(n_links, dtype=jnp.uint32)[:, None]) & 1
    return jnp.where(bits.astype(bool), event_words[None, :], ev.INVALID_EVENT)
