"""Packed spike-event words — the wire format of the BrainScaleS/Extoll link.

The paper (§3) describes events leaving a HICANN as a 12-bit source neuron
pulse address plus a 15-bit timestamp stating an arrival *deadline* in
systemtime units.  On the wire a single event occupies a 30-bit word; we
round up to a ``uint32`` lane ("events are deserialised to groups of four",
i.e. 4 events per 16-byte network word).

Bit layout used here (LSB first)::

    [ 0:15)  timestamp  (15 bits, systemtime units, wraps)
    [15:29)  address    (14 bits: 12-bit pulse address + 2-bit link id,
                         so a full FPGA's 8 HICANNs x 64 sources fit)
    [29:30)  valid flag
    [30:32)  reserved

All functions are shape-polymorphic and jit-safe; events travel through the
system as ``uint32`` arrays so they can be bucketed, shuffled through
``all_to_all`` and multicast without structure-of-arrays bookkeeping.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

# --- wire-format constants (faithful to the paper) ----------------------
TS_BITS = 15
ADDR_BITS = 14          # 12-bit pulse address + 2 spare (link id)
TS_MASK = (1 << TS_BITS) - 1
ADDR_MASK = (1 << ADDR_BITS) - 1
VALID_BIT = 1 << (TS_BITS + ADDR_BITS)      # bit 29
EVENT_BITS = 30                              # "single 30 bit events"
EVENT_BYTES = 4                              # rounded to a uint32 lane

# Extoll packet geometry (§3.1): max payload 496 B == 124 events.
PACKET_PAYLOAD_BYTES = 496
PACKET_MAX_EVENTS = PACKET_PAYLOAD_BYTES // EVENT_BYTES   # == 124
# Tourmalet cell-header overhead for a minimal RMA put: modelled as one
# 16-byte network word.  With a 16-byte/cycle datapath at the 210 MHz FPGA
# clock this reproduces the paper's bottleneck exactly: a single-event
# message costs header (1 cycle) + one deserialisation group (1 cycle)
# = 2 cycles -> "one event every two clocks", while events arrive at up to
# one per clock.  A full 124-event packet costs 32 cycles -> 3.875
# events/cycle of drain headroom.
PACKET_HEADER_BYTES = 16
DATAPATH_BYTES_PER_CYCLE = 16                # FPGA->link datapath width
DESERIAL_GROUP = 4                           # events per network word

# host-side scalars (numpy, not jnp): importing the package must not
# start a JAX backend, or a process could not hand the chip to a child
INVALID_EVENT = np.uint32(0)                 # valid bit clear


def pack(address: jax.Array, timestamp: jax.Array, valid=None) -> jax.Array:
    """Pack (address, timestamp[, valid]) into uint32 event words."""
    address = jnp.asarray(address)
    timestamp = jnp.asarray(timestamp)
    word = ((address.astype(jnp.uint32) & ADDR_MASK) << TS_BITS) | (
        timestamp.astype(jnp.uint32) & TS_MASK
    )
    if valid is None:
        valid = jnp.ones_like(word, dtype=bool)
    return jnp.where(valid, word | VALID_BIT, jnp.uint32(0))


def address(event: jax.Array) -> jax.Array:
    return (event >> TS_BITS) & ADDR_MASK


def timestamp(event: jax.Array) -> jax.Array:
    return event & TS_MASK


def is_valid(event: jax.Array) -> jax.Array:
    return (event & VALID_BIT) != 0


def unpack(event: jax.Array):
    """-> (address, timestamp, valid)."""
    return address(event), timestamp(event), is_valid(event)


def ts_before(a: jax.Array, b: jax.Array) -> jax.Array:
    """Wrap-aware 'deadline a is earlier than deadline b' on 15-bit time.

    Uses the standard serial-number-arithmetic trick: a precedes b iff
    (a - b) mod 2^15 is in the upper half of the ring.
    """
    d = (a.astype(jnp.int32) - b.astype(jnp.int32)) & TS_MASK
    return d > (TS_MASK >> 1)


def ts_slack(deadline: jax.Array, now: jax.Array) -> jax.Array:
    """Signed systemtime units until ``deadline`` (negative = missed)."""
    d = (deadline.astype(jnp.int32) - now.astype(jnp.int32)) & TS_MASK
    return jnp.where(d > (TS_MASK >> 1), d - (TS_MASK + 1), d)


def packet_bytes(n_events) -> jax.Array:
    """Wire bytes for a packet carrying ``n_events`` events (header incl.).

    Events are deserialised to groups of four (16-byte network words), so
    the payload is rounded up to the group size.  A zero-event packet costs
    nothing (no packet is emitted).
    """
    n = jnp.asarray(n_events, jnp.int32)
    groups = (n + (DESERIAL_GROUP - 1)) // DESERIAL_GROUP
    payload = groups * DESERIAL_GROUP * EVENT_BYTES
    return jnp.where(n > 0, payload + PACKET_HEADER_BYTES, 0)


def wire_cycles(n_events) -> jax.Array:
    """FPGA cycles the output port is busy shifting a packet of n events."""
    b = packet_bytes(n_events)
    return (b + (DATAPATH_BYTES_PER_CYCLE - 1)) // DATAPATH_BYTES_PER_CYCLE


def wire_efficiency(n_events) -> jax.Array:
    """Fraction of packet bytes that are event payload (the paper's
    header-amortization curve; == ~0.5 at n=1, -> 496/512 at n=124)."""
    n = jnp.asarray(n_events, jnp.int32)
    useful = n * EVENT_BYTES
    total = packet_bytes(n)
    return jnp.where(total > 0, useful / jnp.maximum(total, 1), 0.0)
