"""64-bit spike wire-word codec (the Extoll pulse-event format).

The follow-up paper ("Demonstrating BrainScaleS-2 Inter-Chip
Pulse-Communication using EXTOLL") ships each pulse event as one 64-bit
wire word: a systemtime timestamp plus a routable neuron label, with spare
bits for protocol use.  This module is that word, configurable:

``WireWordFormat`` lays fields LSB-first into a 64-bit space::

    [0, ts_bits)                         timestamp  (event deadline)
    [ts_bits, +label_bits)               label      (routable pulse address)
    [.., +meta_bits)                     meta       (guid OR injection step)
    [ts_bits+label_bits+meta_bits]       valid flag
    remaining bits                       reserved (zero)

The ``meta`` lane is what makes the word load-bearing beyond the 30-bit
internal event word (``repro.core.events``): the exchange path carries the
destination GUID in it (so the multicast LUT key rides the wire instead of
a parallel bitcast array), and the simulator carries the event's
*injection systemtime step*, which is how per-event latency survives the
flush-window scan, transport deferral and residue re-offers.

JAX has no portable uint64 on the default x64-disabled CPU path and TPU
Pallas has no 64-bit integer lanes, so a wire word is represented as two
``uint32`` lanes ``(lo, hi)`` — ``word = (hi << 32) | lo``.  Fields
straddle the lane boundary (the default layout puts meta at bit 29), so
the codec is real 64-bit bit-packing, not a reshuffle.

Pack/unpack run as a Pallas TPU kernel (elementwise VPU bit ops over
(rows, 128) blocks) with the pure-XLA formulation of the same math auto-selected
off-TPU via ``repro.kernels.dispatch`` — identical policy to the fused
placement kernel.  Round-trip is bit-exact for every well-formed event
word (reserved bits zero, see ``events.pack``) and any 32-bit meta value
when ``meta_bits == 32``; tests pin both backends against each other.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from repro.core import events as ev
from repro.kernels import dispatch

LANES, SUBLANES = 128, 8          # one (8, 128) u32 vreg tile
BLOCK_ROWS = 256                  # rows per grid step: 32K events

_U32 = 0xFFFFFFFF


class WireWordFormat(NamedTuple):
    """Field widths of the 64-bit wire word (LSB-first, see module doc).

    ``ts_bits``/``label_bits`` must cover the internal event word's
    timestamp/address fields for a bit-exact round trip (15/14);
    ``meta_bits == 32`` keeps any i32 meta value exact via bitcast.
    """

    ts_bits: int = ev.TS_BITS          # 15
    label_bits: int = ev.ADDR_BITS     # 14
    meta_bits: int = 32

    @property
    def valid_bit(self) -> int:
        return self.ts_bits + self.label_bits + self.meta_bits

    @property
    def word_bytes(self) -> int:
        return 8

    def validate(self) -> "WireWordFormat":
        if not (1 <= self.ts_bits <= 32 and 1 <= self.label_bits <= 32
                and 0 <= self.meta_bits <= 32):
            raise ValueError(f"field widths out of range: {self}")
        if self.valid_bit > 63:
            raise ValueError(
                f"wire word overflows 64 bits: ts {self.ts_bits} + label "
                f"{self.label_bits} + meta {self.meta_bits} + valid > 64")
        return self


DEFAULT_WORD = WireWordFormat().validate()


def _mask(width: int) -> int:
    return ((1 << width) - 1) & _U32


def _deposit(lo, hi, v, offset: int, width: int):
    """OR field ``v`` (pre-masked, uint32) into bits [offset, offset+width)
    of the (lo, hi) lane pair.  ``offset``/``width`` are static, so every
    shift count is a Python int < 32 (jnp shifts >= lane width are UB)."""
    if width == 0:
        return lo, hi
    if offset < 32:
        lo = lo | (v << offset)            # uint32 wraps: keeps low bits
        if offset + width > 32:
            hi = hi | (v >> (32 - offset))
    else:
        hi = hi | (v << (offset - 32))
    return lo, hi


def _extract(lo, hi, offset: int, width: int):
    """Inverse of :func:`_deposit` -> uint32 field value."""
    if width == 0:
        return jnp.zeros_like(lo)
    if offset < 32:
        v = lo >> offset
        if offset + width > 32:
            v = v | (hi << (32 - offset))
    else:
        v = hi >> (offset - 32)
    return v & jnp.uint32(_mask(width))


def _encode_math(word, meta, fmt: WireWordFormat):
    """uint32 event word + uint32 meta -> (lo, hi) lanes.  Pure bit ops —
    shared verbatim by the Pallas kernel body and the XLA path."""
    ts = word & jnp.uint32(ev.TS_MASK & _mask(fmt.ts_bits))
    label = (word >> ev.TS_BITS) & jnp.uint32(ev.ADDR_MASK
                                              & _mask(fmt.label_bits))
    valid = (word >> (ev.TS_BITS + ev.ADDR_BITS)) & jnp.uint32(1)
    meta = meta & jnp.uint32(_mask(fmt.meta_bits)) if fmt.meta_bits else meta
    lo = jnp.zeros_like(word)
    hi = jnp.zeros_like(word)
    lo, hi = _deposit(lo, hi, ts, 0, fmt.ts_bits)
    lo, hi = _deposit(lo, hi, label, fmt.ts_bits, fmt.label_bits)
    lo, hi = _deposit(lo, hi, meta, fmt.ts_bits + fmt.label_bits,
                      fmt.meta_bits)
    lo, hi = _deposit(lo, hi, valid, fmt.valid_bit, 1)
    return lo, hi


def _decode_math(lo, hi, fmt: WireWordFormat):
    """(lo, hi) lanes -> (uint32 event word, uint32 meta)."""
    ts = _extract(lo, hi, 0, fmt.ts_bits) & jnp.uint32(ev.TS_MASK)
    label = (_extract(lo, hi, fmt.ts_bits, fmt.label_bits)
             & jnp.uint32(ev.ADDR_MASK))
    meta = _extract(lo, hi, fmt.ts_bits + fmt.label_bits, fmt.meta_bits)
    valid = _extract(lo, hi, fmt.valid_bit, 1)
    word = ts | (label << ev.TS_BITS) | (valid << (ev.TS_BITS + ev.ADDR_BITS))
    return word, meta


# ---------------------------------------------------------------------------
# Pallas kernels — the same math over (rows, 128) VMEM blocks.
# ---------------------------------------------------------------------------

def _encode_kernel(word_ref, meta_ref, lo_ref, hi_ref, *, fmt):
    lo, hi = _encode_math(word_ref[...], meta_ref[...], fmt)
    lo_ref[...] = lo
    hi_ref[...] = hi


def _decode_kernel(lo_ref, hi_ref, word_ref, meta_ref, *, fmt):
    word, meta = _decode_math(lo_ref[...], hi_ref[...], fmt)
    word_ref[...] = word
    meta_ref[...] = meta


def _pallas_map2(kernel, a, b, fmt, interpret: bool):
    """Run an elementwise 2-in/2-out codec kernel over flat uint32 arrays,
    laid out as (rows, 128) so every block is whole (8, 128) tiles."""
    n = a.shape[0]
    rows = max(-(-n // (LANES * SUBLANES)), 1) * SUBLANES
    block = min(rows, BLOCK_ROWS)
    rows = -(-rows // block) * block
    a, b = (jnp.pad(x, (0, rows * LANES - n)).reshape(rows, LANES)
            for x in (a, b))
    spec = pl.BlockSpec((block, LANES), lambda i: (i, 0))
    o1, o2 = pl.pallas_call(
        functools.partial(kernel, fmt=fmt),
        grid=(rows // block,),
        in_specs=[spec, spec],
        out_specs=(spec, spec),
        out_shape=(jax.ShapeDtypeStruct((rows, LANES), jnp.uint32),
                   jax.ShapeDtypeStruct((rows, LANES), jnp.uint32)),
        interpret=interpret,
    )(a, b)
    return o1.reshape(-1)[:n], o2.reshape(-1)[:n]


def _dispatch2(kernel, math_fn, a, b, fmt, use_pallas, interpret):
    if use_pallas is None:
        use_pallas = dispatch.use_pallas()
    if interpret is None:
        interpret = dispatch.default_interpret()
    shape = a.shape
    if use_pallas:
        o1, o2 = _pallas_map2(kernel, a.reshape(-1), b.reshape(-1), fmt,
                              interpret)
        return o1.reshape(shape), o2.reshape(shape)
    return math_fn(a, b, fmt)


# ---------------------------------------------------------------------------
# Public API.
# ---------------------------------------------------------------------------

def _as_u32(x) -> jax.Array:
    x = jnp.asarray(x)
    if x.dtype == jnp.uint32:
        return x
    if x.dtype == jnp.int32:
        return lax.bitcast_convert_type(x, jnp.uint32)
    return x.astype(jnp.uint32)


def encode_words(events, meta, fmt: WireWordFormat = DEFAULT_WORD, *,
                 use_pallas: bool | None = None,
                 interpret: bool | None = None):
    """Pack event words + meta into 64-bit wire words -> (lo, hi) u32.

    ``meta`` may be i32 (bitcast, exact at ``meta_bits == 32``) or u32;
    shapes broadcast-free (events and meta must match).
    """
    events = _as_u32(events)
    meta = _as_u32(meta)
    if events.shape != meta.shape:
        raise ValueError(f"events {events.shape} != meta {meta.shape}")
    return _dispatch2(_encode_kernel, _encode_math, events, meta, fmt,
                      use_pallas, interpret)


def decode_words(lo, hi, fmt: WireWordFormat = DEFAULT_WORD, *,
                 use_pallas: bool | None = None,
                 interpret: bool | None = None):
    """Inverse of :func:`encode_words` -> (events u32, meta i32)."""
    word, meta = _dispatch2(_decode_kernel, _decode_math, _as_u32(lo),
                            _as_u32(hi), fmt, use_pallas, interpret)
    return word, lax.bitcast_convert_type(meta, jnp.int32)


def encode_planar(events, meta, fmt: WireWordFormat = DEFAULT_WORD, *,
                  use_pallas: bool | None = None,
                  interpret: bool | None = None) -> jax.Array:
    """(..., C) events + meta -> one (..., 2C) u32 wire buffer.

    Lane-planar layout: ``buf[..., :C]`` are the lo lanes, ``buf[..., C:]``
    the hi lanes of word j — the transport payload stays a single opaque
    u32 buffer exactly as wide as the old events|guids concat.
    """
    lo, hi = encode_words(events, meta, fmt, use_pallas=use_pallas,
                          interpret=interpret)
    return jnp.concatenate([lo, hi], axis=-1)


def decode_planar(buf: jax.Array, fmt: WireWordFormat = DEFAULT_WORD, *,
                  use_pallas: bool | None = None,
                  interpret: bool | None = None):
    """Inverse of :func:`encode_planar` -> (events u32, meta i32)."""
    c = buf.shape[-1] // 2
    return decode_words(buf[..., :c], buf[..., c:], fmt,
                        use_pallas=use_pallas, interpret=interpret)
