"""Fused route+aggregate flush-window kernel (paper §3, §3.1 in one pass).

The seed hot path was three separate stages — routing-LUT gather, then an
O(N·D·C) per-destination one-hot reduce (``bucket_scatter.py``), then the
collective — and the Pallas kernel only ever ran in interpret mode.  This
module replaces the compute side with a sort-based formulation:

  1. **route**   — ``dest = dest_lut[addr]`` gather, validity from the
                   event's valid bit and ``NO_ROUTE`` (LUT 1 of the paper)
  2. **rank**    — one stable multi-operand ``lax.sort`` by destination
                   groups each destination's events contiguously in window
                   order: O(N log N), and the slot of an event is simply its
                   offset from the first event of its destination
  3. **place**   — each destination's bucket row is a *dynamic slice* of
                   the sorted window (O(D·C) total, no scatter); the
                   destination-GUID lookup (LUT 1's second output) runs
                   after placement, so only the ≤ C accepted events per
                   destination are gathered, not all N
  4. **residue** — events beyond a bucket's capacity are compacted into a
                   fixed-size carry buffer re-offered next window (the
                   FPGA's back-pressure on the HICANN links)

Stage 3 is a Pallas TPU kernel (grid over destination tiles; each row is
read from the VMEM-resident sorted window as whole (8, 128) tiles and
rotated into place, see ``_shift_rows``).  Backend dispatch is automatic
(``kernels.dispatch``): compiled Pallas on TPU, pure-XLA placement on
CPU/GPU where interpret mode would be a correctness tool rather than a
fast path; tests exercise the interpret path explicitly against the
``ref.py`` oracle.

The destination gather (stage 1) stays in XLA because it *produces the sort
key*; fusing it into the placement kernel would force the sort inside the
kernel, which TPU Pallas cannot lower.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import events as ev
from repro.core.aggregator import Buckets
from repro.kernels import dispatch

D_TILE = 8                        # destinations per grid step
LANES, SUBLANES = 128, 8
TILE = LANES * SUBLANES           # one (8, 128) u32 vreg


class FusedWindow(NamedTuple):
    """Result of one fused route+aggregate window.

    buckets:  the standard ``aggregator.Buckets`` (data/guids/counts/overflow)
    residue:  (residue_len,) u32 deferred events, window-grouped, INVALID-padded
    deferred: () i32 events carried to the next window via ``residue``
    dropped:  () i32 overflow events that did not fit the residue buffer
    offered:  () i32 valid routed events offered this window
    residue_meta: (residue_len,) i32 the deferred events' meta values (the
              ``guids`` operand, e.g. the simulator's per-event injection
              timestamps), aligned with ``residue``; None unless requested
              via ``with_residue_meta`` (explicit-meta path only)
    """

    buckets: Buckets
    residue: jax.Array
    deferred: jax.Array
    dropped: jax.Array
    offered: jax.Array
    residue_meta: jax.Array | None = None


# ---------------------------------------------------------------------------
# Pallas placement kernel — stage 3.
# ---------------------------------------------------------------------------
#
# A destination's row starts at an arbitrary offset of the sorted window,
# and Mosaic only loads vectors at offsets it can prove tile-aligned.  So
# the window is laid out as (tiles, 8, 128) — one (8, 128) vreg tile per
# leading index, which a dynamic index may address freely — and each row
# loads the N_T whole tiles that cover it, then shifts the wanted run to
# the front with dynamic sublane/lane rotations.

def _shift_rows(x, k):
    """Row-major ``flat(x)[k:]`` laid back onto ``x``'s (rows, 128) shape
    (the tail wraps; callers keep only rows the shift filled)."""
    rows = x.shape[0]
    q, r = k // LANES, k % LANES
    a = pltpu.roll(x, (rows - q) % rows, 0)         # a[j] = x[j + q]
    b = pltpu.roll(a, (LANES - r) % LANES, 1)       # b[j, c] = a[j, c + r]
    c = pltpu.roll(b, rows - 1, 0)                  # c[j] = b[j + 1]
    col = lax.broadcasted_iota(jnp.int32, x.shape, 1)
    return jnp.where(col < LANES - r, b, c)


def _place_kernel(first_ref, counts_ref, *refs, capacity: int, n_t: int):
    n_arr = len(refs) // 2
    rows_out = refs[n_arr].shape[1]
    base = pl.program_id(0) * D_TILE
    shape = (rows_out, LANES)
    slot = (lax.broadcasted_iota(jnp.int32, shape, 0) * LANES
            + lax.broadcasted_iota(jnp.int32, shape, 1))
    for d in range(D_TILE):
        start = first_ref[base + d]
        live = slot < jnp.minimum(counts_ref[base + d], capacity)
        t0, k = start // TILE, start % TILE
        for src, dst in zip(refs[:n_arr], refs[n_arr:]):
            x = src[pl.ds(t0, n_t)].reshape(n_t * SUBLANES, LANES)
            y = _shift_rows(x, k)[:rows_out]
            dst[d] = jnp.where(live, y, jnp.zeros_like(y))


def _placement_pallas(first, counts, arrays, n_dest: int, capacity: int,
                      *, interpret: bool):
    """Place each of ``arrays`` (sorted window operands) into (n_dest,
    capacity) rows; a grid step fills D_TILE destinations."""
    d_pad = -(-n_dest // D_TILE) * D_TILE
    first = jnp.pad(first, (0, d_pad - n_dest))
    counts = jnp.pad(counts, (0, d_pad - n_dest))
    c_pad = -(-capacity // LANES) * LANES
    rows_out = c_pad // LANES
    n_t = -(-(c_pad + TILE - 1) // TILE)            # tiles one row can span
    n = arrays[0].shape[0]
    tiles = -(-n // TILE) + n_t                     # start <= n: t0+n_t fits
    arrays = [jnp.pad(a, (0, tiles * TILE - n)).reshape(tiles, SUBLANES,
                                                        LANES)
              for a in arrays]
    whole = pl.BlockSpec((tiles, SUBLANES, LANES), lambda i, f, c: (0, 0, 0))
    rows = pl.BlockSpec((D_TILE, rows_out, LANES), lambda i, f, c: (i, 0, 0))
    outs = pl.pallas_call(
        functools.partial(_place_kernel, capacity=capacity, n_t=n_t),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(d_pad // D_TILE,),
            in_specs=[whole] * len(arrays), out_specs=[rows] * len(arrays)),
        out_shape=[jax.ShapeDtypeStruct((d_pad, rows_out, LANES), a.dtype)
                   for a in arrays],
        interpret=interpret,
    )(first, counts, *arrays)
    return [o.reshape(d_pad, c_pad)[:n_dest, :capacity] for o in outs]


# ---------------------------------------------------------------------------
# XLA placement — same math, used where Pallas would only interpret.
# ---------------------------------------------------------------------------

def _placement_jnp(first, counts, arrays, n_dest: int, capacity: int):
    slot = jnp.arange(capacity)[None, :]
    live = slot < jnp.minimum(counts, capacity)[:, None]
    idx = first[:, None] + slot                     # <= n + capacity - 1
    return [jnp.where(live, jnp.pad(a, (0, capacity))[idx],
                      jnp.zeros((), a.dtype)) for a in arrays]


# ---------------------------------------------------------------------------
# Fused op.
# ---------------------------------------------------------------------------

def _finish(skey, swords, aux, n_dest: int, capacity: int, residue_len: int,
            *, routed: bool, use_pallas: bool | None, interpret: bool | None,
            with_residue_meta: bool = False):
    n = swords.shape[0]
    edges = jnp.searchsorted(skey, jnp.arange(n_dest + 1, dtype=skey.dtype))
    first = edges[:-1].astype(jnp.int32)
    counts = (edges[1:] - edges[:-1]).astype(jnp.int32)
    if use_pallas is None:
        use_pallas = dispatch.use_pallas()
    if interpret is None:
        interpret = dispatch.default_interpret()
    if with_residue_meta and routed:
        raise ValueError("with_residue_meta needs per-event meta (the "
                         "explicit-guids path), not a routed guid LUT")
    smeta = aux if not routed else None          # (n,) sorted per-event meta
    operands = [swords] if routed else [swords, aux]
    if use_pallas:
        placed = _placement_pallas(first, counts, operands, n_dest, capacity,
                                   interpret=interpret)
    else:
        placed = _placement_jnp(first, counts, operands, n_dest, capacity)
    accepted = jnp.minimum(counts, capacity)
    data = placed[0]
    if routed:
        # LUT 1's guid output, gathered for the <= capacity accepted
        # events of each row only
        live = jnp.arange(capacity)[None, :] < accepted[:, None]
        addr = ev.address(data).astype(jnp.int32)
        g = jnp.take(aux, jnp.minimum(addr, aux.shape[0] - 1))
        gui = jnp.where(live, g, 0)
    else:
        gui = placed[1]
    offered = jnp.sum(counts).astype(jnp.int32)
    overflow = (offered - jnp.sum(accepted)).astype(jnp.int32)
    buckets = Buckets(data, gui, accepted, overflow)

    res_meta = None
    if residue_len:
        # overflow events = sorted index >= first-of-dest + capacity
        first_of = jnp.take(first, jnp.minimum(skey, n_dest - 1))
        pos = jnp.arange(n, dtype=jnp.int32) - first_of
        ovf = (skey < n_dest) & (pos >= capacity)
        ovfkey = jnp.where(ovf, 0, 1).astype(jnp.int32)
        r = min(residue_len, n)
        deferred = jnp.minimum(overflow, r)
        live_r = jnp.arange(r) < deferred
        if with_residue_meta:
            _, rwords, rmeta = lax.sort(
                (ovfkey, swords, smeta.astype(jnp.int32)),
                num_keys=1, is_stable=True)
            res_meta = jnp.where(live_r, rmeta[:r], 0)
            if residue_len > n:
                res_meta = jnp.concatenate(
                    [res_meta, jnp.zeros((residue_len - n,), jnp.int32)])
        else:
            _, rwords = lax.sort((ovfkey, swords), num_keys=1, is_stable=True)
        res = jnp.where(live_r, rwords[:r], ev.INVALID_EVENT)
        if residue_len > n:
            res = jnp.concatenate(
                [res, jnp.full((residue_len - n,), ev.INVALID_EVENT)])
        dropped = overflow - deferred
    else:
        res = jnp.zeros((0,), jnp.uint32)
        if with_residue_meta:
            res_meta = jnp.zeros((0,), jnp.int32)
        deferred = jnp.zeros((), jnp.int32)
        dropped = overflow
    return FusedWindow(buckets, res, deferred.astype(jnp.int32),
                       dropped.astype(jnp.int32), offered, res_meta)


def fused_aggregate(words, dest, guids, n_dest: int, capacity: int, *,
                    residue_len: int = 0, use_pallas: bool | None = None,
                    interpret: bool | None = None,
                    with_residue_meta: bool = False) -> FusedWindow:
    """Sort-based aggregation with explicit per-event destinations/guids.

    Drop-in (via ``.buckets``) for ``aggregator.aggregate`` semantics:
    window order within each destination, capacity clip, invalid events
    (valid bit clear or dest out of range) ignored.  ``guids`` is an
    arbitrary i32 meta value riding with each event (destination GUID —
    or the simulator's injection timestamp); ``with_residue_meta`` also
    carries it for the deferred events (``FusedWindow.residue_meta``),
    so meta survives overflow re-offer round-trips.
    """
    dest = dest.astype(jnp.int32)
    valid = ev.is_valid(words) & (dest >= 0) & (dest < n_dest)
    key = jnp.where(valid, dest, n_dest)
    skey, swords, sguids = lax.sort((key, words, guids.astype(jnp.int32)),
                                    num_keys=1, is_stable=True)
    return _finish(skey, swords, sguids, n_dest, capacity, residue_len,
                   routed=False, use_pallas=use_pallas, interpret=interpret,
                   with_residue_meta=with_residue_meta)


def fused_route_aggregate(words, dest_lut, guid_lut, n_dest: int,
                          capacity: int, *, residue_len: int = 0,
                          use_pallas: bool | None = None,
                          interpret: bool | None = None) -> FusedWindow:
    """Routing-LUT gather + capacity-bounded binning in one fused pass.

    ``dest_lut``/``guid_lut`` are ``RoutingTables.dest_of_addr`` /
    ``.guid_of_addr`` (same clamped-index semantics as ``tables.route``).
    The guid gather runs after placement, over accepted events only.
    """
    addr = ev.address(words).astype(jnp.int32)
    dest = jnp.take(dest_lut, jnp.minimum(addr, dest_lut.shape[0] - 1))
    valid = ev.is_valid(words) & (dest >= 0) & (dest < n_dest)
    key = jnp.where(valid, dest, n_dest).astype(jnp.int32)
    skey, swords = lax.sort((key, words), num_keys=1, is_stable=True)
    return _finish(skey, swords, guid_lut.astype(jnp.int32), n_dest, capacity,
                   residue_len, routed=True, use_pallas=use_pallas,
                   interpret=interpret)
