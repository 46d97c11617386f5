"""Plain reference of one simulation segment: the microcircuit advanced
window by window with no buckets, codec or transport.

A segment is ``n_windows`` flush windows of ``window`` steps.  At the
start of each window every spike still in flight is applied: a spike of
neuron ``j`` emitted at step ``s`` lands at step ``s + delay[j]`` in the
delay ring slot ``(t + max(s + delay[j] - t, 0)) % ring_len`` of every
neuron it synapses onto, weighted by ``W[target, j]`` (events that land
past their deadline count as misses).  Then ``window`` exact-integration
LIF steps run off the ring with a Poisson background drive, and the
window's spikes are the next window's spikes in flight.

State is global over the ``S * per`` neurons (shards concatenated); the
background drive keeps one PRNG key per shard, as the deployment's nodes
each draw their own.  The apply multiplies a 0/1 arrival matrix by the
weights at ``highest`` precision after rounding the weights to
``precision``: the products are then exact and the sums are float32,
whatever the weights' stated precision.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

def round_to(w: jax.Array, precision: str) -> jax.Array:
    """``w`` as a computation in ``precision`` sees it: float32,
    bfloat16, or int8 with one symmetric scale for the whole matrix."""
    if precision == "int8":
        scale = jnp.max(jnp.abs(w)) / 127.0
        return jnp.round(w / scale) * scale
    return w.astype(jnp.dtype(precision)).astype(jnp.float32)


def propagators(lif: dict):
    """Exact-integration constants of one step (``iaf_psc_exp``)."""
    pm = jnp.exp(-lif["dt"] / lif["tau_m"])
    ps = jnp.exp(-lif["dt"] / lif["tau_syn"])
    tau_r = lif["tau_syn"] * lif["tau_m"] / (lif["tau_m"] - lif["tau_syn"])
    pv = (tau_r / lif["c_m"]) * (pm - ps)
    return pm, ps, pv, int(round(lif["t_ref"] / lif["dt"]))


def make_window(lif: dict, bg_rate: np.ndarray, bg_weight: float,
                n_shards: int, steps: int):
    """Jitted ``(state, w, arrivals) -> (state, spikes (steps, N))``.

    ``state``: dict of v, i_exc, i_inh (N,) f32, refrac (N,) i32,
    ring_exc/ring_inh (L, N) f32, t () i32, key (S, 2) u32.
    ``arrivals``: (2, L, N) f32 counts of exc / inh source spikes landing
    in each ring slot this window; ``w``: (N, N) [target, source].
    """
    per = bg_rate.shape[0] // n_shards
    rates = jnp.asarray(bg_rate.reshape(n_shards, per))
    dt = lif["dt"]

    @jax.jit
    def window(state, w, arrivals):
        pm, ps, pv, ref_steps = propagators(lif)
        n_ring = state["ring_exc"].shape[0]
        add = jnp.einsum("xls,ts->xlt", arrivals, w, precision="highest")
        ring_e = state["ring_exc"] + add[0]
        ring_i = state["ring_inh"] + add[1]

        def step(c, _):
            v, ie, ii, refrac, ring_e, ring_i, t, keys = c
            slot = t % n_ring
            drive, new_keys = [], []
            for s in range(n_shards):
                k, sub = jax.random.split(keys[s])
                lam = rates[s] * (dt * 1e-3)
                drive.append(jax.random.poisson(sub, lam, (per,))
                             .astype(jnp.float32) * bg_weight)
                new_keys.append(k)
            exc_in = ring_e[slot] + jnp.concatenate(drive)
            inh_in = ring_i[slot]
            active = refrac <= 0
            v_new = jnp.where(
                active, lif["e_l"] + (v - lif["e_l"]) * pm + pv * (ie + ii), v)
            ie = ie * ps + exc_in
            ii = ii * ps + inh_in
            spk = active & (v_new >= lif["v_th"])
            v = jnp.where(spk, lif["v_reset"], v_new)
            refrac = jnp.where(spk, ref_steps, jnp.maximum(refrac - 1, 0))
            ring_e = ring_e.at[slot].set(0.0)
            ring_i = ring_i.at[slot].set(0.0)
            return (v, ie, ii, refrac, ring_e, ring_i, t + 1,
                    jnp.stack(new_keys)), spk

        c0 = (state["v"], state["i_exc"], state["i_inh"], state["refrac"],
              ring_e, ring_i, state["t"], state["key"])
        c, spikes = jax.lax.scan(step, c0, None, length=steps)
        names = ("v", "i_exc", "i_inh", "refrac", "ring_exc", "ring_inh",
                 "t", "key")
        return dict(zip(names, c)), spikes

    return window


class Network:
    """What the reference knows of a deployment: weights, delays, the
    node each neuron lives on and the nodes each neuron's synapses reach."""

    def __init__(self, w: np.ndarray, inh: np.ndarray, n_shards: int,
                 delay_exc: int, delay_inh: int):
        n = w.shape[0]
        per = -(-n // n_shards)
        n_pad = per * n_shards
        wp = np.zeros((n_pad, n_pad), np.float32)
        wp[:n, :n] = w
        self.w = wp
        self.inh = np.pad(inh, (0, n_pad - n))
        self.n, self.per, self.n_shards = n_pad, per, n_shards
        self.delay = np.where(self.inh, delay_inh, delay_exc).astype(np.int64)
        node = np.arange(n_pad) // per
        # reach[j, d]: neuron j has a synapse on node d
        reach = np.zeros((n_pad, n_shards), bool)
        for d in range(n_shards):
            reach[:, d] = (wp[d * per:(d + 1) * per] != 0).any(0)
        self.reach = reach
        self.node = node
        # synapses of source j onto node d
        self.syn = np.stack([(wp[d * per:(d + 1) * per] != 0).sum(0)
                             for d in range(n_shards)], 1)


def hops(fabric: dict, n_shards: int) -> np.ndarray:
    """(S, S) links a row crosses: 1 off-node on a crossbar; on a torus
    the ring distance summed over axes (x fastest in the node id)."""
    ids = np.arange(n_shards)
    if fabric["transport"] == "alltoall":
        return (ids[:, None] != ids[None, :]).astype(np.int64)
    dims = fabric["torus"]
    h = np.zeros((n_shards, n_shards), np.int64)
    a, b = ids[:, None], ids[None, :]
    for d in dims:
        da = np.abs(a % d - b % d)
        h += np.minimum(da, d - da)
        a, b = a // d, b // d
    return h


def frame_bytes(wire: dict, n: np.ndarray) -> np.ndarray:
    """On-wire bytes of a row of ``n`` events: full frames plus one
    partial, each padded to whole cells, with header, CRC, minimum frame
    and inter-frame gap."""
    n = np.asarray(n, np.int64)
    per_frame = wire["mtu_payload"] // wire["word_bytes"]

    def frame(payload):
        cells = -(-payload // wire["cell_bytes"]) * wire["cell_bytes"]
        return np.maximum(cells + wire["header_bytes"] + wire["crc_bytes"],
                          wire["min_frame_bytes"]) + wire["gap_bytes"]

    full, rem = n // per_frame, n % per_frame
    return full * frame(wire["mtu_payload"]) + np.where(
        rem > 0, frame(rem * wire["word_bytes"]), 0)


LATENCY_EDGES_US = np.array([2.0 ** e for e in range(-2, 13)], np.float32)


def latency_hist(wait_steps, hop_count, row_events, wire: dict,
                 step_us: float) -> np.ndarray:
    """Histogram of per-event latencies over the log2 bins
    [.., 0.25), [0.25, 0.5), ..., [4096, ..) us: the wait since
    injection plus, per link crossed, a switch and one serialization of
    the event's row.  float32, in that order of operations."""
    f = np.float32
    wait = np.asarray(wait_steps).astype(f) * f(step_us)
    ser = frame_bytes(wire, row_events).astype(f) / f(wire["bytes_per_us"])
    hop = np.asarray(hop_count).astype(f) * (f(wire["switch_latency_us"]) + ser)
    lat = np.maximum(wait, f(0)) + hop
    b = np.searchsorted(LATENCY_EDGES_US, lat, side="right")
    return np.bincount(b, minlength=len(LATENCY_EDGES_US) + 1)
