"""The comparison that decides ``correct``: a sampled segment of the
timed path against the plain reference run from the same state.

The program's state at the start of a sampled segment (neurons, delay
ring, PRNG keys, the events in flight) is read into plain arrays, the
reference (``segment.py``) advances it by the segment's windows, and the
two end states and per-window statistics are compared.  The events in
flight are read from the 30-bit event words of the paper (15-bit
deadline, 14-bit address ``local_id * max_fan + replica``, valid bit 29)
with the injection step beside each.

Numbers, each held to a limit of its cell:

* ``v_gap_mv``: largest |v| difference over all neurons;
* ``current_gap_pa``: largest difference of a synaptic current or a
  delay-ring entry;
* ``spike_mismatch``: spikes per window and node that differ, plus
  neurons whose refractory count differs, plus step counters and PRNG
  keys that differ;
* ``event_mismatch``: events in flight that are malformed, misrouted,
  missing or extra (both at the start and at the end), per-window
  delivery counts and deadline misses that differ, events dropped, and
  violations of the link identities ``offered == sent + deferred +
  parked`` and ``sum(sent) + sum(unparked) == sum(delivered)``;
* ``latency_mismatch``: events whose latency falls in another histogram
  bin than the reference's.
"""
from __future__ import annotations

import numpy as np

from perf.reference import segment

TS_BITS, ADDR_BITS, VALID_BIT = 15, 14, 29
TS_MASK = (1 << TS_BITS) - 1
NUMBERS = ("v_gap_mv", "current_gap_pa", "spike_mismatch",
           "event_mismatch", "latency_mismatch")


def decode_events(net: segment.Network, data, meta, counts):
    """Bucket rows (S_src, S_dst, C) -> ((M, 4) int64 rows of
    (source neuron, injection step, node, deadline), malformed count)."""
    fan = int(net.reach.sum(1).max())
    rows, bad = [], 0
    n_src, n_dst = counts.shape
    for s in range(n_src):
        for d in range(n_dst):
            k = int(counts[s, d])
            w = data[s, d, :k].astype(np.int64)
            m = meta[s, d, :k].astype(np.int64)
            valid = (w >> VALID_BIT) & 1 == 1
            addr = (w >> TS_BITS) & ((1 << ADDR_BITS) - 1)
            j = s * net.per + addr // fan
            ok = valid & (addr // fan < net.per)
            jj = np.where(ok, j, 0)
            nodes = [np.flatnonzero(net.reach[x]) for x in jj]
            rep = addr % fan
            ok &= np.array([r < len(nd) and nd[r] == d
                            for r, nd in zip(rep, nodes)], bool)
            ok &= (w & TS_MASK) == ((m + net.delay[jj]) & TS_MASK)
            bad += int((~ok).sum())
            rows.append(np.stack([jj[ok], m[ok], np.full(ok.sum(), d),
                                  (w & TS_MASK)[ok]], 1))
    ev = np.concatenate(rows) if rows else np.zeros((0, 4), np.int64)
    return ev[np.lexsort(ev.T[::-1])], bad


def expand(net: segment.Network, src, inject):
    """Spikes -> the (M, 4) events they owe: one per node reached."""
    src, inject = np.asarray(src, np.int64), np.asarray(inject, np.int64)
    sj, d = np.nonzero(net.reach[src])
    j = src[sj]
    ev = np.stack([j, inject[sj], d, (inject[sj] + net.delay[j]) & TS_MASK], 1)
    return ev[np.lexsort(ev.T[::-1])]


def multiset_gap(a: np.ndarray, b: np.ndarray) -> int:
    """Rows in one multiset and not the other, both ways."""
    ua, ca = np.unique(a, axis=0, return_counts=True)
    ub, cb = np.unique(b, axis=0, return_counts=True)
    keys = {tuple(r): c for r, c in zip(ua, ca)}
    gap = 0
    for r, c in zip(ub, cb):
        gap += abs(keys.pop(tuple(r), 0) - c)
    return int(gap + sum(keys.values()))


def in_flight(net: segment.Network, before: dict):
    """The spikes in flight in a program state, and how many of its
    events in flight are malformed, misrouted, missing or extra."""
    ev, bad = decode_events(net, before["pend_data"], before["pend_meta"],
                            before["pend_counts"])
    spikes = np.unique(ev[:, :2], axis=0) if len(ev) else np.zeros((0, 2),
                                                                   np.int64)
    bad += multiset_gap(ev, expand(net, spikes[:, 0], spikes[:, 1]))
    bad += int(((before["residue"].astype(np.int64) >> VALID_BIT) & 1).sum())
    bad += int(before["parked"])
    return spikes, bad


def simulate(net: segment.Network, cfg: dict, before: dict, spikes,
             window_fn, w_dev, n_windows: int) -> dict:
    """Advance ``before`` by ``n_windows`` windows; outputs in the form
    :func:`program_outputs` gives the program's."""
    import jax.numpy as jnp
    S, per, L = net.n_shards, net.per, cfg["fabric"]["ring_len"]
    hop = segment.hops(cfg["fabric"], S)
    nbins = len(segment.LATENCY_EDGES_US) + 1
    st = {k: jnp.asarray(before[k]) for k in
          ("v", "i_exc", "i_inh", "refrac", "ring_exc", "ring_inh", "key")}
    t = int(before["t"][0])
    st["t"] = jnp.int32(t)
    out = {k: np.zeros((n_windows, S), np.int64) for k in
           ("spikes", "delivered", "miss", "overflow")}
    out["hist"] = np.zeros((n_windows, S, nbins), np.int64)
    src, inj = spikes[:, 0], spikes[:, 1]
    for w in range(n_windows):
        ev = expand(net, src, inj)                 # (j, inject, node, ts)
        j, d = ev[:, 0], ev[:, 2]
        slack = ev[:, 1] + net.delay[j] - t
        out["miss"][w] = np.bincount(d[slack < 0], minlength=S)
        out["delivered"][w] = np.bincount(d, minlength=S)
        rows = np.zeros((S, S), np.int64)
        np.add.at(rows, (net.node[j], d), 1)
        for node in range(S):
            m = d == node
            out["hist"][w, node] = segment.latency_hist(
                t - ev[m, 1], hop[net.node[j[m]], node],
                rows[net.node[j[m]], node], cfg["wire"], cfg["step_us"])
        arr = np.zeros((2, L, net.n), np.float32)
        slot = (t + np.maximum(inj + net.delay[src] - t, 0)) % L
        np.add.at(arr, (net.inh[src].astype(np.int64), slot, src), 1.0)
        st, spk = window_fn(st, w_dev, jnp.asarray(arr))
        spk = np.asarray(spk)
        out["spikes"][w] = spk.reshape(spk.shape[0], S, per).sum((0, 2))
        step, src = np.nonzero(spk)
        inj = t + step
        t += spk.shape[0]
    for k in ("v", "i_exc", "i_inh", "refrac", "ring_exc", "ring_inh",
              "key"):
        out[k] = np.asarray(st[k])
    out["t"] = np.full(S, t, np.int64)
    out["events"] = expand(net, src, inj)
    out["bad"] = 0
    return out


def program_outputs(net: segment.Network, after: dict, stats: dict) -> dict:
    """The program's end state and statistics in the reference's form."""
    out = {k: after[k] for k in ("v", "i_exc", "i_inh", "refrac",
                                 "ring_exc", "ring_inh", "t", "key")}
    for k in ("spikes", "delivered", "miss", "overflow", "hist"):
        out[k] = stats[k]
    out["events"], bad = decode_events(net, after["pend_data"],
                                       after["pend_meta"],
                                       after["pend_counts"])
    bad += int(((after["residue"].astype(np.int64) >> VALID_BIT) & 1).sum())
    bad += int(after["parked"])
    # link identities, per window and node, and per window over the fabric
    bad += int((stats["offered_ev"] != stats["sent_ev"] + stats["deferred_ev"]
                + stats["parked_ev"]).sum())
    bad += int((stats["sent_ev"].sum(1) + stats["unparked_ev"].sum(1)
                != stats["delivered"].sum(1)).sum())
    out["bad"] = bad
    return out


def compare(got: dict, ref: dict, bad_before: int = 0) -> dict:
    """The numbers of one sampled segment (see the module docstring)."""
    def gap(k):
        d = float(np.max(np.abs(np.asarray(got[k], np.float64)
                                - np.asarray(ref[k], np.float64))))
        # a state gone NaN or infinite is as far off as can be
        return d if np.isfinite(d) else float(np.finfo(np.float64).max)
    ne = lambda k: int((np.asarray(got[k]) != np.asarray(ref[k])).sum())
    absdiff = lambda k: int(np.abs(np.asarray(got[k], np.int64)
                                   - np.asarray(ref[k], np.int64)).sum())
    return {
        "v_gap_mv": gap("v"),
        "current_gap_pa": max(gap(k) for k in ("i_exc", "i_inh",
                                               "ring_exc", "ring_inh")),
        "spike_mismatch": absdiff("spikes") + ne("refrac") + ne("t")
        + ne("key"),
        "event_mismatch": int(bad_before) + int(got["bad"])
        + multiset_gap(got["events"], ref["events"])
        + absdiff("delivered") + absdiff("miss") + absdiff("overflow"),
        "latency_mismatch": absdiff("hist") // 2 + absdiff("hist") % 2,
    }


def worst(readings: list[dict]) -> dict:
    """Each number's worst over the sampled segments."""
    return {k: max(r[k] for r in readings) for k in NUMBERS}
