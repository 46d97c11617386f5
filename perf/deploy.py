"""A cell, found by name: its workload, deployment and traffic files,
the network the program runs (cached), and the state the checks read.

Layout, under the benchmark's directory::

    workloads/<cell>.json   config, traffic, chips, why, limits
    configs/<config>.json   one deployment
    traffic/<traffic>.json  one traffic mix; ``"drive"`` names its drive
    drives/<drive>.py       one drive (``background`` if the mix names none)
    metrics/<metric>.json   one per-layer metric (+ <metric>.py)

A cell added later is a new file under each of these; nothing here
names one.

A drive is the stimulus a mix offers the network, given to both sides
of the check by two functions:

    program_inputs(cell, part) -> dict
        the keyword arguments of ``simulator.build_sharded_segments``
        after ``(mesh, axis_name, sim_config, part)``, such as
        ``bg_rates`` and ``bg_weight``;
    reference_window(cell, rnet) -> window_fn
        the reference's window, ``(state, w, arrivals) -> (state,
        spikes)`` as ``reference.segment.make_window`` builds it.

The reference restarts from the program's state at the start of any
sampled segment, so a drive's stimulus may depend only on that plain
state (``t``, ``key``, neurons, rings) and on data the drive makes from
the mix and a fixed seed of its own.  A stimulus periodic in the step
counter, or a raster made once and handed to both sides, meets this; a
PRNG stream carried only in the program's state does not.
"""
from __future__ import annotations

import hashlib
import json
import os

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
CACHE = os.path.join(HERE, ".cache")
# the program files that build a deployment's network: a change to one
# of them makes a new cache entry
NETWORK_SOURCES = ("src/repro/snn/microcircuit.py", "src/repro/snn/network.py")


def _load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def manifest(root: str = HERE) -> dict:
    return _load(os.path.join(os.path.dirname(root), "BENCHMARK.json"))


def load_cell(name: str, root: str = HERE) -> dict:
    """Everything one cell needs, by the cell's name."""
    wl = _load(os.path.join(root, "workloads", f"{name}.json"))
    cfg = _load(os.path.join(root, "configs", f"{wl['config']}.json"))
    traffic = _load(os.path.join(root, "traffic", f"{wl['traffic']}.json"))
    return {"name": name, "root": root, "cache": os.path.join(root, ".cache"),
            "workload": wl, "config": cfg, "traffic": traffic}


def cell_metrics(cell: dict, man: dict) -> tuple[list, list]:
    """The end-to-end and per-layer metrics ``BENCHMARK.json`` asks of
    this cell: those that list it, or list no cells and move a metric
    the cell reports."""
    name = cell["name"]
    mine = lambda m: name in m.get("workloads", [name])
    e2e = [m for m in man["end_to_end"] if mine(m)]
    names = {m["name"] for m in e2e}
    layer = [m for m in man["per_layer"]
             if mine(m) and m["moves"] in names]
    return e2e, layer


def _digest(*parts) -> str:
    h = hashlib.sha256()
    for p in parts:
        h.update(p if isinstance(p, bytes) else json.dumps(
            p, sort_keys=True).encode())
    return h.hexdigest()[:16]


def _cached(cache: str, kind: str, key: str, make):
    """``make()`` -> dict of arrays, kept in ``<cache>/<kind>-<key>.npz``."""
    path = os.path.join(cache, f"{kind}-{key}.npz")
    if os.path.exists(path):
        with np.load(path) as z:
            return {k: z[k] for k in z.files}
    arrays = make()
    os.makedirs(cache, exist_ok=True)
    tmp = f"{path}.{os.getpid()}.tmp"
    with open(tmp, "wb") as f:
        np.savez(f, **arrays)
    os.replace(tmp, path)
    return arrays


def _sparse(w: np.ndarray) -> dict:
    flat = np.flatnonzero(w)
    return {"w_idx": flat.astype(np.int64), "w_val": w.reshape(-1)[flat],
            "n": np.int64(w.shape[0])}


def _dense(arrays: dict) -> np.ndarray:
    n = int(arrays["n"])
    w = np.zeros(n * n, np.float32)
    w[arrays["w_idx"]] = arrays["w_val"]
    return w.reshape(n, n)


def partition(cfg: dict, cache: str = CACHE):
    """The program's own partition of the deployment's network (weights,
    fan-out, delays), built by the program once per checkout."""
    from repro.snn import microcircuit as mc, network
    net, fab = cfg["network"], cfg["fabric"]
    srcs = b"".join(open(os.path.join(REPO, p), "rb").read()
                    for p in NETWORK_SOURCES)
    key = _digest(srcs, net, fab["n_shards"])

    def make():
        spec = mc.MicrocircuitSpec(scale=net["scale"], seed=net["seed"])
        w, inh = spec.weight_matrix()
        p = network.build_partition(w, inh, fab["n_shards"],
                                    net["delay_exc_steps"],
                                    net["delay_inh_steps"])
        return {**_sparse(p.weights), "fanout": p.fanout,
                "is_inh": p.is_inh, "delays": p.delays_steps}

    a = _cached(cache, f"net-{cfg['name']}", key, make)
    n = int(a["n"])
    return network.Partition(
        n_shards=fab["n_shards"], n_neurons=n, per_shard=n // fab["n_shards"],
        fanout=a["fanout"], weights=_dense(a), is_inh=a["is_inh"],
        delays_steps=a["delays"])


def reference_weights(cfg: dict, cache: str = CACHE):
    """The reference's own draw of the network: (W, inhibitory flags)."""
    from perf.reference import pd2014
    net = cfg["network"]
    src = open(os.path.join(HERE, "reference", "pd2014.py"), "rb").read()

    def make():
        w, inh = pd2014.weights(net["scale"], net["seed"])
        return {**_sparse(w), "inh": inh}

    a = _cached(cache, f"ref-{cfg['name']}", _digest(src, net), make)
    return _dense(a), a["inh"]


def sim_config(cfg: dict, part):
    """The program's ``SimConfig`` for a deployment."""
    from repro.snn import lif, simulator as sim
    fab = cfg["fabric"]
    torus = {}
    if fab["transport"] in ("torus2d", "torus3d"):
        torus = dict(zip(("torus_nx", "torus_ny", "torus_nz"), fab["torus"]))
    return sim.SimConfig(
        n_shards=fab["n_shards"], per_shard=part.per_shard,
        max_fan=part.fanout.shape[1], window=fab["window"],
        ring_len=fab["ring_len"], e_max=fab["e_max"],
        capacity=fab["capacity"], residue=fab["residue"],
        params=lif.LIFParams(**cfg["lif"]), transport=fab["transport"],
        link_credits=fab["link_credits"],
        notify_latency=fab["notify_latency"],
        wire_format=fab["wire_format"], step_us=cfg["step_us"], **torus)


def plain_state(carry) -> dict:
    """A program carry (leading node axis on every leaf) as plain numpy:
    neurons and rings over the concatenated nodes, keys and step
    counters per node, the events in flight as bucket rows."""
    st, pend = carry.state, carry.pending
    g = lambda a: np.asarray(a)
    ring = lambda r: np.concatenate(list(g(r)), axis=1)     # (L, S*per)
    return {
        "v": g(st.neuron.v).reshape(-1),
        "i_exc": g(st.neuron.i_exc).reshape(-1),
        "i_inh": g(st.neuron.i_inh).reshape(-1),
        "refrac": g(st.neuron.refrac).reshape(-1),
        "ring_exc": ring(st.ring_exc), "ring_inh": ring(st.ring_inh),
        "t": g(st.t), "key": g(st.key),
        "pend_data": g(pend.data), "pend_meta": g(pend.meta),
        "pend_counts": g(pend.counts), "residue": g(pend.residue),
        "parked": int(g(carry.link.parked_count).sum()),
    }


def plain_stats(st) -> dict:
    """Stacked ``WindowStats`` (node, window, ...) as (window, node, ...)."""
    t = lambda a: np.swapaxes(np.asarray(a), 0, 1)
    link = st.link
    return {"spikes": t(st.spikes), "delivered": t(link.delivered_events),
            "miss": t(st.deadline_miss), "overflow": t(st.overflow),
            "hist": t(st.latency.hist), "offered_ev": t(link.offered_events),
            "sent_ev": t(link.sent_events),
            "deferred_ev": t(link.deferred_events),
            "parked_ev": t(link.parked_events),
            "unparked_ev": t(link.unparked_events)}
