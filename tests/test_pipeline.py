"""Scan-pipelined simulator: residue carry-over, conservation, and timing
equivalence of the software-pipelined window loop (1-device mesh, so the
packed collective degenerates but the full carry machinery runs)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.snn import microcircuit as mc, network, simulator as sim


def _build(capacity, residue, n_windows, scale=0.003, seed=0):
    spec = mc.MicrocircuitSpec(scale=scale, seed=seed)
    w, is_inh = spec.weight_matrix()
    part = network.build_partition(w, is_inh, n_shards=1)
    cfg = sim.SimConfig(n_shards=1, per_shard=part.per_shard,
                        max_fan=part.fanout.shape[1], window=8, ring_len=32,
                        e_max=256, capacity=capacity, residue=residue)
    from repro.launch.mesh import make_wafer_mesh
    mesh = make_wafer_mesh(1)
    init, run = sim.build_sharded_sim(mesh, "wafer", cfg, part,
                                      spec.bg_rates())
    st = init(0)
    st, stats = run(st, n_windows)
    return jax.tree_util.tree_map(lambda x: np.asarray(x).ravel(), stats)


def test_pipelined_sim_no_overflow_is_lossless():
    stats = _build(capacity=512, residue=64, n_windows=8)
    assert stats.spikes.sum() > 0, "network is silent"
    assert stats.overflow.sum() == 0
    assert stats.deferred.sum() == 0
    assert stats.deadline_miss.sum() == 0
    # with no deferral every offered event is shipped the same window
    assert (stats.offered == stats.events_sent).all()


def test_pipelined_sim_residue_conservation_under_pressure():
    """Tiny capacity forces the residue path; the WindowStats chain must
    balance exactly: offered_k = sent_k + deferred_k + dropped_k and
    new_k = offered_k - deferred_{k-1} >= 0, summing to
    sum(new) == sum(sent) + sum(dropped) + deferred_last."""
    stats = _build(capacity=8, residue=64, n_windows=12)
    off, sent = stats.offered, stats.events_sent
    defr, drop = stats.deferred, stats.overflow
    assert defr.sum() > 0, "residue carry-over unexercised"
    assert (off == sent + defr + drop).all()
    new = off - np.concatenate([[0], defr[:-1]])
    assert (new >= 0).all()
    assert new.sum() == sent.sum() + drop.sum() + defr[-1]


def test_pipelined_sim_matches_unpipelined_timing():
    """The pipelined scan decodes window k at the same systemtime as the
    seed formulation (start of window k+1 == end of window k), so with
    ample capacity there are no deadline misses and dynamics stay live
    across many windows."""
    stats = _build(capacity=512, residue=64, n_windows=16)
    assert stats.deadline_miss.sum() == 0
    # spikes occur across the run, not only in the first windows (events
    # keep propagating through the pipelined exchange)
    assert stats.spikes[8:].sum() > 0
