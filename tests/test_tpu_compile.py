"""Compile the main path's Pallas kernels for a described TPU v5e chip.

Nothing runs: each test lowers and compiles at the real widths of
``chip_smoke.py``'s configurations and checks that the kernels survived
as ``tpu_custom_call``s.  This is what the chip's compiler would refuse
(unaligned vector loads, block shapes that do not match the XLA layout)
and what interpret mode on the CPU cannot see.

The topology is described inside a fixture, never at import: one process
at a time may load the TPU library.
"""
import re

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import (NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro.core import events as ev
from repro.core.routing import RoutingTables
from repro.kernels import dispatch, fused_route_bucket as frb
from repro.launch.mesh import make_wafer_mesh
from repro.snn import lif, microcircuit as mc, simulator as sim
from repro import wire

RESIDUE = 256


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described chip's programs cannot be read back from the cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _hlo(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


# (n_dest, capacity, window events): the 1-shard smoke configuration,
# a 4-shard window of C=512 rows (S*C deferred + residue + 512 spikes x
# 4 replicas), and the 4-shard smoke configuration
@pytest.mark.parametrize("n_dest,capacity,n", [
    (1, 4096, RESIDUE + 4096),
    (4, 512, 4 * 512 + RESIDUE + 512 * 4),
    (4, 1024, 4 * 1024 + RESIDUE + 1024 * 4),
])
def test_placement_kernel_compiles(one_chip, n_dest, capacity, n):
    arg = lambda dt: jax.ShapeDtypeStruct((n,), dt, sharding=one_chip)
    txt = _hlo(lambda w, d, m: frb.fused_aggregate(
        w, d, m, n_dest, capacity, residue_len=RESIDUE,
        with_residue_meta=True, use_pallas=True, interpret=False),
        arg(jnp.uint32), arg(jnp.int32), arg(jnp.int32))
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("n_dest,capacity", [(1, 4096), (4, 512), (4, 1024)])
def test_codec_kernels_compile(one_chip, n_dest, capacity):
    arg = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,
                                                 sharding=one_chip)
    enc = _hlo(lambda e, m: wire.encode_planar(e, m, use_pallas=True,
                                               interpret=False),
               arg((n_dest, capacity), jnp.uint32),
               arg((n_dest, capacity), jnp.int32))
    dec = _hlo(lambda b: wire.decode_planar(b, use_pallas=True,
                                            interpret=False),
               arg((n_dest, 2 * capacity), jnp.uint32))
    assert "tpu_custom_call" in enc and "tpu_custom_call" in dec


# the smoke sizes: the microcircuit at scale 0.21 on one ``wafer`` device,
# and on four; the four-node torus is uncredited, so its stall histograms
# fold to constants: the TPU compiler's scatter emitter aborts on such
# scatter-adds
WINDOW_BODIES = pytest.mark.parametrize("n_shards,per,fan,transport", [
    (1, 16202, 1, dict(transport="alltoall")),
    (4, 4051, 4, dict(transport="torus3d", torus_nx=1, torus_ny=2,
                      torus_nz=2)),
])
SEGMENT_WINDOWS = 8


@pytest.fixture(scope="module")
def segments():
    """Compiled segment texts of this module, by size."""
    return {}


def _segment_hlo(segments, topo, monkeypatch, n_shards, per, fan,
                 transport) -> str:
    """The compiled text of the simulator's window body (exchange +
    decode, LIF steps, spike compaction, route + placement) scanned over
    a segment's windows, as ``build_sharded_segments`` runs it; the
    weights source-major, ``(n, weight_width(per))`` a shard, stacked
    along the rows as the program places them.  Compiled once a size."""
    key = (n_shards, per)
    if key in segments:
        return segments[key]
    # the body picks its kernels from the backend it sees; here that is
    # the CPU, so point it at the chip it is compiled for
    monkeypatch.delenv("REPRO_PALLAS_INTERPRET", raising=False)
    monkeypatch.setattr(dispatch, "on_tpu", lambda: True)
    n = per * n_shards
    assert n >= mc.MicrocircuitSpec(scale=0.21).n_neurons      # 16,202
    e_max = 4096 // n_shards
    cfg = sim.SimConfig(n_shards=n_shards, per_shard=per, max_fan=fan,
                        window=8, ring_len=32, e_max=e_max, capacity=e_max,
                        residue=RESIDUE, **transport)
    init_pending, init_link, body, _, _ = sim.make_pipeline_fns(
        cfg, axis_name="wafer")

    def carry0():
        key = jax.random.PRNGKey(0)
        ring = jnp.zeros((cfg.ring_len, per), jnp.float32)
        state = sim.ShardState(lif.init_state(per, cfg.params, key), ring,
                               ring, jnp.int32(0), key)
        return state, init_pending(), init_link()

    mesh = make_wafer_mesh(n_shards, devices=topo.devices[:n_shards])
    shard = NamedSharding(mesh, P("wafer"))
    stacked = lambda shape, dt: jax.ShapeDtypeStruct(
        (n_shards,) + shape, dt, sharding=shard)
    carry = jax.tree.map(lambda a: stacked(a.shape, a.dtype),
                         jax.eval_shape(carry0))
    tables = RoutingTables(stacked((per * fan,), jnp.int32),
                           stacked((per * fan,), jnp.int32),
                           stacked((per * fan,), jnp.uint32))
    w = jax.ShapeDtypeStruct((n_shards * n, sim.weight_width(per)),
                             jnp.float32, sharding=shard)

    def segment(c, t, we, wi, dl, bg):
        mine = lambda tree: jax.tree.map(lambda a: a[0], tree)
        tabs = mine(t)
        win = lambda c, _: body(c, tabs, we, wi, dl[0], bg[0], 87.8)
        out = jax.lax.scan(win, mine(c), None, length=SEGMENT_WINDOWS)
        return jax.tree.map(lambda a: a[None], out)

    fn = jax.shard_map(segment, mesh=mesh, in_specs=P("wafer"),
                       out_specs=P("wafer"), check_vma=False)
    segments[key] = _hlo(fn, carry, tables, w, w,
                         stacked((per,), jnp.int32),
                         stacked((per,), jnp.float32))
    return segments[key]


@WINDOW_BODIES
def test_window_body_compiles(segments, topo, monkeypatch, n_shards, per,
                              fan, transport):
    """The simulator's window body compiles for the chip at the smoke
    sizes, its Pallas kernels kept."""
    txt = _segment_hlo(segments, topo, monkeypatch, n_shards, per, fan,
                       transport)
    assert txt.count("tpu_custom_call") >= 3     # placement, encode, decode
    assert ev.ADDR_MASK + 1 >= per * fan         # no address aliasing


@WINDOW_BODIES
def test_segment_keeps_weight_layout(segments, topo, monkeypatch, n_shards,
                                     per, fan, transport):
    """No copy, transpose or convert in the compiled segment makes an
    array of a weight operand's size, in any order, layout or dtype: the
    apply gathers rows of the source-major weights as they are stored,
    instead of re-laying out the whole matrix once per segment call.
    And the two gathers (excitatory, inhibitory) stay gathers of whole
    rows, of the S * C = 4096 event slots of a window, not loops of
    one-row slices."""
    txt = _segment_hlo(segments, topo, monkeypatch, n_shards, per, fan,
                       transport)
    n = per * n_shards
    weight = [sorted((n, per)), sorted((n, sim.weight_width(per)))]
    relayouts = [
        line.strip() for line in txt.splitlines()
        if (m := re.search(r"= \w+\[([\d,]+)\]\S* (copy|transpose|convert)\(",
                           line))
        and sorted(d for d in map(int, m.group(1).split(",")) if d > 1)
        in weight]
    assert re.search(r"= f32\[[\d,]+\]\S* (copy|transpose)\(", txt)
    assert relayouts == []
    rows = rf"= f32\[4096,{sim.weight_width(per)}\]\S* gather\("
    assert len(re.findall(rows, txt)) == 2
