"""Multi-device integration tests (subprocess with forced host devices):
spike exchange, sharded microcircuit simulation, bucket-MoE vs local-MoE,
int8 error-feedback all-reduce, and a small-mesh dry-run of one cell.
"""
import pytest

from md_helper import run_md

pytestmark = pytest.mark.slow


def test_exchange_conservation_and_routing():
    out = run_md("""
import jax, jax.numpy as jnp, numpy as np
from repro.core import events as ev, routing as rt
from repro.core.exchange import make_exchange
n_shards, N, C, n_addr = 8, 32, 16, 64
from repro.launch.mesh import make_wafer_mesh
mesh = make_wafer_mesh(n_shards)
tabs = []
for s in range(n_shards):
    projs = [rt.Projection(a, a+1, dest_node=a % n_shards, dest_links=[a % 3, 7])
             for a in range(n_addr)]
    tabs.append(rt.build_tables(n_addr, projs, n_guid=64))
stacked = rt.RoutingTables(
    dest_of_addr=jnp.stack([t.dest_of_addr for t in tabs]),
    guid_of_addr=jnp.stack([t.guid_of_addr for t in tabs]),
    mcast_of_guid=jnp.stack([t.mcast_of_guid for t in tabs]))
key = jax.random.PRNGKey(0)
addr = jax.random.randint(key, (n_shards, N), 0, n_addr)
ts = jax.random.randint(jax.random.PRNGKey(1), (n_shards, N), 0, 1000)
words = ev.pack(addr, ts)
run = make_exchange(mesh, "wafer", n_shards=n_shards, capacity=C,
                    n_addr_per_shard=n_addr)
out = run(words, stacked)
tot_sent = int(out.sent_counts.sum()); tot_recv = int(out.recv_counts.sum())
assert tot_sent == tot_recv
assert tot_sent + int(out.overflow.sum()) == n_shards * N
re = np.asarray(out.recv_events).reshape(n_shards, n_shards, C)
for s in range(n_shards):
    e = re[s][(re[s] & (1 << 29)) != 0]
    a = (e >> 15) & 0x3FFF
    assert ((a % n_shards) == s).all()
print("EXCHANGE_OK")
""")
    assert "EXCHANGE_OK" in out


def test_sharded_microcircuit_simulation():
    out = run_md("""
import jax, numpy as np
from repro.snn import microcircuit as mc, network, simulator as sim
spec = mc.MicrocircuitSpec(scale=0.003)
w, is_inh = spec.weight_matrix()
part = network.build_partition(w, is_inh, n_shards=4)
cfg = sim.SimConfig(n_shards=4, per_shard=part.per_shard,
                    max_fan=part.fanout.shape[1], window=8, ring_len=32,
                    e_max=256, capacity=512)
from repro.launch.mesh import make_wafer_mesh
mesh = make_wafer_mesh(4)
init, run = sim.build_sharded_sim(mesh, "wafer", cfg, part, spec.bg_rates())
st = init(0)
st, stats = run(st, 8)
spikes = int(np.asarray(stats.spikes).sum())
assert spikes > 0, "network is silent"
assert int(np.asarray(stats.overflow).sum()) == 0
assert int(np.asarray(stats.deadline_miss).sum()) == 0
print("SIM_OK", spikes)
""", n_devices=4)
    assert "SIM_OK" in out


def test_exchange_single_collective_hlo():
    """The packed exchange must lower to EXACTLY one all-to-all per flush
    window (the tentpole: data+guids+counts travel in a single buffer)."""
    out = run_md("""
import jax, jax.numpy as jnp
from repro.core import events as ev, routing as rt
from repro.core.exchange import make_exchange
n_shards, N, C, n_addr = 8, 32, 16, 64
from repro.launch.mesh import make_wafer_mesh
mesh = make_wafer_mesh(n_shards)
tabs = []
for s in range(n_shards):
    projs = [rt.Projection(a, a+1, dest_node=a % n_shards, dest_links=[a % 3])
             for a in range(n_addr)]
    tabs.append(rt.build_tables(n_addr, projs, n_guid=64))
stacked = rt.RoutingTables(
    dest_of_addr=jnp.stack([t.dest_of_addr for t in tabs]),
    guid_of_addr=jnp.stack([t.guid_of_addr for t in tabs]),
    mcast_of_guid=jnp.stack([t.mcast_of_guid for t in tabs]))
words = ev.pack(jnp.zeros((n_shards, N), jnp.int32),
                jnp.zeros((n_shards, N), jnp.int32))
run = make_exchange(mesh, "wafer", n_shards=n_shards, capacity=C,
                    n_addr_per_shard=n_addr)
txt = jax.jit(run).lower(words, stacked).as_text()
n_a2a = txt.count("all_to_all") + txt.count("all-to-all")
print("A2A_COUNT", n_a2a)
assert n_a2a == 1, txt.count("all_to_all")
print("SINGLE_COLLECTIVE_OK")
""")
    assert "SINGLE_COLLECTIVE_OK" in out


def test_moe_bucket_equals_local():
    """shard_map EP dispatch must reproduce the single-device result."""
    out = run_md("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.configs.base import MoEConfig
from repro.models import moe as M
from repro.launch.mesh import make_mesh
mesh = make_mesh((2, 4), ("data", "model"))
moe = MoEConfig(n_experts=8, top_k=2, expert_ff=16, capacity_factor=8.0)
d, T = 12, 32
key = jax.random.PRNGKey(0)
params = {
  "router": 0.3 * jax.random.normal(key, (d, 8)),
  "w_gate": jax.random.normal(jax.random.fold_in(key,1), (8, d, 16)) / np.sqrt(d),
  "w_up": jax.random.normal(jax.random.fold_in(key,2), (8, d, 16)) / np.sqrt(d),
  "w_down": jax.random.normal(jax.random.fold_in(key,3), (8, 16, d)) / 4.0,
}
x = jax.random.normal(jax.random.fold_in(key, 4), (T, d))
y_ref, stats_ref = M.moe_layer_local(x, params, moe, capacity=64)

def body(xl, router, wg, wu, wd):
    y, stats = M.moe_layer_bucket(
        xl.reshape(-1, d), {"router": router, "w_gate": wg, "w_up": wu,
                            "w_down": wd}, moe, axis="model", capacity=64)
    return y.reshape(xl.shape)

fn = jax.shard_map(body, mesh=mesh,
                   in_specs=(P("data", None), P(), P("model", None, None),
                             P("model", None, None), P("model", None, None)),
                   out_specs=P("data", None), check_vma=False)
y2 = fn(x.reshape(2, T // 2, d).reshape(T, d),
        params["router"], params["w_gate"], params["w_up"], params["w_down"])
np.testing.assert_allclose(np.asarray(y_ref), np.asarray(y2), rtol=2e-4, atol=2e-4)
print("MOE_OK")
""")
    assert "MOE_OK" in out


def test_compressed_allreduce():
    out = run_md("""
import jax, jax.numpy as jnp, numpy as np
from repro.distributed.compression import make_compressed_allreduce, init_error_feedback
from repro.launch.mesh import make_mesh
mesh = make_mesh((4,), ("pod",))
ar = make_compressed_allreduce(mesh, ("pod",))
g = {"w": jnp.asarray(np.random.default_rng(0).normal(size=(64, 32)), jnp.float32)}
e = init_error_feedback(g)
# replicated input -> mean over identical copies should ~= input
got, e2 = jax.jit(ar)(g, e)
err = np.abs(np.asarray(got["w"]) - np.asarray(g["w"])).max()
scale = np.abs(np.asarray(g["w"])).max()
assert err <= scale / 127.0 * 1.5 + 1e-6, err
# error feedback captures the residual
assert np.abs(np.asarray(e2["w"])).max() <= scale / 127.0 + 1e-6
print("COMPRESS_OK", float(err))
""", n_devices=4)
    assert "COMPRESS_OK" in out


def test_small_mesh_dryrun_cell():
    """Tiny-mesh version of the production dry-run machinery end-to-end."""
    out = run_md("""
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_config, SHAPES, reduced
from repro.configs.base import ShapeConfig
from repro.distributed import sharding as shd
from repro.launch import dryrun as dr
from repro.launch.mesh import make_mesh
mesh = make_mesh((2, 4), ("data", "model"))
cfg = get_config("qwen3_32b")
import dataclasses
cfg = dataclasses.replace(cfg, n_layers=2)          # keep compile small
shape = ShapeConfig("train_small", 512, 8, "train")
fn, args, shardings, model = dr.build_train_cell(cfg, shape, mesh)
with mesh:
    compiled = jax.jit(fn, in_shardings=shardings).lower(*args).compile()
cost = compiled.cost_analysis()
if isinstance(cost, list):       # older jax returns one dict per computation
    cost = cost[0]
assert compiled.memory_analysis() is not None
print("DRYRUN_OK", int(cost.get("flops", 0)) > 0)
""", n_devices=8, timeout=900)
    assert "DRYRUN_OK" in out


def test_split_kv_decode_attention():
    out = run_md("""
import jax, jax.numpy as jnp, numpy as np
from functools import partial
from jax.sharding import PartitionSpec as P
from repro.distributed.collectives import split_kv_decode_attention
from repro.launch.mesh import make_mesh
mesh = make_mesh((4,), ("model",))
B, T, Hq, Hkv, D = 2, 64, 8, 2, 16
k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
q = jax.random.normal(k1, (B, 1, Hq, D))
k = jax.random.normal(k2, (B, T, Hkv, D))
v = jax.random.normal(k3, (B, T, Hkv, D))
clen = jnp.asarray(50)
fn = jax.shard_map(
    partial(split_kv_decode_attention, axis_name="model"),
    mesh=mesh,
    in_specs=(P(), P(None, "model", None, None), P(None, "model", None, None), P()),
    out_specs=P(), check_vma=False)
o1 = fn(q, k, v, clen)
# reference: full attention over valid prefix
kk = jnp.repeat(k, Hq // Hkv, 2); vv = jnp.repeat(v, Hq // Hkv, 2)
s = jnp.einsum("bqhd,bkhd->bhqk", q, kk) / np.sqrt(D)
s = jnp.where((jnp.arange(T) < 50)[None, None, None], s, -1e30)
p = jax.nn.softmax(s, -1)
o2 = jnp.einsum("bhqk,bkhd->bqhd", p, vv)
np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), rtol=2e-4, atol=2e-4)
print("SPLITKV_OK")
""", n_devices=4)
    assert "SPLITKV_OK" in out
