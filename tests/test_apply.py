"""The event apply on source-major weights equals the column form it
replaced: the same delay ring and the same deadline misses."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.core import events as ev
from repro.snn import lif, simulator as sim


def _apply_columns(state, words, counts, w_cols_exc, w_cols_inh, cfg,
                   src_shard):
    """The apply as it was: weight columns gathered from target-major
    ``(per, n_total)`` operands."""
    S, C = words.shape
    live = jnp.arange(C)[None, :] < counts[:, None]
    addr = ev.address(words).astype(jnp.int32)
    ts = ev.timestamp(words).astype(jnp.int32)
    src_global = (src_shard[:, None] * cfg.per_shard
                  + addr // cfg.max_fan)
    slack = ev.ts_slack(ts, state.t & ev.TS_MASK)
    miss = jnp.sum(jnp.where(live & (slack < 0), 1, 0))
    slot = (state.t + jnp.maximum(slack, 0)) % cfg.ring_len
    flat_live = live.reshape(-1)
    flat_src = jnp.where(flat_live, src_global.reshape(-1), 0)
    exc_cols = w_cols_exc[:, flat_src] * flat_live[None, :]
    inh_cols = w_cols_inh[:, flat_src] * flat_live[None, :]
    onehot = jax.nn.one_hot(slot.reshape(-1), cfg.ring_len,
                            dtype=exc_cols.dtype)
    ring_exc = state.ring_exc + jnp.einsum("el,pe->lp", onehot, exc_cols)
    ring_inh = state.ring_inh + jnp.einsum("el,pe->lp", onehot, inh_cols)
    return state._replace(ring_exc=ring_exc, ring_inh=ring_inh), miss


def test_source_major_apply_equals_column_form():
    S, per, fan, C, ring_len = 3, 20, 2, 12, 16
    n = S * per
    cfg = sim.SimConfig(n_shards=S, per_shard=per, max_fan=fan,
                        ring_len=ring_len, capacity=C)
    rng = np.random.default_rng(7)
    is_inh = rng.random(n) < 0.25
    w_local = (rng.normal(87.8, 9.0, (S, per, n))
               * np.where(is_inh, -4.0, 1.0)).astype(np.float32)
    w_local *= rng.random((S, per, n)) < 0.3              # sparse synapses
    me = 1                                                 # this shard

    t = 29                  # ring slot 13 of 16: a slack past 2 wraps
    src = rng.integers(0, per, (S, C))
    slack = rng.integers(-3, 11, (S, C))
    slack[0, 0], slack[1, 1] = -2, 9                       # a miss, a wrap
    words = ev.pack(src * fan + rng.integers(0, fan, (S, C)),
                    (t + slack) & ev.TS_MASK)
    counts = np.array([C, 5, 0], np.int32)   # dead slots are valid words
    key = jax.random.PRNGKey(0)
    ring = jnp.asarray(rng.normal(0.0, 5.0, (ring_len, per)), jnp.float32)
    state = sim.ShardState(lif.init_state(per, cfg.params, key), ring,
                           -ring, jnp.int32(t), key)
    src_shard = jnp.arange(S)

    cols = [np.where(keep[None, :], w_local[me], 0.0) for keep in
            (~is_inh, is_inh)]
    rows = [sim._source_major(w_local, keep, block=7)[me] for keep in
            (~is_inh, is_inh)]
    assert rows[0].shape == (n, sim.weight_width(per))
    for r, c in zip(rows, cols):
        np.testing.assert_array_equal(r[:, :per], c.T)
        assert not r[:, per:].any()

    with jax.default_matmul_precision("highest"):
        got, miss = sim._apply_events(state, words, jnp.asarray(counts),
                                      *map(jnp.asarray, rows), cfg,
                                      src_shard)
        want, want_miss = _apply_columns(state, words, jnp.asarray(counts),
                                         *map(jnp.asarray, cols), cfg,
                                         src_shard)
    live = np.arange(C)[None, :] < counts[:, None]
    assert (live & (slack < 0)).any() and (live & (slack > 2)).any()
    assert int(miss) == int(want_miss) == int((live & (slack < 0)).sum())
    for a, b in ((got.ring_exc, want.ring_exc),
                 (got.ring_inh, want.ring_inh)):
        assert not np.array_equal(b, ring) and not np.array_equal(b, -ring)
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6)
