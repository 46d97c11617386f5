"""Shared congestion-study harness for the benchmark subprocess scripts.

``STUDY_SNIPPET`` is spliced into the ``-c`` SCRIPT strings of
``bench_transport.py`` and ``bench_wire.py`` (both subprocesses define
``mesh``, ``words``, ``stacked``, ``n_shards``, ``C`` and ``params``
before it runs).  It builds ``make_study(backend, opts)`` — a jitted
shard_map whose ``lax.scan`` threads the transport's ``FabricState``
across ``N_WIN`` sustained windows of the same offered load, so parked
rows resume mid-route and the congestion terms of the latency model are
actually measured.  Keeping the harness in one place means the two BENCH
files can never diverge on the study methodology.

``make_study(..., recorder_depth=D)`` additionally threads a
``repro.obs.recorder`` telemetry ring through the scan (transport built
with ``stall_attribution=True``) and returns it as a third output — the
flight-recorder overhead row of ``BENCH_transport.json`` times exactly
this against the uninstrumented study.
"""

STUDY_SNIPPET = r'''
from jax.sharding import PartitionSpec as _StudyP
from repro import transport as _study_tp
from repro import wire as _study_wire
from repro.core.exchange import exchange_window as _study_xw
from repro.core.routing import RoutingTables as _StudyRT
from repro.obs import recorder as _study_rec

N_WIN = params["windows"]

def make_study(backend, opts, recorder_depth=None):
    """Jitted multi-window exchange scan -> (LinkStats, LatencySummary)
    stacked (n_shards, N_WIN, ...); stats summed over windows by callers.
    With recorder_depth set, the flight-recorder ring rides the carry and
    is returned third (stall attribution on)."""
    kw = dict(opts)
    if recorder_depth is not None:
        kw["stall_attribution"] = True
    tb = _study_tp.create(backend, n_shards=n_shards, max_row_events=C,
                          **kw)
    def body(w, d, g, m):
        tables = _StudyRT(d[0], g[0], m[0])
        if recorder_depth is None:
            def win(lstate, _):
                out = _study_xw(w[0], tables, axis_name="wafer",
                                n_shards=n_shards, capacity=C,
                                transport=tb, link_state=lstate)
                return out.link_state, (out.link, out.latency)
            _, stats = jax.lax.scan(win, tb.init_state(2 * C), None,
                                    length=N_WIN)
            return jax.tree_util.tree_map(lambda x: x[None], stats)
        def win(carry, i):
            lstate, ring = carry
            out = _study_xw(w[0], tables, axis_name="wafer",
                            n_shards=n_shards, capacity=C,
                            transport=tb, link_state=lstate)
            ring = _study_rec.record(ring, i, out.link, out.link_state,
                                     out.latency.hist)
            return (out.link_state, ring), (out.link, out.latency)
        lstate0 = tb.init_state(2 * C)
        ring0 = _study_rec.ring_init(
            recorder_depth, lstate0, (),
            (_study_wire.N_LATENCY_BINS,), lstate0.bank.credits.shape[0])
        (_, ring), stats = jax.lax.scan(win, (lstate0, ring0),
                                        jnp.arange(N_WIN))
        lift = lambda t: jax.tree_util.tree_map(lambda x: x[None], t)
        return lift(stats) + (lift(ring),)
    spec = _StudyP("wafer")
    n_out = 2 if recorder_depth is None else 3
    fn = jax.shard_map(body, mesh=mesh, in_specs=(spec,) * 4,
                       out_specs=(spec,) * n_out if n_out == 3 else spec,
                       check_vma=False)
    return jax.jit(lambda: fn(words, stacked.dest_of_addr,
                              stacked.guid_of_addr, stacked.mcast_of_guid))
'''
