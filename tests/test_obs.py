"""Observability stack tests: tracer, metrics, flight-recorder ring,
run-directory report, and the disabled-path pins.

The pins encode the PR's central contract: with the recorder off (the
default) the scan carry pytree and the lowered HLO of both the serving
engine's segment and the simulator's segment are EXACTLY the
uninstrumented program.  This was verified once against the
pre-observability tree (commit f1e89b0) via a git worktree — the
disabled-path ``jax.jit(...).lower(...).as_text()`` dumps were
byte-identical pre/post for both programs; the slow test below keeps the
in-tree halves of that promise honest (disabled arity/HLO stable,
enabled HLO differs).
"""
import json
import os
import threading
from typing import NamedTuple

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from md_helper import run_md
from repro.fabric import faults as fabric_faults
from repro.obs import metrics as obs_metrics
from repro.obs import recorder as obs_recorder
from repro.obs import report as obs_report
from repro.obs import spans as obs_spans

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TESTS_DIR = os.path.dirname(os.path.abspath(__file__))


# -- spans -------------------------------------------------------------------

def test_tracer_span_api():
    tr = obs_spans.Tracer()
    with tr.span("ingest/fill", track="spike-ingest", seg=0) as sp:
        sp.args["events"] = 17
    tr.complete("device/segment", 10.0, 25.0, track="device", win0=4)
    tr.instant("window", track="device", cat="device", window=4)

    def worker():
        with tr.span("device/dispatch", track="spike-device"):
            pass

    t = threading.Thread(target=worker)
    t.start()
    t.join()
    d = tr.to_dict()
    assert obs_spans.validate_trace(d) == []
    names = obs_spans.thread_names(d)
    assert set(names.values()) == {"spike-ingest", "device", "spike-device"}
    evs = {e["name"]: e for e in d["traceEvents"] if e["ph"] != "M"}
    assert evs["ingest/fill"]["args"] == {"seg": 0, "events": 17}
    assert evs["device/segment"]["dur"] == 25.0
    assert evs["window"]["ph"] == "i"


def test_tracer_disabled_still_times():
    tr = obs_spans.Tracer(enabled=False)
    with tr.span("train/step", track="train") as sp:
        x = sum(range(1000))
    assert x and sp.dur_s > 0.0
    assert tr.to_dict()["traceEvents"][1:] == []     # only process_name meta
    # the shared NULL tracer behaves the same and never accumulates
    with obs_spans.NULL.span("x") as sp:
        pass
    assert sp.dur_us >= 0.0


def _profiled_host_events(trace_dir, body):
    """Host events of a ``jax.profiler`` trace of ``body()``."""
    import glob
    from jax.profiler import ProfileData
    jax.profiler.start_trace(str(trace_dir))
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                      recursive=True)
    return [(e.name, e.start_ns, e.duration_ns)
            for p in ProfileData.from_file(path).planes
            if p.name.startswith("/host:CPU") for line in p.lines
            for e in line.events]


def test_enabled_span_lands_in_the_profiler_trace(tmp_path):
    tr = obs_spans.Tracer()
    spans = {}

    def body():
        with tr.span("segment/fetch", seg=3) as sp:
            np.asarray(jnp.ones(8).sum())
        spans["fetch"] = sp
        with obs_spans.NULL.span("segment/hidden"):
            pass

    host = _profiled_host_events(tmp_path, body)
    got = [h for h in host if h[0].startswith("segment/")]
    assert [h[0] for h in got] == ["segment/fetch"]
    # the profiler's span lies inside the tracer's own timing of it
    assert 0 < got[0][2] <= spans["fetch"].dur_us * 1e3
    # the in-memory record is unchanged
    ev, = [e for e in tr.to_dict()["traceEvents"] if e["ph"] == "X"]
    assert ev["name"] == "segment/fetch" and ev["args"] == {"seg": 3}


def test_validate_trace_detects_problems():
    bad = {"traceEvents": [
        {"name": "a", "ph": "X", "ts": 5.0, "dur": -1.0, "pid": 0, "tid": 0},
        {"name": "b", "ph": "X", "ts": 1.0, "dur": 2.0, "pid": 0, "tid": 0},
        {"name": "c", "ph": "i"},
    ]}
    problems = obs_spans.validate_trace(bad)
    assert any("negative dur" in p for p in problems)
    assert any("not monotonic" in p for p in problems)
    assert any("missing ts" in p for p in problems)
    assert obs_spans.validate_trace({}) == ["no traceEvents list"]


# -- metrics -----------------------------------------------------------------

def test_metrics_prometheus_roundtrip():
    reg = obs_metrics.Registry()
    c = reg.counter("fabric_sent_events_total", "Sent.",
                    labels=("backend",))
    c.inc(41, backend="torus3d")
    c.inc(1, backend="torus3d")
    reg.gauge("engine_events_per_s", "Throughput.").set(123.5)
    h = reg.histogram("tenant_latency_us", "Latency.", labels=("tenant",),
                      edges=(1.0, 2.0, 4.0))
    h.add_binned([0, 3, 1], tenant="quiet")
    assert h.percentile(0.5, tenant="quiet") == 2.0
    text = obs_metrics.prometheus_text(reg)
    parsed = obs_metrics.parse_prometheus(text)
    assert parsed["fabric_sent_events_total"][
        frozenset({("backend", "torus3d")})] == 42.0
    assert parsed["engine_events_per_s"][frozenset()] == 123.5
    assert parsed["tenant_latency_us_count"][
        frozenset({("tenant", "quiet")})] == 4.0


def test_metrics_parse_rejects_malformed():
    with pytest.raises(ValueError):
        obs_metrics.parse_prometheus("this is { not exposition\n")
    with pytest.raises(ValueError):
        # samples without a # TYPE declaration
        obs_metrics.parse_prometheus("orphan_metric 1.0\n")


def test_metrics_label_mismatch_raises():
    reg = obs_metrics.Registry()
    c = reg.counter("x_total", "X.", labels=("tenant",))
    with pytest.raises(ValueError):
        c.inc(1)                                     # missing label
    with pytest.raises(ValueError):
        reg.counter("x_total", "X.", labels=())      # redeclared differently


# -- flight-recorder ring ----------------------------------------------------

class _Bank(NamedTuple):
    credits: jax.Array


class _State(NamedTuple):
    bank: _Bank
    parked_by_link: jax.Array


class _Stats(NamedTuple):
    offered_events: int
    sent_events: int
    deferred_events: int
    delivered_events: int
    credit_stalls: int
    parked_events: int
    unparked_events: int
    in_fabric_events: int
    rerouted: int


def _fake_state(k=4):
    return _State(_Bank(jnp.full((k,), 7, jnp.int32)),
                  jnp.zeros((k,), jnp.int32))


def _write(ring, n):
    st = _fake_state()
    for w in range(n):
        ring = obs_recorder.record(
            ring, w, _Stats(*(w * 10 + i for i in range(9))), st,
            jnp.full((3,), w, jnp.int32))
    return ring


def test_ring_records_and_totals():
    ring = obs_recorder.ring_init(8, _fake_state(), (), (3,), 4)
    rows = obs_recorder.ring_rows(_write(ring, 6))
    assert [r["window"] for r in rows] == list(range(6))
    assert rows[0]["overwritten"] == 0
    assert rows[5]["counters"]["rerouted"] == 58
    assert rows[2]["credits"] == [7, 7, 7, 7]
    totals = obs_recorder.counter_totals(rows)
    assert totals["offered_events"] == sum(w * 10 for w in range(6))


def test_ring_wrap_keeps_newest():
    ring = obs_recorder.ring_init(4, _fake_state(), (), (3,), 4)
    rows = obs_recorder.ring_rows(_write(ring, 6))
    # flight-recorder semantics: the most recent `depth` windows survive
    assert [r["window"] for r in rows] == [2, 3, 4, 5]
    assert all(r["overwritten"] == 2 for r in rows)
    with pytest.raises(ValueError, match="wrapped"):
        obs_recorder.counter_totals(rows)


def test_ring_depth_validation():
    with pytest.raises(ValueError):
        obs_recorder.ring_init(0, _fake_state(), (), (3,), 4)


# -- faults -> events --------------------------------------------------------

def test_fault_transitions_and_labels():
    dims = (2, 2, 2)
    sched = fabric_faults.link_fault(dims, 12, 0, 0, start=4, stop=9)
    evs = fabric_faults.transitions(sched)
    downs = [e for e in evs if e["event"] == "link_down"]
    ups = [e for e in evs if e["event"] == "link_up"]
    assert downs and downs[0]["window"] == 4
    assert ups and ups[0]["window"] == 9
    lbl = fabric_faults.link_label(dims, downs[0]["links"][0])
    assert lbl[0] == "n" and lbl[-2] in "xyz" and lbl[-1] in "+-"


# -- run directory + report --------------------------------------------------

def _synthetic_run_dir(tmp_path):
    dims = (2, 1, 1)
    k = int(np.prod(dims)) * 2 * len(dims)
    ring = obs_recorder.ring_init(8, _fake_state(k), (2,), (2, 3), k)
    st = _fake_state(k)
    for w in range(6):
        ring = obs_recorder.record(
            ring, w,
            _Stats(*(jnp.full((2,), w + i, jnp.int32) for i in range(9))),
            st, jnp.full((2, 3), w, jnp.int32))
    sched = fabric_faults.link_fault(dims, 6, 0, 0, start=2, stop=5)
    tenants = [
        {"tenant": "quiet", "reserve": 8, "rate_epw": 10.0,
         "guaranteed_epw": 20.0, "injected": 100, "delivered": 100,
         "shed": 0, "clipped": 0, "p50_us": 2.0, "p99_us": 4.0,
         "max_us": 8.0, "mean_us": 2.5, "hist": [10, 80, 10]},
        {"tenant": "hot", "reserve": 4, "rate_epw": 100.0,
         "guaranteed_epw": 10.0, "injected": 500, "delivered": 420,
         "shed": 80, "clipped": 7, "p50_us": 64.0, "p99_us": 512.0,
         "max_us": 900.0, "mean_us": 120.0, "hist": [1, 200, 219]},
    ]
    reg = obs_metrics.Registry()
    reg.gauge("engine_events_per_s", "T.").set(1000.0)
    return obs_report.write_run_dir(
        str(tmp_path / "run"),
        meta={"kind": "serve", "dims": list(dims), "n_shards": 2,
              "windows": 6, "window_us": 100.0},
        recorder_rows=obs_recorder.ring_rows(ring),
        fault_events=fabric_faults.transitions(sched),
        tenant_rows=tenants, registry=reg)


def test_report_structured_output(tmp_path):
    run_dir = _synthetic_run_dir(tmp_path)
    rep = obs_report.build_report(run_dir)
    # the fault lands on the right timeline row
    by_w = {e["window"]: e for e in rep["timeline"]}
    assert any(ev["event"] == "link_down" for ev in by_w[2]["events"])
    assert any(ev["event"] == "link_up" for ev in by_w[5]["events"])
    assert all(lbl.startswith("n") for ev in by_w[2]["events"]
               for lbl in ev["labels"])
    # rerouted deliveries and per-tenant p99 ride the same rows
    assert by_w[3]["rerouted"] == (3 + 8) * 2      # _Stats field 8, T=2
    assert set(by_w[3]["p99_us"]) == {"quiet", "hot"}
    # tenants gain the SLO burn block
    slo = {t["tenant"]: t["slo"] for t in rep["tenants"]}
    assert slo["quiet"]["overcommit"] == pytest.approx(0.5)
    assert slo["hot"]["overcommit"] == pytest.approx(10.0)
    assert slo["hot"]["delivered_ratio"] == pytest.approx(420 / 500)
    assert rep["totals"]["rerouted"] == sum(
        e["rerouted"] for e in rep["timeline"])
    # and the human rendering mentions all of it
    text = obs_report.render(rep)
    assert "link_down" in text and "quiet" in text and "p99[hot]" in text


def test_report_cli_json(tmp_path, capsys):
    run_dir = _synthetic_run_dir(tmp_path)
    obs_report.main([run_dir, "--json"])
    rep = json.loads(capsys.readouterr().out)
    assert rep["meta"]["kind"] == "serve"
    assert len(rep["timeline"]) == 6
    obs_report.main([run_dir])
    assert "window timeline" in capsys.readouterr().out


def test_report_requires_meta(tmp_path):
    with pytest.raises(FileNotFoundError):
        obs_report.build_report(str(tmp_path))


# -- committed trace artifact ------------------------------------------------

def test_committed_trace_artifact_is_valid():
    """docs/observability_trace.json (written by tools/trace_smoke.py) must
    stay Perfetto-loadable: parses, monotonic per track, one span per
    engine thread, window instants carrying the device window indices the
    flight recorder stamps its rows with."""
    path = os.path.join(ROOT, "docs", "observability_trace.json")
    with open(path) as f:
        trace = json.load(f)
    assert obs_spans.validate_trace(trace) == []
    names = obs_spans.thread_names(trace)
    tracks = {}
    windows = []
    for ev in trace["traceEvents"]:
        if ev.get("ph") in ("X", "i"):
            tracks.setdefault(names.get(ev.get("tid", 0), "?"), 0)
            tracks[names[ev["tid"]]] += 1
            if ev.get("name") == "window":
                windows.append(ev["args"]["window"])
    for track in ("spike-ingest", "spike-device", "device"):
        assert tracks.get(track, 0) >= 1, (track, tracks)
    assert windows == sorted(windows) and len(windows) >= 2


# -- engine integration (1-shard, in-process) --------------------------------

def _make_instrumented_engine(seed=3):
    from repro.serve.loadgen import PoissonLoadGen, TenantProfile
    from repro.serve.spike_engine import EngineConfig, SpikeEngine
    from repro.serve.tenancy import TenantSpec
    from repro.launch.mesh import make_wafer_mesh
    mesh = make_wafer_mesh(1, "w")
    tenants = [TenantSpec("a", reserve=8, rate_epw=10.0),
               TenantSpec("b", reserve=4, rate_epw=30.0)]
    cfg = EngineConfig(capacity=8, link_credits=16, seg_windows=3,
                       nx=1, ny=1, nz=1)
    src = PoissonLoadGen(seed, [TenantProfile("a", 10.0),
                                TenantProfile("b", 30.0)], 1, cfg.capacity)
    return SpikeEngine(mesh, "w", tenants, cfg, src,
                       recorder=obs_recorder.RecorderConfig(depth=32),
                       tracer=obs_spans.Tracer())


@pytest.mark.timeout(300)
def test_engine_recorder_conserves_and_correlates(tmp_path):
    eng = _make_instrumented_engine()
    rep = eng.run(4)
    # ring totals == ledger totals, bit-exact per tenant
    totals = obs_recorder.counter_totals(eng.recorder_rows())
    assert np.array_equal(totals["delivered_events"], rep.delivered)
    assert totals["offered_events"].sum() >= totals["delivered_events"].sum()
    # the trace validates and the host spans carry the device windows
    trace = eng.tracer.to_dict()
    assert obs_spans.validate_trace(trace) == []
    win_in_trace = sorted(ev["args"]["window"]
                          for ev in trace["traceEvents"]
                          if ev.get("name") == "window")
    win_in_ring = [r["window"] for r in eng.recorder_rows()]
    assert set(win_in_trace) <= set(win_in_ring)
    assert len(win_in_trace) == rep.windows + rep.drain_windows
    # the assembled run directory reports the same story
    run_dir = obs_report.write_engine_run(str(tmp_path / "run"), eng, rep)
    built = obs_report.build_report(run_dir)
    assert built["totals"]["delivered_events"] == int(rep.delivered.sum())
    assert {t["tenant"] for t in built["tenants"]} == {"a", "b"}
    assert os.path.exists(os.path.join(run_dir, "trace.json"))
    parsed = obs_metrics.parse_prometheus(
        open(os.path.join(run_dir, "metrics.prom")).read())
    assert parsed["tenant_delivered_events_total"][
        frozenset({("tenant", "a")})] == float(rep.delivered[0])


@pytest.mark.timeout(300)
def test_engine_determinism_unchanged_by_recorder():
    """The instrumented engine serves the EXACT same traffic outcome as an
    uninstrumented one on the same seed — the recorder observes, it never
    perturbs."""
    from repro.serve.loadgen import PoissonLoadGen, TenantProfile
    from repro.serve.spike_engine import EngineConfig, SpikeEngine
    from repro.serve.tenancy import TenantSpec
    from repro.launch.mesh import make_wafer_mesh
    mesh = make_wafer_mesh(1, "w")
    tenants = [TenantSpec("a", reserve=8, rate_epw=10.0),
               TenantSpec("b", reserve=4, rate_epw=30.0)]
    cfg = EngineConfig(capacity=8, link_credits=16, seg_windows=3,
                       nx=1, ny=1, nz=1)

    def run(recorder):
        src = PoissonLoadGen(11, [TenantProfile("a", 10.0),
                                  TenantProfile("b", 30.0)], 1,
                             cfg.capacity)
        return SpikeEngine(mesh, "w", tenants, cfg, src,
                           recorder=recorder).run(3)

    plain = run(None)
    rec = run(obs_recorder.RecorderConfig(depth=32))
    assert np.array_equal(plain.injected, rec.injected)
    assert np.array_equal(plain.delivered, rec.delivered)
    assert np.array_equal(plain.shed, rec.shed)
    for d1, d2 in zip(plain.tenants, rec.tenants):
        assert np.array_equal(d1.hist, d2.hist)


# -- old batched engine span smoke -------------------------------------------

@pytest.mark.timeout(300)
def test_old_engine_emits_serve_spans():
    from repro.configs import get_config, reduced
    from repro.models import build
    from repro.serve.engine import Engine, Request, ServeConfig
    cfg = reduced(get_config("qwen15_4b"))
    m = build(cfg)
    params = m.init(jax.random.PRNGKey(0))
    tr = obs_spans.Tracer()
    eng = Engine(m, ServeConfig(slots=2, max_len=64, max_new_tokens=4),
                 tracer=tr)
    out = eng.generate_batch(params, [
        Request(rid=0, prompt=np.array([5, 6, 7], np.int32)),
        Request(rid=1, prompt=np.array([9, 10], np.int32))])
    assert set(out) == {0, 1}
    d = tr.to_dict()
    assert obs_spans.validate_trace(d) == []
    names = [e["name"] for e in d["traceEvents"] if e["ph"] == "X"]
    assert "serve/prefill" in names and "serve/decode" in names
    dec = [e for e in d["traceEvents"] if e["name"] == "serve/decode"]
    assert all(e["args"]["tokens"] >= 0 for e in dec)


# -- disabled-path pins (subprocess: needs >1 device) ------------------------

def test_sim_carry_structure_disabled():
    from repro.snn.simulator import SimCarry
    # trailing ring=None is leafless: the disabled carry IS the 3-tuple
    assert (jax.tree_util.tree_structure(SimCarry(1, 2, 3))
            == jax.tree_util.tree_structure(SimCarry(1, 2, 3, None)))
    assert jax.tree_util.tree_leaves(SimCarry(1, 2, 3)) == [1, 2, 3]


@pytest.mark.slow
def test_disabled_path_hlo_pinned():
    out = run_md(r"""
import numpy as np, jax
from repro.obs import recorder as obs_recorder
from repro.serve.loadgen import PoissonLoadGen, TenantProfile
from repro.serve.spike_engine import EngineConfig, SpikeEngine
from repro.serve.tenancy import TenantSpec

from repro.launch.mesh import make_wafer_mesh
mesh = make_wafer_mesh(4, "w")
cfg = EngineConfig(capacity=8, link_credits=16, notify_latency=2,
                   window_us=100.0, seg_windows=3, nx=2, ny=2, nz=1)
tenants = [TenantSpec("a", reserve=8, rate_epw=16.0),
           TenantSpec("b", reserve=4, rate_epw=8.0)]

def build(recorder):
    src = PoissonLoadGen(3, [TenantProfile("a", 16.0),
                             TenantProfile("b", 8.0)], 4, cfg.capacity)
    return SpikeEngine(mesh, "w", tenants, cfg, src, recorder=recorder)

def hlo(eng):
    return eng._seg.lower(*eng._carry, eng._zero_fw, eng._zero_fc,
                          0).as_text()

off1, off2 = build(None), build(None)
assert len(off1._carry) == 4, "disabled carry grew"
txt1, txt2 = hlo(off1), hlo(off2)
assert txt1 == txt2, "disabled lowering is not deterministic"
assert "telemetry" not in txt1.lower()

on = build(obs_recorder.RecorderConfig(depth=16))
assert len(on._carry) == 5, "enabled carry must add exactly the ring"
assert hlo(on) != txt1, "recorder ring was DCE'd out of the program"
print("HLO_PIN_OK", len(txt1))
""", n_devices=4)
    assert "HLO_PIN_OK" in out


@pytest.mark.slow
def test_recorder_conservation_all_backends():
    """Ring counter totals must be bit-identical to the end-of-run
    ``LinkStats`` on every transport backend, and the instrumented run's
    stats must equal the uninstrumented run's (observer effect = 0)."""
    out = run_md(r"""
import jax, numpy as np
from repro import obs
from repro.snn import microcircuit as mc, network, simulator as sim

spec = mc.MicrocircuitSpec(scale=0.003)
w, is_inh = spec.weight_matrix()
part = network.build_partition(w, is_inh, n_shards=8)
from repro.launch.mesh import make_wafer_mesh
mesh = make_wafer_mesh(8)
N_WIN = 6
for transport in ("alltoall", "torus2d", "torus3d"):
    kw = {}
    if transport != "alltoall":
        kw = dict(torus_nx=2, torus_ny=4 if transport == "torus2d" else 2,
                  link_credits=32, notify_latency=2)
        if transport == "torus3d":
            kw.update(torus_ny=2, torus_nz=2)
    cfg = sim.SimConfig(n_shards=8, per_shard=part.per_shard,
                        max_fan=part.fanout.shape[1], window=8,
                        ring_len=32, e_max=512, capacity=32,
                        transport=transport, **kw)
    args = (mesh, "wafer", cfg, part, spec.bg_rates())
    init_p, run_p = sim.build_sharded_sim(*args)
    st_p, stats_p = run_p(init_p(0), N_WIN)
    init_r, run_r = sim.build_sharded_sim(
        *args, recorder=obs.RecorderConfig(depth=16))
    st_r, stats_r, ring = run_r(init_r(0), N_WIN)
    sp = jax.tree_util.tree_map(np.asarray, stats_p)
    sr = jax.tree_util.tree_map(np.asarray, stats_r)
    # zero observer effect: instrumented == uninstrumented, bit-exact
    for f in obs.COUNTER_FIELDS:
        assert (getattr(sp.link, f) == getattr(sr.link, f)).all(), \
            (transport, f)
    assert (np.asarray(st_p.neuron.v) == np.asarray(st_r.neuron.v)).all()
    # per-shard ring totals == per-shard LinkStats totals, bit-exact
    for s in range(8):
        tot = obs.counter_totals(
            obs.ring_rows(obs.ring_shard(ring, s)))
        for f in obs.COUNTER_FIELDS:
            want = int(getattr(sr.link, f)[s].sum())
            assert int(tot[f]) == want, (transport, s, f)
    # stall attribution sums to the global deferred total (torus+credits)
    rows = obs.global_rows(ring, 8)
    sbl = sum(int(np.asarray(r["stalled_by_link"]).sum()) for r in rows)
    defr = int(sr.link.deferred_events.sum())
    if transport != "alltoall":
        assert sbl == defr, (transport, sbl, defr)
    print(transport, "OK", defr)
print("CONSERVATION_OK")
""")
    assert "CONSERVATION_OK" in out
