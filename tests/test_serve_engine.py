"""In-process serve-engine tests (1-shard mesh — no subprocess, no forced
device count) covering the host-side machinery the fabric fuzz can't see:
ingestion/device thread handoff, staging-slot reuse, graceful drain, the
per-tenant conservation ledger and run-to-run determinism.

Every test here starts the engine's threads, so every test carries the
hard ``timeout`` marker (see ``conftest.py``): a queue deadlock must kill
the run with tracebacks, not hang CI.
"""
import numpy as np
import pytest

from repro.launch.mesh import make_wafer_mesh
from repro.serve.loadgen import PoissonLoadGen, TenantProfile, WindowTraffic
from repro.serve.spike_engine import EngineConfig, SpikeEngine
from repro.serve.tenancy import TenantLedger, TenantSpec


def make_engine(seed=3, rate_b=30.0, segments_cfg=None, **cfg_kw):
    mesh = make_wafer_mesh(1, "w")
    tenants = [TenantSpec("a", reserve=8, rate_epw=10.0),
               TenantSpec("b", reserve=4, rate_epw=rate_b)]
    kw = dict(capacity=8, link_credits=16, seg_windows=3, nx=1, ny=1, nz=1)
    kw.update(cfg_kw)
    cfg = EngineConfig(**kw)
    src = PoissonLoadGen(seed, [TenantProfile("a", 10.0),
                                TenantProfile("b", rate_b,
                                              burst_factor=2.0,
                                              burst_prob=0.3)],
                         1, cfg.capacity)
    return SpikeEngine(mesh, "w", tenants, cfg, src)


@pytest.mark.timeout(300)
def test_engine_conserves_every_tenant():
    eng = make_engine()
    rep = eng.run(5)
    assert rep.conservation_checked
    assert np.all(rep.injected == rep.delivered + rep.shed)
    assert rep.delivered.sum() > 0
    assert rep.windows == 5 * 3
    # post-drain the engine holds nothing back
    assert eng.backlog_events() == 0
    assert eng.in_fabric_events() == 0


@pytest.mark.timeout(300)
def test_engine_overload_is_counted_not_hidden():
    # rate far beyond row capacity: on a 1-shard fabric every row is
    # local (local rows never defer, so engine-side shed needs the
    # multi-shard QoS test in test_fabric_fuzz.py), but the generator
    # must report its over-capacity clipping and the ledger must still
    # balance exactly
    # capacity 32 >> the quiet tenant's single-row Poisson(10) tail, so
    # only the hot tenant clips
    eng = make_engine(rate_b=500.0, capacity=32, link_credits=40)
    rep = eng.run(4)
    assert rep.clipped[1] > 0
    assert np.all(rep.injected == rep.delivered + rep.shed)
    # the quiet tenant is not the one overloading
    assert rep.clipped[0] == 0 and rep.shed[0] == 0


@pytest.mark.timeout(300)
def test_engine_deterministic_across_runs():
    r1 = make_engine(seed=11).run(4)
    r2 = make_engine(seed=11).run(4)
    assert np.array_equal(r1.injected, r2.injected)
    assert np.array_equal(r1.delivered, r2.delivered)
    assert np.array_equal(r1.shed, r2.shed)
    for d1, d2 in zip(r1.tenants, r2.tenants):
        assert np.array_equal(d1.hist, d2.hist)
        assert d1.p99_us == d2.p99_us
    r3 = make_engine(seed=12).run(4)
    assert not np.array_equal(r1.injected, r3.injected)


@pytest.mark.timeout(300)
def test_engine_continuous_start_stop():
    # continuous mode: no segment bound; stop() must join both threads,
    # finish staged work and still conserve
    eng = make_engine()
    eng.start()
    import time
    time.sleep(1.0)
    rep = eng.stop()
    assert rep.conservation_checked
    assert np.all(rep.injected == rep.delivered + rep.shed)
    # threads are gone and the engine is reusable-safe (double stop raises)
    with pytest.raises(RuntimeError):
        eng.stop()


@pytest.mark.timeout(300)
def test_engine_latency_attribution_counts_delivered():
    eng = make_engine()
    rep = eng.run(5)
    for t, dig in enumerate(rep.tenants):
        assert dig.hist.sum() == rep.delivered[t]
        if dig.delivered:
            assert dig.p99_us >= dig.p50_us


@pytest.mark.timeout(120)
def test_engine_device_thread_failure_is_raised():
    # a segment that fails on the device thread (as a compile error on the
    # chip would) must surface from run() at once, not as a stop timeout
    eng = make_engine()

    def broken_segment(*args):
        raise RuntimeError("injected segment failure")

    eng._seg = broken_segment
    with pytest.raises(RuntimeError, match="injected segment failure"):
        eng.run(4, timeout=60.0)
    assert eng._device_t is None and eng._ingest_t is None


@pytest.mark.timeout(300)
def test_engine_rejects_mismatched_source():
    src = PoissonLoadGen(0, [TenantProfile("a", 1.0)], 1, 8)
    mesh = make_wafer_mesh(1, "w")
    cfg = EngineConfig(capacity=8, link_credits=16, nx=1, ny=1, nz=1)
    with pytest.raises(ValueError):
        SpikeEngine(mesh, "w", [TenantSpec("a", 8), TenantSpec("b", 4)],
                    cfg, src)


def test_ledger_conservation_violation_raises():
    from repro.wire.latency import N_LATENCY_BINS
    led = TenantLedger(["a"])
    led.add_injected(np.array([5]))
    led.add_windows(np.array([[3]]), np.array([[1]]),
                    np.zeros((1, 1, N_LATENCY_BINS)), np.zeros((1, 1)),
                    np.zeros((1, 1)))
    with pytest.raises(AssertionError):
        led.check_conservation()
    led.add_windows(np.array([[1]]), np.array([[0]]),
                    np.zeros((1, 1, N_LATENCY_BINS)), np.zeros((1, 1)),
                    np.zeros((1, 1)))
    led.check_conservation()


@pytest.mark.slow
@pytest.mark.timeout(900)
def test_engine_link_death_mid_segment_conserves():
    """A cable dies mid-run (window 6 of a 4-segment serve) on the full
    8-shard 2x2x2 torus: the engine's per-tenant ledger must still
    balance exactly (``injected == delivered + shed``) — a fabric fault
    may delay or detour a tenant's events, it must never lose or
    double-count them.  Needs 8 devices, so the engine runs in a
    subprocess; the pytest ``timeout`` is the outer belt against a
    stalled device thread, ``run_md``'s subprocess timeout the inner."""
    from md_helper import run_md
    out = run_md(r"""
import numpy as np
import jax

from repro.fabric import link_fault
from repro.serve.loadgen import PoissonLoadGen, TenantProfile
from repro.serve.spike_engine import EngineConfig, SpikeEngine
from repro.serve.tenancy import TenantSpec

n = 8
from repro.launch.mesh import make_wafer_mesh
mesh = make_wafer_mesh(n, "w")
tenants = [TenantSpec("a", reserve=12, rate_epw=40.0),
           TenantSpec("b", reserve=10, rate_epw=20.0)]
cfg = EngineConfig(capacity=16, link_credits=32, notify_latency=2,
                   window_us=100.0, seg_windows=4, nx=2, ny=2, nz=2)
src = PoissonLoadGen(0, [TenantProfile("a", 40.0),
                         TenantProfile("b", 20.0)], n, cfg.capacity)
# the cable dies at absolute window 6 — mid-segment 2 of 4 — and stays dead
sched = link_fault((2, 2, 2), 64, 0, 0, start=6)
eng = SpikeEngine(mesh, "w", tenants, cfg, src,
                  fault_schedule=sched)
rep = eng.run(4)
assert rep.conservation_checked
assert np.all(rep.injected == rep.delivered + rep.shed), (
    rep.injected, rep.delivered, rep.shed)
assert rep.delivered.sum() > 0
assert rep.windows == 4 * 4
# both tenants kept receiving after the fault landed
for t, dig in enumerate(rep.tenants):
    assert dig.hist.sum() == rep.delivered[t]
print("injected=%s delivered=%s shed=%s" %
      (rep.injected.tolist(), rep.delivered.tolist(), rep.shed.tolist()))
print("ENGINE_FAULT_OK")
""", timeout=840)
    assert "ENGINE_FAULT_OK" in out


def test_loadgen_substreams_independent_of_cotenants():
    # tenant 0's window-k draw must not depend on other tenants' profiles
    a = PoissonLoadGen(5, [TenantProfile("q", 20.0),
                           TenantProfile("h", 0.0)], 4, 16)
    b = PoissonLoadGen(5, [TenantProfile("q", 20.0),
                           TenantProfile("h", 300.0, burst_factor=3.0,
                                         burst_prob=0.5)], 4, 16)
    for w in range(6):
        ta, tb = a.next_window(w), b.next_window(w)
        assert np.array_equal(ta.counts[0], tb.counts[0])
        assert np.array_equal(ta.words[0], tb.words[0])
    assert isinstance(ta, WindowTraffic)
