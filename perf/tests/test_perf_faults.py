"""A run whose timed path is broken underneath comes out not correct.

Each test drives a whole benchmark run on the CPU (the look for a chip
skipped) at a tiny size, with one fault planted in the program: a
segment that returns its state unchanged, half of the delivered events
left out of the apply, an event altered where it is produced, and, on
four nodes, the exchange between nodes left out.
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [REPO, os.path.join(REPO, "src")]

from perf import harness  # noqa: E402
from perf.tests import tinycell  # noqa: E402

SECONDS = 0.3


def _run(root, nodes=1, seed=7):
    res, checks = harness.run(f"tiny{nodes}.ground", seed, SECONDS, False,
                              root=root, require_tpu=False)
    return res


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tinycell.make(tmp_path_factory.mktemp("bench"))


def test_sound_run_is_correct(root):
    res = _run(root)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "checks"


def _unchanged_state(monkeypatch):
    from repro.snn import simulator as sim
    real = sim.build_sharded_segments

    def build(*a, **k):
        init, run_segment, finish = real(*a, **k)
        return init, (lambda c, n: (c, run_segment(c, n)[1])), finish

    monkeypatch.setattr(sim, "build_sharded_segments", build)


def _half_the_events(monkeypatch):
    from repro.snn import simulator as sim
    real = sim._apply_events
    monkeypatch.setattr(sim, "_apply_events",
                        lambda st, w, counts, *a: real(st, w, counts // 2, *a))


def _event_altered(monkeypatch):
    from repro.snn import simulator as sim
    real = sim._spikes_to_events

    def produce(*a):
        words, inject, lost = real(*a)
        return words.at[0].set(words[0] ^ 1), inject, lost

    monkeypatch.setattr(sim, "_spikes_to_events", produce)


@pytest.mark.parametrize("fault", [_unchanged_state, _half_the_events,
                                   _event_altered],
                         ids=["unchanged_state", "half_the_events",
                              "event_altered"])
def test_fault_is_not_correct(root, monkeypatch, fault):
    fault(monkeypatch)
    res = _run(root)
    assert not res["correct"], res["checks"]


FOUR = textwrap.dedent("""
    import json, os, sys
    sys.path[:0] = [{repo!r}, os.path.join({repo!r}, "src")]
    from perf import harness
    from repro.transport import torus
    root = {root!r}
    out = {{}}
    res, _ = harness.run("tiny4.ground", 7, {seconds}, False, root=root,
                         require_tpu=False)
    out["sound"] = res["correct"]
    real = torus.TorusTransport.exchange

    def local_only(self, state, payload, counts, *, axis_name, **kw):
        import jax, jax.numpy as jnp
        me = jax.lax.axis_index(axis_name)
        keep = jnp.arange(counts.shape[0]) == me
        o = real(self, state, payload, jnp.where(keep, counts, 0),
                 axis_name=axis_name, **kw)
        return o

    torus.TorusTransport.exchange = local_only
    res, _ = harness.run("tiny4.ground", 7, {seconds}, False, root=root,
                         require_tpu=False)
    out["no_exchange"] = res["correct"]
    print(json.dumps(out))
""")


def test_exchange_left_out_is_not_correct(tmp_path):
    root = tinycell.make(tmp_path, nodes=4)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run(
        [sys.executable, "-c", FOUR.format(repo=REPO, root=root,
                                           seconds=SECONDS)],
        env=env, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out == {"sound": True, "no_exchange": False}
