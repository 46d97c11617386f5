"""Sharding-rule tests (host-side; no forced device count needed — we build
pspecs against a fake mesh description via jax.sharding.Mesh on 1 device is
impossible, so we exercise `axes_to_pspec` with a stub mesh object)."""
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.distributed import sharding as shd


class FakeMesh:
    """Duck-typed mesh: only axis_names + devices.shape are consulted."""

    def __init__(self, shape, names):
        self.axis_names = names
        self.devices = np.empty(shape, dtype=object)


MESH = FakeMesh((16, 16), ("data", "model"))
MESH3 = FakeMesh((2, 16, 16), ("pod", "data", "model"))


def spec(axes, shape, mesh=MESH):
    return shd.axes_to_pspec(axes, shape, mesh)


def test_mlp_weight_fsdp_plus_tp():
    # (layers, embed, mlp): embed->data (ZeRO), mlp->model (TP)
    assert spec(("layers", "embed", "mlp"), (64, 5120, 25600)) == \
        P(None, "data", "model")


def test_attention_heads_divisible():
    assert spec(("layers", "embed", "heads", "head_dim"),
                (64, 5120, 64, 128)) == P(None, "data", "model", None)


def test_kv_heads_not_divisible_stays_replicated_on_model():
    # kv=8 over model=16: no head sharding, no head_dim fallback
    s = spec(("layers", "embed", "kv_heads", "head_dim"), (64, 5120, 8, 128))
    assert s == P(None, "data", None, None)


def test_heads_not_divisible_falls_back_cleanly():
    # qwen1.5: 20 heads over 16 -> attention weights data-sharded only
    s = spec(("layers", "embed", "heads", "head_dim"), (40, 2560, 20, 128))
    assert s == P(None, "data", None, None)


def test_embedding_vocab_model():
    assert spec(("vocab", "embed"), (151936, 5120)) == P("model", "data")


def test_expert_weights():
    s = spec(("layers", "expert", "embed", "mlp"), (28, 64, 2048, 1408))
    assert s == P(None, "model", "data", None)      # mlp 1408/16=88 ok too?
    # 1408 % 16 == 0, but "data" already used by embed; mlp unused axes none


def test_batch_over_pod_and_data():
    s = spec(("batch", "seq"), (256, 4096), MESH3)
    assert s[0] == ("pod", "data")


def test_cache_seq_fallback():
    # kv=8 not divisible by model -> seq picks up the model axis
    s = spec(("layers", "batch", "seq", "kv_heads", "head_dim"),
             (42, 128, 32768, 8, 256))
    assert s == P(None, "data", "model", None, None)


def test_non_divisible_never_sharded():
    s = spec(("batch", None), (1, 1))
    assert s == P(None, None)


def test_bytes_per_device():
    import jax
    import jax.numpy as jnp
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1,), ("model",))
    sds = {"a": jax.ShapeDtypeStruct((8, 8), jnp.float32)}
    from jax.sharding import NamedSharding
    sh = {"a": NamedSharding(mesh, P("model", None))}
    assert shd.bytes_per_device(sds, sh) == 8 * 8 * 4
