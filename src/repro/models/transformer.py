"""Decoder-only transformer assembly covering the dense / MoE / VLM
architectures of the zoo, config-driven, with scanned layers.

Layers are *stacked* (leading ``layers`` dim) and applied with ``lax.scan``
so the HLO stays one-block-sized regardless of depth — this is what keeps
the 512-device dry-run compile tractable and is also how the big frameworks
do it (MaxText et al.).

A ``Runtime`` carries mesh context (sharding-constraint hook, MoE dispatch
impl); models stay mesh-agnostic for CPU tests by passing ``Runtime()``.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models import attention as A
from repro.models import layers as L
from repro.models import moe as M
from repro.models.modules import ParamSpec


@dataclasses.dataclass(frozen=True)
class Runtime:
    """Mesh-dependent hooks; default is single-device-safe no-ops."""

    mesh: Any = None
    batch_axes: tuple = ("data",)       # mesh axes the batch is split over
    model_axis: str = "model"
    moe_impl: str = "local"             # local | bucket
    remat: bool = False
    attn_chunk: int = 1024
    logits_chunk: int = 512
    seq_axis: Any = None                # Megatron-style sequence parallelism:
                                        # residual stream sharded over this
                                        # mesh axis between blocks
    split_kv_axis: Any = None           # decode: KV cache sharded on seq
                                        # over this axis -> flash-decoding
                                        # (logsumexp-combine), no cache AG
    grad_specs: Any = None              # param-sharding tree; constrains
                                        # grads so XLA reduce-scatters the
                                        # FSDP gradients instead of AR

    def wsc(self, t, spec):
        if self.mesh is None:
            return t
        return jax.lax.with_sharding_constraint(
            t, jax.sharding.NamedSharding(self.mesh, spec))

    def aspec(self):
        """Residual-activation PartitionSpec (B, S, d)."""
        return P(self.batch_axes, self.seq_axis, None)


# ---------------------------------------------------------------------------
# Parameter specs
# ---------------------------------------------------------------------------

def _attn_specs(cfg: ModelConfig, n: int) -> dict:
    d, H, Hkv, Dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    s: dict = {
        "wq": ParamSpec((n, d, H, Dh), ("layers", "embed", "heads", "head_dim")),
        "wk": ParamSpec((n, d, Hkv, Dh), ("layers", "embed", "kv_heads", "head_dim")),
        "wv": ParamSpec((n, d, Hkv, Dh), ("layers", "embed", "kv_heads", "head_dim")),
        "wo": ParamSpec((n, H, Dh, d), ("layers", "heads", "head_dim", "embed")),
    }
    if cfg.qkv_bias:
        s["bq"] = ParamSpec((n, H, Dh), ("layers", "heads", "head_dim"), init="zeros")
        s["bk"] = ParamSpec((n, Hkv, Dh), ("layers", "kv_heads", "head_dim"), init="zeros")
        s["bv"] = ParamSpec((n, Hkv, Dh), ("layers", "kv_heads", "head_dim"), init="zeros")
    if cfg.qk_norm:
        s["q_norm"] = ParamSpec((n, Dh), ("layers", "head_dim"), init="ones")
        s["k_norm"] = ParamSpec((n, Dh), ("layers", "head_dim"), init="ones")
    return s


def _mlp_specs(cfg: ModelConfig, n: int, ff: int, prefix: str = "") -> dict:
    d = cfg.d_model
    return {
        prefix + "wg": ParamSpec((n, d, ff), ("layers", "embed", "mlp")),
        prefix + "wu": ParamSpec((n, d, ff), ("layers", "embed", "mlp")),
        prefix + "wd": ParamSpec((n, ff, d), ("layers", "mlp", "embed")),
    }


def _moe_specs(cfg: ModelConfig, n: int) -> dict:
    m = cfg.moe
    d, f = cfg.d_model, m.expert_ff
    s = {
        "router": ParamSpec((n, d, m.n_experts), ("layers", "embed", None),
                            init="small"),
        "w_gate": ParamSpec((n, m.n_experts, d, f),
                            ("layers", "expert", "embed", "mlp")),
        "w_up": ParamSpec((n, m.n_experts, d, f),
                          ("layers", "expert", "embed", "mlp")),
        "w_down": ParamSpec((n, m.n_experts, f, d),
                            ("layers", "expert", "mlp", "embed")),
    }
    if m.n_shared:
        s.update(_mlp_specs(cfg, n, m.n_shared * f, prefix="sh_"))
    if m.parallel_dense_ff:
        s.update(_mlp_specs(cfg, n, m.parallel_dense_ff, prefix="pd_"))
    return s


def _norm_specs(cfg: ModelConfig, n: int) -> dict:
    d = cfg.d_model
    init = "zeros" if cfg.post_norm else "ones"   # gemma stores w-1
    s = {
        "ln1": ParamSpec((n, d), ("layers", "embed"), init=init),
        "ln2": ParamSpec((n, d), ("layers", "embed"), init=init),
    }
    if cfg.post_norm:
        s["ln1b"] = ParamSpec((n, d), ("layers", "embed"), init=init)
        s["ln2b"] = ParamSpec((n, d), ("layers", "embed"), init=init)
    return s


def param_specs(cfg: ModelConfig) -> dict:
    nl = cfg.n_layers
    n_moe = 0
    specs: dict = {
        "embed": ParamSpec((cfg.vocab, cfg.d_model), ("vocab", "embed"),
                           init="embed"),
        "final_norm": ParamSpec((cfg.d_model,), ("embed",),
                                init="zeros" if cfg.post_norm else "ones"),
    }
    if not cfg.tie_embeddings:
        specs["lm_head"] = ParamSpec((cfg.d_model, cfg.vocab),
                                     ("embed", "vocab"))
    if cfg.moe:
        n_dense = cfg.moe.first_dense
        n_moe = nl - n_dense
        block = {**_attn_specs(cfg, n_moe), **_moe_specs(cfg, n_moe),
                 **_norm_specs(cfg, n_moe)}
        specs["blocks"] = block
        if n_dense:
            dense = {**_attn_specs(cfg, n_dense),
                     **_mlp_specs(cfg, n_dense, cfg.moe.dense_ff or cfg.d_ff),
                     **_norm_specs(cfg, n_dense)}
            specs["dense_blocks"] = dense
    else:
        specs["blocks"] = {**_attn_specs(cfg, nl),
                           **_mlp_specs(cfg, nl, cfg.d_ff),
                           **_norm_specs(cfg, nl)}
    return specs


# ---------------------------------------------------------------------------
# Blocks
# ---------------------------------------------------------------------------

def _res_scale(cfg: ModelConfig) -> float:
    return float(cfg.scale_depth / np.sqrt(cfg.n_layers)) if cfg.scale_depth else 1.0


def _scaled(o, cfg: ModelConfig):
    s = _res_scale(cfg)
    return o if s == 1.0 else o * jnp.asarray(s, o.dtype)


def _norm(cfg):
    return partial(L.rms_norm, eps=cfg.rms_eps, unit_offset=cfg.post_norm)


def _project_qkv(p, h, cfg: ModelConfig):
    q = jnp.einsum("bsd,dhk->bshk", h, p["wq"].astype(h.dtype))
    k = jnp.einsum("bsd,dhk->bshk", h, p["wk"].astype(h.dtype))
    v = jnp.einsum("bsd,dhk->bshk", h, p["wv"].astype(h.dtype))
    if cfg.qkv_bias:
        q = q + p["bq"].astype(h.dtype)
        k = k + p["bk"].astype(h.dtype)
        v = v + p["bv"].astype(h.dtype)
    if cfg.qk_norm:
        q = L.rms_norm(q, p["q_norm"], cfg.rms_eps)
        k = L.rms_norm(k, p["k_norm"], cfg.rms_eps)
    return q, k, v


def _rope(cfg: ModelConfig, x, positions, positions3=None):
    if cfg.mrope_sections and positions3 is not None:
        return L.apply_mrope(x, positions3, cfg.mrope_sections, cfg.rope_theta)
    return L.apply_rope(x, positions, cfg.rope_theta)


def cast_params(p, dtype=jnp.bfloat16):
    """Cast a block's f32 params to compute dtype BEFORE any collective:
    FSDP all-gathers then move bf16 on the wire (2x fewer bytes) and the
    backward cast boundary keeps master params f32."""
    return jax.tree_util.tree_map(
        lambda t: t.astype(dtype) if t.dtype == jnp.float32 else t, p)


def attn_block(p, x, cfg: ModelConfig, rt: Runtime, *, window: int,
               positions, positions3=None, cache: A.KVCache | None = None,
               ring: bool = False):
    """Pre/post-norm attention residual. Returns (x, new_cache)."""
    p = cast_params(p)
    norm = _norm(cfg)
    h = norm(x, p["ln1"])
    q, k, v = _project_qkv(p, h, cfg)
    q = _rope(cfg, q, positions, positions3)
    k = _rope(cfg, k, positions, positions3)
    if rt.mesh is not None and rt.seq_axis is not None and cache is None:
        # context-parallel attention (train): queries stay sequence-sharded
        # over the model axis, K/V replicate across it — every flash-chunk
        # step is then communication-free; only dK/dV pay one all-reduce.
        # K/V are constrained seq-sharded FIRST so the projection runs on
        # the local sequence slice and the all-gather moves K/V
        # (B,S,Hkv,D — 5x smaller than gathering the d_model residual).
        q = rt.wsc(q, P(rt.batch_axes, rt.seq_axis, None, None))
        k = rt.wsc(k, P(rt.batch_axes, rt.seq_axis, None, None))
        v = rt.wsc(v, P(rt.batch_axes, rt.seq_axis, None, None))
        k = rt.wsc(k, P(rt.batch_axes, None, None, None))
        v = rt.wsc(v, P(rt.batch_axes, None, None, None))
    scale = cfg.query_scale if cfg.query_scale else None
    cp = rt.mesh is not None and rt.seq_axis is not None and cache is None
    if cache is not None:
        cache = A.cache_update(cache, k, v, ring=ring)
        if x.shape[1] == 1:
            if rt.split_kv_axis is not None and not ring:
                o = _split_kv_decode(q, cache, rt, scale, window,
                                     cfg.attn_softcap)
            else:
                o = A.decode_attention(q, cache, window=window,
                                       softcap=cfg.attn_softcap, scale=scale,
                                       ring=ring)
        else:
            o = A.flash_attention(q, cache.k, cache.v, causal=True,
                                  window=window, softcap=cfg.attn_softcap,
                                  scale=scale, kv_len=cache.length,
                                  chunk=rt.attn_chunk)
    else:
        o = A.flash_attention(q, k, v, causal=True, window=window,
                              softcap=cfg.attn_softcap, scale=scale,
                              chunk=rt.attn_chunk,
                              gqa="group" if cp else "expand")
    o = jnp.einsum("bshk,hkd->bsd", o, p["wo"].astype(o.dtype))
    if cfg.post_norm:
        o = norm(o, p["ln1b"])
    return x + _scaled(o, cfg), cache


def _split_kv_decode(q, cache, rt: Runtime, scale, window, softcap):
    """Flash-decoding over the seq-sharded cache (hillclimb: replaces the
    per-layer cache all-gather with one tiny logsumexp-combine psum)."""
    from functools import partial as _partial

    from repro.distributed.collectives import split_kv_decode_attention

    ax = rt.split_kv_axis
    bspec = P(rt.batch_axes, None, None, None)
    kspec = P(rt.batch_axes, ax, None, None)
    fn = jax.shard_map(
        lambda q_, k_, v_, ln_, w_: split_kv_decode_attention(
            q_, k_, v_, ln_, axis_name=ax, scale=scale if scale else None,
            softcap=softcap, window=w_),
        mesh=rt.mesh,
        in_specs=(bspec, kspec, kspec, P(), P()),
        out_specs=bspec,
        check_vma=False,
    )
    return fn(q, cache.k, cache.v, cache.length, jnp.asarray(window))


def ffn_block(p, x, cfg: ModelConfig, rt: Runtime, *, ff_prefix: str = ""):
    p = cast_params(p)
    norm = _norm(cfg)
    h = norm(x, p["ln2"])
    o = L.glu_mlp(h, p[ff_prefix + "wg"].astype(h.dtype),
                  p[ff_prefix + "wu"].astype(h.dtype),
                  p[ff_prefix + "wd"].astype(h.dtype), cfg.act)
    if cfg.post_norm:
        o = norm(o, p["ln2b"])
    return x + _scaled(o, cfg)


def moe_block(p, x, cfg: ModelConfig, rt: Runtime):
    """MoE residual (+ optional shared experts / parallel dense)."""
    p = cast_params(p)
    norm = _norm(cfg)
    h = norm(x, p["ln2"])
    B, S, d = h.shape
    flat = h.reshape(-1, d)
    mp = {k: p[k] for k in ("router", "w_gate", "w_up", "w_down")}
    if rt.moe_impl == "bucket" and rt.mesh is not None:
        o_flat, stats = _moe_bucket_sharded(flat, mp, cfg, rt, B, S)
    else:
        o_flat, stats = M.moe_layer_local(
            flat, mp, cfg.moe, act=cfg.act,
            wsc=(rt.wsc if rt.mesh is not None else None))
    o = o_flat.reshape(B, S, d)
    if cfg.moe.n_shared:
        o = o + L.glu_mlp(h, p["sh_wg"].astype(h.dtype),
                          p["sh_wu"].astype(h.dtype),
                          p["sh_wd"].astype(h.dtype), cfg.act)
    if cfg.moe.parallel_dense_ff:
        o = o + L.glu_mlp(h, p["pd_wg"].astype(h.dtype),
                          p["pd_wu"].astype(h.dtype),
                          p["pd_wd"].astype(h.dtype), cfg.act)
    return x + _scaled(o, cfg), stats


def _moe_bucket_sharded(flat, mp, cfg: ModelConfig, rt: Runtime, B, S):
    """shard_map EP dispatch (paper's bucket aggregation over the ICI)."""
    d = flat.shape[-1]
    x3 = flat.reshape(B, S, d)
    # tokens enter the dispatch sequence-sharded over the EP axis: each
    # model-rank buckets ONLY its S/ep slice (without this, every rank
    # routes all tokens and the a2a carries ep identical copies — measured
    # 16x redundant bytes on deepseek train).
    bspec = P(rt.batch_axes, rt.seq_axis, None)
    espec = P(rt.model_axis, None, None)

    def body(xl, router, wg, wu, wd):
        # xl: (B_loc, S, d); experts pre-sliced over model axis; the mlp dim
        # may be FSDP-sharded over the batch axes -> gather it back first.
        wg = _regather(wg, rt)
        wu = _regather(wu, rt)
        wd = _regather_t(wd, rt)
        t = xl.reshape(-1, d)
        y, stats = M.moe_layer_bucket(
            t, {"router": router, "w_gate": wg, "w_up": wu, "w_down": wd},
            cfg.moe, axis=rt.model_axis, act=cfg.act)
        stats = jax.tree_util.tree_map(
            lambda s: jax.lax.pmean(s, rt.model_axis), stats)
        return y.reshape(xl.shape), stats

    fn = jax.shard_map(
        body, mesh=rt.mesh,
        in_specs=(bspec, P(), espec, espec, espec),
        out_specs=(bspec, P()),
        check_vma=False,
    )
    y, stats = fn(x3, mp["router"],
                  mp["w_gate"], mp["w_up"], mp["w_down"])
    return y.reshape(-1, d), stats


def _regather(w, rt: Runtime):
    """No-op placeholder: expert mlp dim arrives full inside shard_map
    because in_specs only split the expert axis; kept as a hook for FSDP'd
    expert weights (arctic uses sliced mlp + all_gather here)."""
    return w


def _regather_t(w, rt: Runtime):
    return w


# ---------------------------------------------------------------------------
# Model: init / forward / decode
# ---------------------------------------------------------------------------

def _layer_windows(cfg: ModelConfig) -> np.ndarray:
    """Per-layer sliding window (0 = global)."""
    if cfg.alt_local_global and cfg.sliding_window:
        w = np.zeros(cfg.n_layers, np.int32)
        w[0::2] = cfg.sliding_window          # even layers local (gemma2)
        return w
    if cfg.sliding_window:
        return np.full(cfg.n_layers, cfg.sliding_window, np.int32)
    return np.zeros(cfg.n_layers, np.int32)


def embed_tokens(params, tokens, cfg: ModelConfig, rt: Runtime,
                 vision_embeds=None):
    x = params["embed"].astype(jnp.bfloat16)[tokens]
    if cfg.scale_emb != 1.0:
        x = x * cfg.scale_emb
    elif cfg.post_norm:                        # gemma convention
        x = x * jnp.asarray(np.sqrt(cfg.d_model), x.dtype)
    if vision_embeds is not None and cfg.vision_tokens:
        x = jax.lax.dynamic_update_slice(
            x, vision_embeds.astype(x.dtype), (0, 0, 0))
    return rt.wsc(x, rt.aspec())


def forward(params, tokens, cfg: ModelConfig, rt: Runtime | None = None,
            positions=None, positions3=None, vision_embeds=None):
    """Full-sequence forward -> final hidden states (B, S, d) bf16."""
    rt = rt or Runtime()
    B, S = tokens.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    x = embed_tokens(params, tokens, cfg, rt, vision_embeds)
    windows = jnp.asarray(_layer_windows(cfg))
    aux = jnp.zeros((), jnp.float32)

    def make_scan(block_params, moe: bool, windows_slice):
        def body(carry, xs):
            x, aux = carry
            p, win = xs
            x, _ = attn_block(p, x, cfg, rt, window=win, positions=positions,
                              positions3=positions3)
            if moe:
                x, stats = moe_block(p, x, cfg, rt)
                aux = aux + stats.aux_loss
            else:
                x = ffn_block(p, x, cfg, rt)
            x = rt.wsc(x, rt.aspec())
            return (x, aux), None
        if rt.remat:
            body = jax.checkpoint(body,
                                  policy=jax.checkpoint_policies.nothing_saveable)
        return body

    if cfg.moe and cfg.moe.first_dense:
        nd = cfg.moe.first_dense
        (x, aux), _ = jax.lax.scan(
            make_scan(params["dense_blocks"], False, windows[:nd]),
            (x, aux), (params["dense_blocks"], windows[:nd]))
        (x, aux), _ = jax.lax.scan(
            make_scan(params["blocks"], True, windows[nd:]),
            (x, aux), (params["blocks"], windows[nd:]))
    else:
        (x, aux), _ = jax.lax.scan(
            make_scan(params["blocks"], bool(cfg.moe), windows),
            (x, aux), (params["blocks"], windows))

    x = _norm(cfg)(x, params["final_norm"])
    return x, aux


def logits_fn(params, hidden, cfg: ModelConfig, rt: Runtime | None = None):
    rt = rt or Runtime()
    w = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = hidden @ w.astype(hidden.dtype)
    logits = logits * cfg.logit_scale
    logits = L.softcap(logits.astype(jnp.float32), cfg.logit_softcap)
    return rt.wsc(logits, P(rt.batch_axes, None, rt.model_axis))


# -- decode -----------------------------------------------------------------

def ring_caches(cfg: ModelConfig) -> bool:
    """Static: ring-buffer caches iff every layer is windowed."""
    w = _layer_windows(cfg)
    return bool(w.min() > 0)


def init_caches(cfg: ModelConfig, batch: int, max_len: int,
                dtype=jnp.bfloat16):
    """Stacked per-layer KV caches; windowed layers get ring buffers."""
    windows = _layer_windows(cfg)
    # a single stacked cache sized max(window or max_len) keeps scan simple:
    # global layers use full length, local layers could use `window` — we
    # allocate full length per layer unless ALL layers are windowed.
    ring = ring_caches(cfg)
    T = int(windows.max()) if ring else max_len
    nl = cfg.n_layers

    def mk(n):
        return A.KVCache(
            k=jnp.zeros((n, batch, T, cfg.n_kv_heads, cfg.head_dim), dtype),
            v=jnp.zeros((n, batch, T, cfg.n_kv_heads, cfg.head_dim), dtype),
            length=jnp.zeros((n,), jnp.int32),
        )

    if cfg.moe and cfg.moe.first_dense:
        return {"dense": mk(cfg.moe.first_dense),
                "blocks": mk(nl - cfg.moe.first_dense)}
    return {"blocks": mk(nl)}


def decode_step(params, caches, tokens, cfg: ModelConfig,
                rt: Runtime | None = None, positions=None, positions3=None):
    """One token for every sequence. tokens: (B, 1). Returns (logits, caches)."""
    rt = rt or Runtime()
    B = tokens.shape[0]
    if positions is None:
        pos0 = caches["blocks"].length[0]
        positions = jnp.broadcast_to(pos0, (B, 1)).astype(jnp.int32)
    x = embed_tokens(params, tokens, cfg, rt)
    windows = jnp.asarray(_layer_windows(cfg))
    ring = ring_caches(cfg)

    def body(x, xs):
        p, win, ck, cv, clen = xs
        cache = A.KVCache(ck, cv, clen)
        x, cache = attn_block(p, x, cfg, rt, window=win, positions=positions,
                              positions3=positions3, cache=cache, ring=ring)
        if "router" in p:
            x, _ = moe_block(p, x, cfg, rt)
        elif "wg" in p:
            x = ffn_block(p, x, cfg, rt)
        return x, (cache.k, cache.v, cache.length)

    def run_scan(x, block_params, cache, win):
        c = caches[cache]
        xs = (block_params, win, c.k, c.v, c.length)
        x, (k, v, ln) = jax.lax.scan(body, x, xs)
        return x, A.KVCache(k, v, ln)

    if cfg.moe and cfg.moe.first_dense:
        nd = cfg.moe.first_dense
        x, cd = run_scan(x, params["dense_blocks"], "dense", windows[:nd])
        x, cb = run_scan(x, params["blocks"], "blocks", windows[nd:])
        new = {"dense": cd, "blocks": cb}
    else:
        x, cb = run_scan(x, params["blocks"], "blocks", windows)
        new = {"blocks": cb}

    x = _norm(cfg)(x, params["final_norm"])
    logits = logits_fn(params, x, cfg, rt)
    return logits, new
