"""Llama-3.2-Vision-style VLM backbone: a dense GQA decoder with gated
cross-attention layers interleaved every ``cross_every`` self-attention
layers (40 = 8 x (4 self + 1 cross) for the 11B config).

Per the assignment the modality frontend is a STUB: ``input_specs()``
provides precomputed patch embeddings (B, vision_seq, d_model); this module
consumes them as the cross-attention memory.  The stack is scanned over
homogeneous (self x cross_every-1, cross) groups.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.distributed.sharding import shard
from repro.models import layers as L
from repro.models import transformer as T
from repro.models.common import ModelConfig, dense_param, init_stacked, stack_axes
from repro.obs.registry import repeated


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def init_cross_layer(rng, cfg: ModelConfig):
    d, H, KV, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    ks = jax.random.split(rng, 5)
    attn, attn_ax = T.init_attn(ks[0], cfg)
    mlp, mlp_ax = T.init_mlp(ks[1], cfg)
    params = {"attn": attn, "mlp": mlp,
              "ln1": jnp.zeros((d,)), "ln2": jnp.zeros((d,)),
              "ln_kv": jnp.zeros((d,)),
              "gate_attn": jnp.zeros(()), "gate_mlp": jnp.zeros(())}
    axes = {"attn": attn_ax, "mlp": mlp_ax,
            "ln1": ("embed",), "ln2": ("embed",), "ln_kv": ("embed",),
            "gate_attn": (), "gate_mlp": ()}
    return params, axes


def init_group(rng, cfg: ModelConfig):
    """cross_every-1 self layers + 1 cross layer."""
    n_self = cfg.cross_every - 1
    k1, k2 = jax.random.split(rng)
    _, self_ax = T.init_dense_layer(k1, cfg)
    selfs = init_stacked(k1, n_self, lambda r: T.init_dense_layer(r, cfg)[0])
    cross, cross_ax = init_cross_layer(k2, cfg)
    return ({"selfs": selfs, "cross": cross},
            {"selfs": stack_axes(self_ax), "cross": cross_ax})


def init(rng, cfg: ModelConfig):
    assert cfg.n_layers % cfg.cross_every == 0
    ng = cfg.n_layers // cfg.cross_every
    k_emb, k_g, k_head = jax.random.split(rng, 3)
    _, group_ax = init_group(k_g, cfg)
    groups = init_stacked(k_g, ng, lambda r: init_group(r, cfg)[0])
    params = {
        "embed": dense_param(k_emb, (cfg.padded_vocab, cfg.d_model), scale=1.0),
        "groups": groups,
        "ln_f": jnp.zeros((cfg.d_model,)),
        "lm_head": dense_param(k_head, (cfg.d_model, cfg.padded_vocab)),
    }
    axes = {
        "embed": ("vocab", "embed"),
        "groups": stack_axes(group_ax),
        "ln_f": ("embed",),
        "lm_head": ("embed", "vocab"),
    }
    return params, axes


# ---------------------------------------------------------------------------
# cross-attention block
# ---------------------------------------------------------------------------

def cross_block(p, cfg: ModelConfig, x, memory, *, kv_cache=None):
    """Gated cross-attention against vision memory (B, Lv, d).

    kv_cache: optional precomputed (k, v) from the memory — used in decode
    so the image K/V projection runs once per request, not per token."""
    eng = cfg.engine
    B, Lq, d = x.shape
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    xn = L.rmsnorm(x, p["ln1"], cfg.norm_eps)
    q = eng(xn, p["attn"]["wq"]).reshape(B, Lq, H, hd)
    if kv_cache is None:
        mn = L.rmsnorm(memory, p["ln_kv"], cfg.norm_eps)
        Lv = memory.shape[1]
        k = eng(mn, p["attn"]["wk"]).reshape(B, Lv, KV, hd)
        v = eng(mn, p["attn"]["wv"]).reshape(B, Lv, KV, hd)
    else:
        k, v = kv_cache
    q = shard(q, "batch", "seq", "heads", "head_dim")
    out = L.attention_flash(q, k, v, causal=False,
                            q_chunk=cfg.q_chunk, kv_chunk=cfg.kv_chunk,
                            engine=eng)
    out = eng(out.reshape(B, Lq, H * hd), p["attn"]["wo"])
    x = x + jnp.tanh(p["gate_attn"]).astype(x.dtype) * out
    xn2 = L.rmsnorm(x, p["ln2"], cfg.norm_eps)
    mlp_out = L.swiglu(xn2, p["mlp"]["w_gate"], p["mlp"]["w_up"],
                       p["mlp"]["w_down"], eng)
    return x + jnp.tanh(p["gate_mlp"]).astype(x.dtype) * mlp_out


def cross_kv(p, cfg: ModelConfig, memory):
    """Precompute cross K/V for decode."""
    eng = cfg.engine
    B, Lv, _ = memory.shape
    KV, hd = cfg.n_kv_heads, cfg.hd
    mn = L.rmsnorm(memory, p["ln_kv"], cfg.norm_eps)
    k = eng(mn, p["attn"]["wk"]).reshape(B, Lv, KV, hd)
    v = eng(mn, p["attn"]["wv"]).reshape(B, Lv, KV, hd)
    return k, v


# ---------------------------------------------------------------------------
# forward / decode
# ---------------------------------------------------------------------------

def _group_fwd(gp, cfg, x, cos, sin, memory, *, self_cache=None,
               cross_kv_cache=None, cur_len=None):
    n_self = cfg.cross_every - 1
    new_kv = None
    if self_cache is None:
        def body(lp, xc, _):
            xc, _ = T.dense_layer(lp, cfg, xc, cos, sin)
            return xc, None
        x, _ = T.scan_layers(body, gp["selfs"], x, n_layers=n_self)
    else:
        def body(xc, inputs):
            lp, kc, vc = inputs
            xc, kv = T.dense_layer(lp, cfg, xc, cos, sin, cache=(kc, vc),
                                   cur_len=cur_len)
            return xc, kv
        with repeated(n_self):
            x, new_kv = lax.scan(
                body, x, (gp["selfs"], self_cache[0], self_cache[1]),
                length=n_self)
    x = cross_block(gp["cross"], cfg, x, memory, kv_cache=cross_kv_cache)
    return x, new_kv


def forward(params, cfg: ModelConfig, tokens: jax.Array,
            image_embeds: jax.Array, positions=None):
    """tokens (B, L); image_embeds (B, vision_seq, d_model) — stub frontend."""
    B, Lq = tokens.shape
    x = L.embed_tokens(tokens, params["embed"], cfg.compute_dtype)
    memory = shard(image_embeds.astype(cfg.compute_dtype),
                   "batch", "seq", "embed")
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(Lq, dtype=jnp.int32), (B, Lq))
    cos, sin = L.rope_cos_sin(positions, cfg.hd, cfg.rope_theta)

    ng = cfg.n_layers // cfg.cross_every

    def body(gp, x, _):
        x, _ = _group_fwd(gp, cfg, x, cos, sin, memory)
        return x, None

    x, _ = T.scan_layers(body, params["groups"], x, n_layers=ng,
                         remat_block=cfg.remat_block)
    x = L.rmsnorm(x, params["ln_f"], cfg.norm_eps)
    return L.logits_head(x, params["lm_head"], cfg.engine)


def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               image_embeds: Optional[jax.Array] = None, params=None):
    """Self-attn KV ring buffers per group + precomputed cross K/V."""
    ng = cfg.n_layers // cfg.cross_every
    n_self = cfg.cross_every - 1
    KV, hd = cfg.n_kv_heads, cfg.hd
    shp = (ng, n_self, batch, max_len, KV, hd)
    cache = {
        "k": shard(jnp.zeros(shp, jnp.bfloat16),
                   "layers", None, "cache_batch", None, "cache_heads", "cache_hd"),
        "v": shard(jnp.zeros(shp, jnp.bfloat16),
                   "layers", None, "cache_batch", None, "cache_heads", "cache_hd"),
    }
    if image_embeds is not None:
        memory = image_embeds.astype(cfg.compute_dtype)
        def kv_of_group(gp):
            return cross_kv(gp["cross"], cfg, memory)
        ck, cv = jax.vmap(kv_of_group)(
            jax.tree.map(lambda a: a, params["groups"]))
    else:
        Lv = cfg.vision_seq
        ck = jnp.zeros((ng, batch, Lv, KV, hd), cfg.compute_dtype)
        cv = jnp.zeros((ng, batch, Lv, KV, hd), cfg.compute_dtype)
    cache["cross_k"] = shard(ck.astype(jnp.bfloat16), "layers", "cache_batch",
                             None, "cache_heads", "cache_hd")
    cache["cross_v"] = shard(cv.astype(jnp.bfloat16), "layers", "cache_batch",
                             None, "cache_heads", "cache_hd")
    return cache


def cache_axes(cfg: ModelConfig):
    return {
        "k": ("layers", None, "cache_batch", None, "cache_heads", "cache_hd"),
        "v": ("layers", None, "cache_batch", None, "cache_heads", "cache_hd"),
        "cross_k": ("layers", "cache_batch", None, "cache_heads", None),
        "cross_v": ("layers", "cache_batch", None, "cache_heads", None),
    }


def decode_step(params, cfg: ModelConfig, cache, tokens: jax.Array,
                cur_len: jax.Array):
    B = tokens.shape[0]
    x = L.embed_tokens(tokens, params["embed"], cfg.compute_dtype)
    pos = L.decode_positions(cur_len, B)
    cos, sin = L.rope_cos_sin(pos, cfg.hd, cfg.rope_theta)

    def body(x, inputs):
        gp, kc, vc, ck, cv = inputs
        x, new_kv = _group_fwd(gp, cfg, x, cos, sin, None,
                               self_cache=(kc, vc),
                               cross_kv_cache=(ck.astype(x.dtype),
                                               cv.astype(x.dtype)),
                               cur_len=cur_len)
        return x, new_kv

    ng = cfg.n_layers // cfg.cross_every
    with repeated(ng):
        x, (k_n, v_n) = lax.scan(
            body, x, (params["groups"], cache["k"], cache["v"],
                      cache["cross_k"], cache["cross_v"]), length=ng)
    x = L.rmsnorm(x, params["ln_f"], cfg.norm_eps)
    logits = L.logits_head(x, params["lm_head"], cfg.engine)
    new_cache = dict(cache)
    new_cache["k"], new_cache["v"] = k_n, v_n
    return logits, new_cache
