"""Seamless-M4T-style encoder-decoder backbone (audio family).

Per the assignment, the modality frontend is a STUB: the encoder consumes
precomputed frame embeddings (B, frames, d_model) from ``input_specs()``.
Encoder: bidirectional self-attention stack.  Decoder: causal self-attention
+ cross-attention to the encoder output.  Training is teacher-forced
seq2seq; serving decodes one token against (a) the decoder's KV ring buffer
and (b) cross K/V precomputed once from the encoder output.
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

def init_dec_layer(rng, cfg: ModelConfig):
    k1, k2, k3 = jax.random.split(rng, 3)
    self_attn, sa_ax = T.init_attn(k1, cfg)
    cross_attn, ca_ax = T.init_attn(k2, cfg)
    mlp, mlp_ax = T.init_mlp(k3, cfg)
    d = cfg.d_model
    params = {"self": self_attn, "cross": cross_attn, "mlp": mlp,
              "ln1": jnp.zeros((d,)), "ln_x": jnp.zeros((d,)),
              "ln2": jnp.zeros((d,))}
    axes = {"self": sa_ax, "cross": ca_ax, "mlp": mlp_ax,
            "ln1": ("embed",), "ln_x": ("embed",), "ln2": ("embed",)}
    return params, axes


def init(rng, cfg: ModelConfig):
    k_emb, k_enc, k_dec, k_head = jax.random.split(rng, 4)
    _, enc_ax = T.init_dense_layer(k_enc, cfg)
    enc = init_stacked(k_enc, cfg.enc_layers,
                       lambda r: T.init_dense_layer(r, cfg)[0])
    _, dec_ax = init_dec_layer(k_dec, cfg)
    dec = init_stacked(k_dec, cfg.n_layers,
                       lambda r: init_dec_layer(r, cfg)[0])
    params = {
        "embed": dense_param(k_emb, (cfg.padded_vocab, cfg.d_model), scale=1.0),
        "enc_layers": enc,
        "dec_layers": dec,
        "ln_enc": jnp.zeros((cfg.d_model,)),
        "ln_f": jnp.zeros((cfg.d_model,)),
        "lm_head": dense_param(k_head, (cfg.d_model, cfg.padded_vocab)),
    }
    axes = {
        "embed": ("vocab", "embed"),
        "enc_layers": stack_axes(enc_ax),
        "dec_layers": stack_axes(dec_ax),
        "ln_enc": ("embed",),
        "ln_f": ("embed",),
        "lm_head": ("embed", "vocab"),
    }
    return params, axes


# ---------------------------------------------------------------------------
# encoder
# ---------------------------------------------------------------------------

def encode(params, cfg: ModelConfig, frames: jax.Array) -> jax.Array:
    """frames (B, F, d_model) — precomputed frame embeddings (stub frontend)."""
    B, F, _ = frames.shape
    x = shard(frames.astype(cfg.compute_dtype), "batch", "seq", "embed")
    positions = jnp.broadcast_to(jnp.arange(F, dtype=jnp.int32), (B, F))
    cos, sin = L.rope_cos_sin(positions, cfg.hd, cfg.rope_theta)

    def body(lp, x, _):
        # bidirectional: causal=False
        eng = cfg.engine
        H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
        xn = L.rmsnorm(x, lp["ln1"], cfg.norm_eps)
        q = eng(xn, lp["attn"]["wq"]).reshape(B, F, H, hd)
        k = eng(xn, lp["attn"]["wk"]).reshape(B, F, KV, hd)
        v = eng(xn, lp["attn"]["wv"]).reshape(B, F, KV, hd)
        q = L.apply_rope(q, cos, sin)
        k = L.apply_rope(k, cos, sin)
        q = shard(q, "batch", "seq", "heads", "head_dim")
        out = L.attention_flash(q, k, v, causal=False,
                                q_chunk=cfg.q_chunk, kv_chunk=cfg.kv_chunk,
                                engine=eng)
        x = x + eng(out.reshape(B, F, H * hd), lp["attn"]["wo"])
        x = T.mlp_block(lp, cfg, x)
        return x, None

    x, _ = T.scan_layers(body, params["enc_layers"], x,
                         n_layers=cfg.enc_layers, remat_block=cfg.remat_block)
    return L.rmsnorm(x, params["ln_enc"], cfg.norm_eps)


# ---------------------------------------------------------------------------
# decoder
# ---------------------------------------------------------------------------

def _dec_layer(lp, cfg, x, cos, sin, memory=None, *, self_cache=None,
               cross_kv_cache=None, cur_len=None):
    x, new_kv = T.attn_block({"attn": lp["self"], "ln1": lp["ln1"]}, cfg, x,
                             cos, sin, cache=self_cache, cur_len=cur_len)
    # cross-attention
    eng = cfg.engine
    B, Lq, _ = x.shape
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    xn = L.rmsnorm(x, lp["ln_x"], cfg.norm_eps)
    q = eng(xn, lp["cross"]["wq"]).reshape(B, Lq, H, hd)
    if cross_kv_cache is None:
        Lk = memory.shape[1]
        k = eng(memory, lp["cross"]["wk"]).reshape(B, Lk, KV, hd)
        v = eng(memory, lp["cross"]["wv"]).reshape(B, Lk, KV, hd)
    else:
        k, v = cross_kv_cache
    q = shard(q, "batch", "seq", "heads", "head_dim")
    out = L.attention_flash(q, k, v, causal=False,
                            q_chunk=cfg.q_chunk, kv_chunk=cfg.kv_chunk,
                            engine=eng)
    x = x + eng(out.reshape(B, Lq, H * hd), lp["cross"]["wo"])
    x = T.mlp_block(lp, cfg, x)
    return x, new_kv


def forward(params, cfg: ModelConfig, tokens: jax.Array, frames: jax.Array,
            positions=None):
    """Teacher-forced decode over the full target: returns (B, L, vocab)."""
    memory = encode(params, cfg, frames)
    B, Lq = tokens.shape
    x = L.embed_tokens(tokens, params["embed"], cfg.compute_dtype)
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(Lq, dtype=jnp.int32), (B, Lq))
    cos, sin = L.rope_cos_sin(positions, cfg.hd, cfg.rope_theta)

    def body(lp, x, _):
        x, _ = _dec_layer(lp, cfg, x, cos, sin, memory)
        return x, None

    x, _ = T.scan_layers(body, params["dec_layers"], x,
                         n_layers=cfg.n_layers, remat_block=cfg.remat_block)
    x = L.rmsnorm(x, params["ln_f"], cfg.norm_eps)
    return L.logits_head(x, params["lm_head"], cfg.engine)


def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               memory: Optional[jax.Array] = None, params=None):
    KV, hd = cfg.n_kv_heads, cfg.hd
    shp = (cfg.n_layers, batch, max_len, KV, hd)
    cache = {
        "k": shard(jnp.zeros(shp, jnp.bfloat16),
                   "layers", "cache_batch", None, "cache_heads", "cache_hd"),
        "v": shard(jnp.zeros(shp, jnp.bfloat16),
                   "layers", "cache_batch", None, "cache_heads", "cache_hd"),
    }
    if memory is not None and params is not None:
        eng = cfg.engine
        B, Lk, _ = memory.shape
        def kv_of(lp):
            k = eng(memory, lp["cross"]["wk"]).reshape(B, Lk, KV, hd)
            v = eng(memory, lp["cross"]["wv"]).reshape(B, Lk, KV, hd)
            return k, v
        ck, cv = jax.vmap(kv_of)(params["dec_layers"])
    else:
        Lk = max_len
        ck = jnp.zeros((cfg.n_layers, batch, Lk, KV, hd), jnp.bfloat16)
        cv = jnp.zeros((cfg.n_layers, batch, Lk, KV, hd), jnp.bfloat16)
    cache["cross_k"] = shard(ck.astype(jnp.bfloat16),
                             "layers", "cache_batch", None, "cache_heads", "cache_hd")
    cache["cross_v"] = shard(cv.astype(jnp.bfloat16),
                             "layers", "cache_batch", None, "cache_heads", "cache_hd")
    return cache


def cache_axes(cfg: ModelConfig):
    ax = ("layers", "cache_batch", None, "cache_heads", "cache_hd")
    return {"k": ax, "v": ax, "cross_k": ax, "cross_v": ax}


def decode_step(params, cfg: ModelConfig, cache, tokens: jax.Array,
                cur_len: jax.Array):
    B = tokens.shape[0]
    x = L.embed_tokens(tokens, params["embed"], cfg.compute_dtype)
    pos = L.decode_positions(cur_len, B)
    cos, sin = L.rope_cos_sin(pos, cfg.hd, cfg.rope_theta)

    def body(x, inputs):
        lp, kc, vc, ck, cv = inputs
        x, new_kv = _dec_layer(lp, cfg, x, cos, sin,
                               self_cache=(kc, vc),
                               cross_kv_cache=(ck.astype(x.dtype),
                                               cv.astype(x.dtype)),
                               cur_len=cur_len)
        return x, new_kv

    with repeated(cfg.n_layers):
        x, (k_n, v_n) = lax.scan(
            body, x, (params["dec_layers"], cache["k"], cache["v"],
                      cache["cross_k"], cache["cross_v"]),
            length=cfg.n_layers)
    x = L.rmsnorm(x, params["ln_f"], cfg.norm_eps)
    logits = L.logits_head(x, params["lm_head"], cfg.engine)
    new_cache = dict(cache)
    new_cache["k"], new_cache["v"] = k_n, v_n
    return logits, new_cache
