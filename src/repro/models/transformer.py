"""Dense decoder-only transformer (GQA + RoPE): starcoder2-3b, phi4-mini,
internlm2-1.8b, deepseek-7b — and the base machinery reused by the MoE, VLM,
enc-dec and hybrid families."""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.distributed.sharding import shard
from repro.models import layers as L
from repro.models.common import ModelConfig, dense_param, init_stacked, stack_axes
from repro.obs.registry import repeated


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def init_attn(rng, cfg: ModelConfig):
    d, H, KV, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    ks = jax.random.split(rng, 4)
    params = {
        "wq": dense_param(ks[0], (d, H * hd)),
        "wk": dense_param(ks[1], (d, KV * hd)),
        "wv": dense_param(ks[2], (d, KV * hd)),
        "wo": dense_param(ks[3], (H * hd, d), scale=(H * hd) ** -0.5),
    }
    axes = {
        "wq": ("embed", "heads"),
        "wk": ("embed", "kv_heads"),
        "wv": ("embed", "kv_heads"),
        "wo": ("heads", "embed"),
    }
    return params, axes


def init_mlp(rng, cfg: ModelConfig, d_ff: Optional[int] = None):
    d, f = cfg.d_model, d_ff or cfg.d_ff
    ks = jax.random.split(rng, 3)
    if getattr(cfg, "mlp_type", "swiglu") == "gelu":
        params = {"w_up": dense_param(ks[0], (d, f)),
                  "w_down": dense_param(ks[1], (f, d), scale=f ** -0.5)}
        axes = {"w_up": ("embed", "mlp"), "w_down": ("mlp", "embed")}
    else:
        params = {"w_gate": dense_param(ks[0], (d, f)),
                  "w_up": dense_param(ks[1], (d, f)),
                  "w_down": dense_param(ks[2], (f, d), scale=f ** -0.5)}
        axes = {"w_gate": ("embed", "mlp"), "w_up": ("embed", "mlp"),
                "w_down": ("mlp", "embed")}
    return params, axes


def init_dense_layer(rng, cfg: ModelConfig):
    k1, k2 = jax.random.split(rng)
    attn, attn_ax = init_attn(k1, cfg)
    mlp, mlp_ax = init_mlp(k2, cfg)
    params = {"attn": attn, "mlp": mlp,
              "ln1": jnp.zeros((cfg.d_model,)), "ln2": jnp.zeros((cfg.d_model,))}
    axes = {"attn": attn_ax, "mlp": mlp_ax,
            "ln1": ("embed",), "ln2": ("embed",)}
    return params, axes


def init(rng, cfg: ModelConfig):
    k_emb, k_layers, k_head = jax.random.split(rng, 3)
    layer_p, layer_ax = init_dense_layer(k_layers, cfg)  # axes template
    stacked = init_stacked(k_layers, cfg.n_layers,
                           lambda r: init_dense_layer(r, cfg)[0])
    params = {
        "embed": dense_param(k_emb, (cfg.padded_vocab, cfg.d_model), scale=1.0),
        "layers": stacked,
        "ln_f": jnp.zeros((cfg.d_model,)),
        "lm_head": dense_param(k_head, (cfg.d_model, cfg.padded_vocab)),
    }
    axes = {
        "embed": ("vocab", "embed"),
        "layers": stack_axes(layer_ax),
        "ln_f": ("embed",),
        "lm_head": ("embed", "vocab"),
    }
    return params, axes


# ---------------------------------------------------------------------------
# blocks
# ---------------------------------------------------------------------------

def attn_block(p, cfg: ModelConfig, x, cos, sin, *, cache=None, cur_len=None,
               window=None):
    """Pre-norm GQA attention. cache=(k, v) (B, Lmax, KV, hd) -> decode."""
    eng = cfg.engine
    B, Lq, d = x.shape
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    xn = L.rmsnorm(x, p["ln1"], cfg.norm_eps)
    q = eng(xn, p["attn"]["wq"]).reshape(B, Lq, H, hd)
    k = eng(xn, p["attn"]["wk"]).reshape(B, Lq, KV, hd)
    v = eng(xn, p["attn"]["wv"]).reshape(B, Lq, KV, hd)
    q = L.apply_rope(q, cos, sin)
    k = L.apply_rope(k, cos, sin)
    q = shard(q, "batch", "seq", "heads", "head_dim")
    new_cache = None
    if cache is None:
        if cfg.expand_kv and KV < H:
            # replicate KV heads across their G-groups so the score blocks
            # shard over all H q-heads (model axis) instead of only KV
            k = jnp.repeat(k, H // KV, axis=2)
            v = jnp.repeat(v, H // KV, axis=2)
            k = shard(k, "batch", "seq", "heads", "head_dim")
            v = shard(v, "batch", "seq", "heads", "head_dim")
        out = L.attention_flash(q, k, v, causal=True, window=window,
                                q_chunk=cfg.q_chunk, kv_chunk=cfg.kv_chunk,
                                engine=eng)
    else:
        # The cache is sized min(max_len, window): for windowed attention it
        # is a ring buffer (slot = (pos) mod window); otherwise a plain
        # append-at-position buffer.  Ring semantics: once full, every slot
        # is within the window, so no extra window mask is needed.
        kc, vc = cache
        cache_len = kc.shape[1]
        # cur_len is () or (B,) (per-slot continuous batching); the row
        # write and the validity mask are per slot either way
        valid_len = jnp.minimum(cur_len, cache_len)
        kc = L.cache_update_row(kc, k, cur_len)
        vc = L.cache_update_row(vc, v, cur_len)
        new_cache = (kc, vc)
        out = L.attention_decode(q, kc, vc, valid_len, window=None,
                                 engine=eng)
    out = eng(out.reshape(B, Lq, H * hd), p["attn"]["wo"])
    return x + out, new_cache


def mlp_block(p, cfg: ModelConfig, x):
    eng = cfg.engine
    xn = L.rmsnorm(x, p["ln2"], cfg.norm_eps)
    if getattr(cfg, "mlp_type", "swiglu") == "gelu":
        out = L.gelu_mlp(xn, p["mlp"]["w_up"], p["mlp"]["w_down"], eng)
    else:
        out = L.swiglu(xn, p["mlp"]["w_gate"], p["mlp"]["w_up"],
                       p["mlp"]["w_down"], eng)
    return x + out


def dense_layer(p, cfg, x, cos, sin, cache=None, cur_len=None):
    x, new_cache = attn_block(p, cfg, x, cos, sin, cache=cache,
                              cur_len=cur_len, window=cfg.window)
    x = mlp_block(p, cfg, x)
    return x, new_cache


# ---------------------------------------------------------------------------
# layer-stack scan with remat blocks
# ---------------------------------------------------------------------------

def scan_layers(body, stacked_params, x, xs=None, *, n_layers: int,
                remat_block: int = 1):
    """scan ``body(layer_params, x, layer_xs) -> (x, ys)`` over the stacked
    layer dim, rematerializing every ``remat_block`` layers.  Emulation
    counts the body records while it traces stand for every layer
    (``registry.repeated``)."""
    rb = max(1, remat_block)
    assert n_layers % rb == 0, (n_layers, rb)

    def one(carry, inputs):
        lp, lxs = inputs
        return body(lp, carry, lxs)

    if rb == 1:
        step = jax.checkpoint(one)
        with repeated(n_layers):
            x, ys = lax.scan(step, x, (stacked_params, xs), length=n_layers)
        return x, ys

    nb = n_layers // rb
    blocked = jax.tree.map(
        lambda a: a.reshape(nb, rb, *a.shape[1:]), stacked_params)
    xs_b = None if xs is None else jax.tree.map(
        lambda a: a.reshape(nb, rb, *a.shape[1:]), xs)

    @jax.checkpoint
    def block(carry, inputs):
        bp, bxs = inputs
        with repeated(rb):
            return lax.scan(one, carry, (bp, bxs), length=rb)

    with repeated(nb):
        x, ys = lax.scan(block, x, (blocked, xs_b), length=nb)
    ys = jax.tree.map(lambda a: a.reshape(-1, *a.shape[2:]), ys)
    return x, ys


# ---------------------------------------------------------------------------
# full-sequence forward (training / prefill)
# ---------------------------------------------------------------------------

def forward(params, cfg: ModelConfig, tokens: jax.Array,
            positions: Optional[jax.Array] = None) -> jax.Array:
    B, Lq = tokens.shape
    x = L.embed_tokens(tokens, params["embed"], cfg.compute_dtype)
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(Lq, dtype=jnp.int32), (B, Lq))
    cos, sin = L.rope_cos_sin(positions, cfg.hd, cfg.rope_theta)

    def body(lp, x, _):
        x, _ = dense_layer(lp, cfg, x, cos, sin)
        return x, None

    x, _ = scan_layers(body, params["layers"], x, n_layers=cfg.n_layers,
                       remat_block=cfg.remat_block)
    x = L.rmsnorm(x, params["ln_f"], cfg.norm_eps)
    return L.logits_head(x, params["lm_head"], cfg.engine)


# ---------------------------------------------------------------------------
# decode (serving)
# ---------------------------------------------------------------------------

def init_cache(cfg: ModelConfig, batch: int, max_len: int):
    KV, hd = cfg.n_kv_heads, cfg.hd
    cache_len = min(max_len, cfg.window) if cfg.window else max_len
    shape = (cfg.n_layers, batch, cache_len, KV, hd)
    k = jnp.zeros(shape, jnp.bfloat16)
    v = jnp.zeros(shape, jnp.bfloat16)
    k = shard(k, "layers", "cache_batch", None, "cache_heads", "cache_hd")
    v = shard(v, "layers", "cache_batch", None, "cache_heads", "cache_hd")
    return {"k": k, "v": v}


def cache_axes(cfg: ModelConfig):
    ax = ("layers", "cache_batch", None, "cache_heads", "cache_hd")
    return {"k": ax, "v": ax}


def decode_step(params, cfg: ModelConfig, cache, tokens: jax.Array,
                cur_len: jax.Array):
    """One-token decode: tokens (B, 1) at absolute position cur_len-1.

    Returns (logits (B, 1, vocab), new_cache).  For windowed attention the
    cache is a rolling buffer of size window (index modulo window).
    ``cur_len`` is a scalar (all slots in lock-step) or a (B,) vector
    (continuous batching: each slot decodes at its own position).
    """
    B = tokens.shape[0]
    x = L.embed_tokens(tokens, params["embed"], cfg.compute_dtype)
    pos = L.decode_positions(cur_len, B)
    cos, sin = L.rope_cos_sin(pos, cfg.hd, cfg.rope_theta)

    def body(x, inputs):
        lp, kc, vc = inputs
        x, new_kv = dense_layer(lp, cfg, x, cos, sin, cache=(kc, vc),
                                cur_len=cur_len)
        return x, new_kv

    with repeated(cfg.n_layers):
        x, (k_new, v_new) = lax.scan(
            body, x, (params["layers"], cache["k"], cache["v"]),
            length=cfg.n_layers)
    x = L.rmsnorm(x, params["ln_f"], cfg.norm_eps)
    logits = L.logits_head(x, params["lm_head"], cfg.engine)
    return logits, {"k": k_new, "v": v_new}
