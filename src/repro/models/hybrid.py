"""RecurrentGemma / Griffin hybrid: RG-LRU recurrent blocks + local (sliding
window) attention, pattern (R, R, A) — recurrentgemma-9b.

Layers come in two types, so the stack is scanned over homogeneous *pattern
blocks* (each holding 2 stacked recurrent layers + 1 attention layer); the
remainder layers (38 = 12*3 + 2) form an unrolled tail.  Like the SSM, the
recurrent state is constant-size, so this family runs ``long_500k``.

RG-LRU recurrence (Griffin eq. 4-6):
    r_t = sigmoid(W_a x_t + b_a)             # recurrence gate
    i_t = sigmoid(W_x x_t + b_x)             # input gate
    a_t = exp(c * softplus(Lambda) * (-r_t)) # in (0, 1), c = 8
    h_t = a_t * h_{t-1} + sqrt(1 - a_t^2) * (i_t * x_t)

Computed with an associative scan over the diagonal linear recurrence
(log-space coefficients for stability at 500k steps).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.distributed.sharding import shard
from repro.models import layers as L
from repro.models import transformer as T
from repro.models.common import ModelConfig, dense_param, init_stacked, stack_axes
from repro.obs.registry import repeated

_LRU_C = 8.0


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def init_recurrent_layer(rng, cfg: ModelConfig):
    d, w = cfg.d_model, cfg.lru_width or cfg.d_model
    ks = jax.random.split(rng, 6)
    # Lambda init so a^c in [0.9, 0.999] at r=1 (Griffin appendix)
    u = jax.random.uniform(ks[4], (w,), minval=0.9 ** 2, maxval=0.999 ** 2)
    lam = jnp.log(jnp.expm1(-jnp.log(u) / (2 * _LRU_C)))  # softplus^-1
    params = {
        "w_x": dense_param(ks[0], (d, w)),           # conv branch in-proj
        "w_gate": dense_param(ks[1], (d, w)),        # gate branch (GeLU)
        "conv_w": dense_param(ks[2], (4, w), scale=0.5),
        "conv_b": jnp.zeros((w,)),
        "lru_a": dense_param(ks[3], (w, w), scale=w ** -0.5),  # W_a (diag-ish)
        "lru_a_b": jnp.zeros((w,)),
        "lru_x_b": jnp.zeros((w,)),
        "lambda": lam,
        "w_out": dense_param(ks[5], (w, d), scale=w ** -0.5),
        "ln": jnp.zeros((d,)),
    }
    axes = {
        "w_x": ("embed", "mlp"), "w_gate": ("embed", "mlp"),
        "conv_w": ("conv", "mlp"), "conv_b": ("mlp",),
        "lru_a": ("mlp", None), "lru_a_b": ("mlp",), "lru_x_b": ("mlp",),
        "lambda": ("mlp",),
        "w_out": ("mlp", "embed"), "ln": ("embed",),
    }
    return params, axes


def init_block(rng, cfg: ModelConfig):
    """One pattern block: the R-layers (stacked) + one attention layer, each
    followed by its MLP."""
    n_r = sum(1 for c in cfg.pattern if c == "R")
    ks = jax.random.split(rng, 4)
    _, r_ax = init_recurrent_layer(ks[0], cfg)
    r_stack = init_stacked(ks[0], n_r, lambda r: init_recurrent_layer(r, cfg)[0])
    r_mlp_stack = init_stacked(ks[1], n_r, lambda r: _init_mlp_with_ln(r, cfg)[0])
    _, mlp_ax = _init_mlp_with_ln(ks[1], cfg)
    attn, attn_ax = T.init_dense_layer(ks[2], cfg)
    params = {"r_layers": r_stack, "r_mlps": r_mlp_stack, "attn_layer": attn}
    axes = {"r_layers": stack_axes(r_ax), "r_mlps": stack_axes(mlp_ax),
            "attn_layer": attn_ax}
    return params, axes


def _init_mlp_with_ln(rng, cfg):
    mlp, mlp_ax = T.init_mlp(rng, cfg)
    return ({"mlp": mlp, "ln2": jnp.zeros((cfg.d_model,))},
            {"mlp": mlp_ax, "ln2": ("embed",)})


def init(rng, cfg: ModelConfig):
    k_emb, k_blocks, k_tail = jax.random.split(rng, 3)
    _, block_ax = init_block(k_blocks, cfg)
    nb = cfg.n_pattern_blocks
    blocks = init_stacked(k_blocks, nb, lambda r: init_block(r, cfg)[0])
    # tail: remaining R layers (with MLPs)
    n_tail = cfg.n_tail_layers
    _, r_ax = init_recurrent_layer(k_tail, cfg)
    _, m_ax = _init_mlp_with_ln(k_tail, cfg)
    tail_r = init_stacked(k_tail, max(n_tail, 1),
                          lambda r: init_recurrent_layer(r, cfg)[0])
    tail_m = init_stacked(k_tail, max(n_tail, 1),
                          lambda r: _init_mlp_with_ln(r, cfg)[0])
    params = {
        "embed": dense_param(k_emb, (cfg.padded_vocab, cfg.d_model), scale=1.0),
        "blocks": blocks,
        "tail_r": tail_r, "tail_m": tail_m,
        "ln_f": jnp.zeros((cfg.d_model,)),
    }
    axes = {
        "embed": ("vocab", "embed"),
        "blocks": stack_axes(block_ax),
        "tail_r": stack_axes(r_ax), "tail_m": stack_axes(m_ax),
        "ln_f": ("embed",),
    }
    return params, axes


# ---------------------------------------------------------------------------
# RG-LRU
# ---------------------------------------------------------------------------

def _lru_coeffs(p, x):
    """Per-step log-decay and input; x (Bb, L, w) -> (log_a, v) both f32."""
    xf = x.astype(jnp.float32)
    r = jax.nn.sigmoid(xf @ p["lru_a"].astype(jnp.float32) +
                       p["lru_a_b"].astype(jnp.float32))
    i = jax.nn.sigmoid(xf + p["lru_x_b"].astype(jnp.float32))
    log_a = -_LRU_C * jax.nn.softplus(p["lambda"].astype(jnp.float32)) * r
    a2 = jnp.exp(2.0 * log_a)
    v = jnp.sqrt(jnp.maximum(1.0 - a2, 1e-12)) * (i * xf)
    return log_a, v


def rg_lru(p, x, h0: Optional[jax.Array] = None):
    """Diagonal linear recurrence h_t = a_t h_{t-1} + v_t via associative scan.

    x (Bb, L, w); h0 (Bb, w) or None.  Returns (h (Bb, L, w), h_last)."""
    log_a, v = _lru_coeffs(p, x)
    if h0 is not None:
        # fold the initial state into the first input
        v = v.at[:, 0].add(jnp.exp(log_a[:, 0]) * h0.astype(jnp.float32))

    def combine(c1, c2):
        la1, v1 = c1
        la2, v2 = c2
        return la1 + la2, v1 * jnp.exp(la2) + v2

    la_all, h = lax.associative_scan(combine, (log_a, v), axis=1)
    return h.astype(x.dtype), h[:, -1]


def rg_lru_step(p, x1, h):
    """Single-step: x1 (Bb, w), h (Bb, w) -> (y, h_new)."""
    log_a, v = _lru_coeffs(p, x1[:, None])
    h_new = jnp.exp(log_a[:, 0]) * h.astype(jnp.float32) + v[:, 0]
    return h_new.astype(x1.dtype), h_new


def recurrent_block(p, cfg: ModelConfig, x, *, conv_state=None, lru_state=None):
    """Griffin recurrent block. Returns (out, new_conv, new_lru)."""
    eng = cfg.engine
    Bb, Lq, _ = x.shape
    xn = L.rmsnorm(x, p["ln"], cfg.norm_eps)
    branch = eng(xn, p["w_x"])
    gate = jax.nn.gelu(eng(xn, p["w_gate"]))
    conv_w = p["conv_w"].astype(branch.dtype)
    new_conv = None
    if conv_state is None:
        acc = branch * conv_w[-1]
        for i in range(3):
            shift = 3 - i
            acc = acc + jnp.pad(branch, ((0, 0), (shift, 0), (0, 0))
                                )[:, :Lq] * conv_w[i]
        conv_out = acc + p["conv_b"].astype(acc.dtype)
        y, h_last = rg_lru(p, conv_out, lru_state)
        new_lru = h_last
    else:
        window = jnp.concatenate([conv_state, branch], axis=1)
        acc = jnp.einsum("btc,tc->bc", window, conv_w)
        conv_out = acc + p["conv_b"].astype(acc.dtype)
        y1, new_lru = rg_lru_step(p, conv_out, lru_state)
        y = y1[:, None]
        new_conv = window[:, 1:]
    y = shard(y * gate, "batch", "seq", "mlp")
    return x + eng(y, p["w_out"]), new_conv, new_lru


def _mlp(p, cfg, x):
    xn = L.rmsnorm(x, p["ln2"], cfg.norm_eps)
    return x + L.gelu_mlp(xn, p["mlp"]["w_up"], p["mlp"]["w_down"], cfg.engine)


# ---------------------------------------------------------------------------
# full model
# ---------------------------------------------------------------------------

def _block_fwd(bp, cfg, x, cos, sin, caches=None, cur_len=None):
    """One (R, R, A) pattern block.  caches: dict with 'conv' (n_r, ...),
    'lru' (n_r, ...), 'k'/'v' attention cache — or None for training."""
    n_r = sum(1 for c in cfg.pattern if c == "R")
    new_caches = {}
    for i in range(n_r):
        rp = jax.tree.map(lambda a: a[i], bp["r_layers"])
        mp = jax.tree.map(lambda a: a[i], bp["r_mlps"])
        conv = caches["conv"][i] if caches else None
        lru = caches["lru"][i] if caches else None
        x, conv_n, lru_n = recurrent_block(rp, cfg, x, conv_state=conv,
                                           lru_state=lru)
        x = _mlp(mp, cfg, x)
        if caches:
            new_caches.setdefault("conv", []).append(conv_n)
            new_caches.setdefault("lru", []).append(lru_n)
    attn_cache = (caches["k"], caches["v"]) if caches else None
    x, attn_new = T.attn_block(bp["attn_layer"], cfg, x, cos, sin,
                               cache=attn_cache, cur_len=cur_len,
                               window=cfg.window)
    x = _mlp({"mlp": bp["attn_layer"]["mlp"], "ln2": bp["attn_layer"]["ln2"]},
             cfg, x)
    if caches:
        new_caches = {"conv": jnp.stack(new_caches["conv"]),
                      "lru": jnp.stack(new_caches["lru"]),
                      "k": attn_new[0], "v": attn_new[1]}
    return x, new_caches or None


def forward(params, cfg: ModelConfig, tokens: jax.Array, positions=None):
    B, Lq = tokens.shape
    x = L.embed_tokens(tokens, params["embed"], cfg.compute_dtype)
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(Lq, dtype=jnp.int32), (B, Lq))
    cos, sin = L.rope_cos_sin(positions, cfg.hd, cfg.rope_theta)

    def body(bp, x, _):
        x, _ = _block_fwd(bp, cfg, x, cos, sin)
        return x, None

    x, _ = T.scan_layers(body, params["blocks"], x,
                         n_layers=cfg.n_pattern_blocks,
                         remat_block=cfg.remat_block)
    for i in range(cfg.n_tail_layers):
        rp = jax.tree.map(lambda a: a[i], params["tail_r"])
        mp = jax.tree.map(lambda a: a[i], params["tail_m"])
        x, _, _ = recurrent_block(rp, cfg, x)
        x = _mlp(mp, cfg, x)
    x = L.rmsnorm(x, params["ln_f"], cfg.norm_eps)
    return L.logits_head(x, params["embed"].T, cfg.engine)


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------

def init_cache(cfg: ModelConfig, batch: int, max_len: int):
    w = cfg.lru_width or cfg.d_model
    n_r = sum(1 for c in cfg.pattern if c == "R")
    nb = cfg.n_pattern_blocks
    KV, hd = cfg.n_kv_heads, cfg.hd
    attn_len = min(max_len, cfg.window) if cfg.window else max_len
    cache = {
        "conv": shard(jnp.zeros((nb, n_r, batch, 3, w), jnp.bfloat16),
                      "layers", None, "cache_batch", None, "mlp"),
        "lru": shard(jnp.zeros((nb, n_r, batch, w), jnp.float32),
                     "layers", None, "cache_batch", "mlp"),
        "k": shard(jnp.zeros((nb, batch, attn_len, KV, hd), jnp.bfloat16),
                   "layers", "cache_batch", None, "cache_heads", "cache_hd"),
        "v": shard(jnp.zeros((nb, batch, attn_len, KV, hd), jnp.bfloat16),
                   "layers", "cache_batch", None, "cache_heads", "cache_hd"),
        "tail_conv": shard(jnp.zeros((max(cfg.n_tail_layers, 1), batch, 3, w),
                                     jnp.bfloat16),
                           "layers", "cache_batch", None, "mlp"),
        "tail_lru": shard(jnp.zeros((max(cfg.n_tail_layers, 1), batch, w),
                                    jnp.float32),
                          "layers", "cache_batch", "mlp"),
    }
    return cache


def cache_axes(cfg: ModelConfig):
    return {
        "conv": ("layers", None, "cache_batch", None, "mlp"),
        "lru": ("layers", None, "cache_batch", "mlp"),
        "k": ("layers", "cache_batch", None, "cache_heads", "cache_hd"),
        "v": ("layers", "cache_batch", None, "cache_heads", "cache_hd"),
        "tail_conv": ("layers", "cache_batch", None, "mlp"),
        "tail_lru": ("layers", "cache_batch", "mlp"),
    }


def decode_step(params, cfg: ModelConfig, cache, tokens: jax.Array,
                cur_len: jax.Array):
    B = tokens.shape[0]
    x = L.embed_tokens(tokens, params["embed"], cfg.compute_dtype)
    pos = L.decode_positions(cur_len, B)
    cos, sin = L.rope_cos_sin(pos, cfg.hd, cfg.rope_theta)

    def body(x, inputs):
        bp, bc = inputs
        x, nc = _block_fwd(bp, cfg, x, cos, sin, caches=bc, cur_len=cur_len)
        return x, nc

    block_caches = {k: cache[k] for k in ("conv", "lru", "k", "v")}
    with repeated(cfg.n_pattern_blocks):
        x, new_bc = lax.scan(body, x, (params["blocks"], block_caches),
                             length=cfg.n_pattern_blocks)
    tail_conv, tail_lru = [], []
    for i in range(cfg.n_tail_layers):
        rp = jax.tree.map(lambda a: a[i], params["tail_r"])
        mp = jax.tree.map(lambda a: a[i], params["tail_m"])
        x, conv_n, lru_n = recurrent_block(
            rp, cfg, x, conv_state=cache["tail_conv"][i].astype(x.dtype),
            lru_state=cache["tail_lru"][i])
        x = _mlp(mp, cfg, x)
        tail_conv.append(conv_n.astype(jnp.bfloat16))
        tail_lru.append(lru_n)
    x = L.rmsnorm(x, params["ln_f"], cfg.norm_eps)
    logits = L.logits_head(x, params["embed"].T, cfg.engine)
    new_cache = dict(new_bc)
    new_cache["tail_conv"] = (jnp.stack(tail_conv) if tail_conv
                              else cache["tail_conv"])
    new_cache["tail_lru"] = (jnp.stack(tail_lru) if tail_lru
                             else cache["tail_lru"])
    return logits, new_cache
