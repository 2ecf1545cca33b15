"""Mamba2 (SSD — state-space duality form): mamba2-780m.

The SSD form computes the selective-state-space recurrence as *chunked
matmuls* (intra-chunk quadratic term + inter-chunk state carry), which is
what makes it MXU-friendly — and GEMM-dominated, so the Ozaki engine applies
to its projections like any dense layer.

Layer i/o contract matches the dense transformer so the train/serve steps
are shared: ``forward(params, cfg, tokens)`` and
``decode_step(params, cfg, cache, tokens, cur_len)`` with a *constant-size*
cache (conv window + SSM state) — this is the sub-quadratic family that runs
the ``long_500k`` cell.
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


def _dims(cfg: ModelConfig):
    d_inner = cfg.expand * cfg.d_model
    n_heads = d_inner // cfg.ssm_headdim
    return d_inner, n_heads, cfg.ssm_headdim, cfg.d_state


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def init_mamba_layer(rng, cfg: ModelConfig):
    d = cfg.d_model
    d_inner, H, P, N = _dims(cfg)
    conv_dim = d_inner + 2 * N          # x, B, C all pass through the conv
    ks = jax.random.split(rng, 6)
    params = {
        # order: [z (gate), x, B, C, dt]
        "w_in": dense_param(ks[0], (d, 2 * d_inner + 2 * N + H)),
        "conv_w": dense_param(ks[1], (cfg.d_conv, conv_dim), scale=0.5),
        "conv_b": jnp.zeros((conv_dim,)),
        "A_log": jnp.log(jnp.linspace(1.0, 16.0, H)),    # A = -exp(A_log) < 0
        "dt_bias": jnp.log(jnp.expm1(
            jnp.exp(jax.random.uniform(ks[2], (H,)) * 3.0 - 4.6))),  # ~[1e-3,1e-1]
        "D": jnp.ones((H,)),
        "norm_w": jnp.zeros((d_inner,)),
        "w_out": dense_param(ks[3], (d_inner, d), scale=d_inner ** -0.5),
        "ln": jnp.zeros((d,)),
    }
    axes = {
        "w_in": ("embed", "mlp"),
        "conv_w": ("conv", "mlp"),
        "conv_b": ("mlp",),
        "A_log": ("heads",),
        "dt_bias": ("heads",),
        "D": ("heads",),
        "norm_w": ("mlp",),
        "w_out": ("mlp", "embed"),
        "ln": ("embed",),
    }
    return params, axes


def init(rng, cfg: ModelConfig):
    k_emb, k_layers = jax.random.split(rng)
    _, layer_ax = init_mamba_layer(k_layers, cfg)
    stacked = init_stacked(k_layers, cfg.n_layers,
                           lambda r: init_mamba_layer(r, cfg)[0])
    params = {
        "embed": dense_param(k_emb, (cfg.padded_vocab, cfg.d_model), scale=1.0),
        "layers": stacked,
        "ln_f": jnp.zeros((cfg.d_model,)),
    }
    axes = {
        "embed": ("vocab", "embed"),
        "layers": stack_axes(layer_ax),
        "ln_f": ("embed",),
    }
    return params, axes


# ---------------------------------------------------------------------------
# SSD core — chunked scan (training / prefill)
# ---------------------------------------------------------------------------

def ssd_chunked(x, dt, A, B, C, chunk: int):
    """Chunked SSD: y[t] = C[t] . h[t];  h[t] = exp(dt_t A) h[t-1] + dt_t B[t] (x) x[t].

    x:  (Bb, L, H, P)   per-head inputs
    dt: (Bb, L, H)      discretization steps (post-softplus), > 0
    A:  (H,)            negative per-head decay rates
    B:  (Bb, L, N)      input projections  (single group, shared across heads)
    C:  (Bb, L, N)      output projections
    Returns y: (Bb, L, H, P), final_state: (Bb, H, P, N).
    """
    Bb, Lq, H, P = x.shape
    N = B.shape[-1]
    Q = min(chunk, Lq)
    nc = -(-Lq // Q)
    pad = nc * Q - Lq
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        B = jnp.pad(B, ((0, 0), (0, pad), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0)))

    xc = x.reshape(Bb, nc, Q, H, P)
    dtc = dt.reshape(Bb, nc, Q, H)
    Bc = B.reshape(Bb, nc, Q, N)
    Cc = C.reshape(Bb, nc, Q, N)

    dA = dtc * A  # (Bb, nc, Q, H), negative
    cum = jnp.cumsum(dA, axis=2)                       # l_q = sum_{s<=q} dt_s A
    seg_total = cum[:, :, -1, :]                       # (Bb, nc, H)

    # intra-chunk (the "quadratic attention" term of SSD):
    #   scores[b,c,h,q,s] = (C_q . B_s) * exp(l_q - l_s) * dt_s,  s <= q
    cb = jnp.einsum("bcqn,bcsn->bcqs", Cc, Bc,
                    preferred_element_type=jnp.float32)
    decay = cum[:, :, :, None, :] - cum[:, :, None, :, :]   # (b,c,q,s,h)
    causal = jnp.tril(jnp.ones((Q, Q), bool))
    decay = jnp.where(causal[None, None, :, :, None], decay, -jnp.inf)
    w = jnp.exp(decay) * dtc[:, :, None, :, :]              # (b,c,q,s,h)
    scores = cb[..., None] * w                               # (b,c,q,s,h)
    y_intra = jnp.einsum("bcqsh,bcshp->bcqhp", scores.astype(x.dtype), xc,
                         preferred_element_type=jnp.float32)

    # chunk input states: S_c = sum_s exp(l_Q - l_s) dt_s B_s (x) x_s
    w_state = jnp.exp(seg_total[:, :, None, :] - cum) * dtc  # (b,c,s,h)
    S = jnp.einsum("bcsh,bcsn,bcshp->bchpn",
                   w_state.astype(x.dtype), Bc.astype(x.dtype), xc,
                   preferred_element_type=jnp.float32)       # (b,c,h,p,n)

    # inter-chunk recurrence over c:  h_c_in = exp(seg_total) h_{c-1}_in + S_{c-1}
    def carry_fn(h, inputs):
        S_c, g_c = inputs  # state contribution of chunk c, total decay of c
        h_out = h
        h = h * jnp.exp(g_c)[:, :, None, None] + S_c
        return h, h_out    # h_out = state at *entry* of chunk c

    S_sw = jnp.moveaxis(S, 1, 0)                # (nc, b, h, p, n)
    g_sw = jnp.moveaxis(seg_total, 1, 0)        # (nc, b, h)
    h0 = jnp.zeros((Bb, H, P, N), jnp.float32)
    h_final, h_entry = lax.scan(carry_fn, h0, (S_sw, g_sw))
    h_entry = jnp.moveaxis(h_entry, 0, 1)       # (b, nc, h, p, n)

    # inter-chunk output: y_inter[q] = exp(l_q) * C_q . h_entry
    y_inter = jnp.einsum("bcqn,bchpn->bcqhp", Cc.astype(x.dtype),
                         h_entry.astype(x.dtype),
                         preferred_element_type=jnp.float32)
    y_inter = y_inter * jnp.exp(cum)[..., None]

    y = (y_intra + y_inter).reshape(Bb, nc * Q, H, P)[:, :Lq]
    return y.astype(x.dtype), h_final


def ssd_step(x, dt, A, B, C, h):
    """Single-token SSD update.  x (Bb,H,P); dt (Bb,H); B,C (Bb,N);
    h (Bb,H,P,N) -> (y (Bb,H,P), h_new)."""
    dA = jnp.exp(dt * A)                                     # (Bb, H)
    dBx = jnp.einsum("bn,bhp->bhpn", B, x * dt[..., None])
    h = h * dA[:, :, None, None] + dBx
    y = jnp.einsum("bhpn,bn->bhp", h, C)
    return y.astype(x.dtype), h


# ---------------------------------------------------------------------------
# the Mamba2 block
# ---------------------------------------------------------------------------

def _split_proj(z, cfg):
    d_inner, H, P, N = _dims(cfg)
    zs = jnp.split(z, [d_inner, 2 * d_inner, 2 * d_inner + N,
                       2 * d_inner + 2 * N], axis=-1)
    return zs  # gate, x, B, C, dt_raw


def mamba_block(p, cfg: ModelConfig, u, *, conv_state=None, ssm_state=None):
    """u (Bb, L, d).  Full-sequence when states are None; single-step (L==1)
    decode otherwise.  Returns (out, new_conv_state, new_ssm_state)."""
    eng = cfg.engine
    d_inner, H, P, N = _dims(cfg)
    Bb, Lq, _ = u.shape
    un = L.rmsnorm(u, p["ln"], cfg.norm_eps)
    proj = eng(un, p["w_in"])
    gate, xbc_x, Bp, Cp, dt_raw = _split_proj(proj, cfg)
    xbc = jnp.concatenate([xbc_x, Bp, Cp], axis=-1)          # conv channels
    conv_w = p["conv_w"].astype(xbc.dtype)                   # (d_conv, conv_dim)

    new_conv = None
    if conv_state is None:
        # causal depthwise conv via shifted adds (d_conv is tiny, typ. 4)
        acc = xbc * conv_w[-1]
        for i in range(cfg.d_conv - 1):
            shift = cfg.d_conv - 1 - i
            acc = acc + jnp.pad(xbc, ((0, 0), (shift, 0), (0, 0))
                                )[:, :Lq] * conv_w[i]
        xbc = jax.nn.silu(acc + p["conv_b"].astype(acc.dtype))
    else:
        # conv_state: (Bb, d_conv-1, conv_dim) of past inputs
        window = jnp.concatenate([conv_state, xbc], axis=1)  # (Bb, d_conv, C)
        acc = jnp.einsum("btc,tc->bc", window, conv_w)[:, None]
        xbc = jax.nn.silu(acc + p["conv_b"].astype(acc.dtype))
        new_conv = window[:, 1:]

    x, Bp, Cp = jnp.split(xbc, [d_inner, d_inner + N], axis=-1)
    x = x.reshape(Bb, Lq, H, P)
    x = shard(x, "batch", "seq", "heads", None)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) +
                         p["dt_bias"].astype(jnp.float32))
    A = -jnp.exp(p["A_log"].astype(jnp.float32))

    if ssm_state is None:
        y, h_final = ssd_chunked(x, dt, A, Bp.astype(x.dtype),
                                 Cp.astype(x.dtype), cfg.chunk)
    else:
        y1, h_final = ssd_step(x[:, 0], dt[:, 0], A,
                               Bp[:, 0].astype(x.dtype),
                               Cp[:, 0].astype(x.dtype), ssm_state)
        y = y1[:, None]
    y = y + x * p["D"].astype(x.dtype)[None, None, :, None]
    y = y.reshape(Bb, Lq, d_inner)
    # gated RMSNorm (mamba2's norm-before-out with silu gate)
    y = L.rmsnorm(y, p["norm_w"], cfg.norm_eps) * jax.nn.silu(gate)
    y = shard(y, "batch", "seq", "mlp")
    out = eng(y, p["w_out"])
    return u + out, new_conv, h_final


# ---------------------------------------------------------------------------
# full model
# ---------------------------------------------------------------------------

def forward(params, cfg: ModelConfig, tokens: jax.Array,
            positions=None) -> jax.Array:
    x = L.embed_tokens(tokens, params["embed"], cfg.compute_dtype)

    def body(lp, x, _):
        x, _, _ = mamba_block(lp, cfg, x)
        return x, None

    x, _ = T.scan_layers(body, params["layers"], x, n_layers=cfg.n_layers,
                         remat_block=cfg.remat_block)
    x = L.rmsnorm(x, params["ln_f"], cfg.norm_eps)
    # tied embedding head
    return L.logits_head(x, params["embed"].T, cfg.engine)


def init_cache(cfg: ModelConfig, batch: int, max_len: int):
    """Constant-size state: conv window + SSM state per layer."""
    d_inner, H, P, N = _dims(cfg)
    conv_dim = d_inner + 2 * N
    conv = jnp.zeros((cfg.n_layers, batch, cfg.d_conv - 1, conv_dim),
                     jnp.bfloat16)
    ssm = jnp.zeros((cfg.n_layers, batch, H, P, N), jnp.float32)
    conv = shard(conv, "layers", "cache_batch", None, "mlp")
    ssm = shard(ssm, "layers", "cache_batch", "heads", None, None)
    return {"conv": conv, "ssm": ssm}


def cache_axes(cfg: ModelConfig):
    return {"conv": ("layers", "cache_batch", None, "mlp"),
            "ssm": ("layers", "cache_batch", "heads", None, None)}


def decode_step(params, cfg: ModelConfig, cache, tokens: jax.Array,
                cur_len: jax.Array):
    x = L.embed_tokens(tokens, params["embed"], cfg.compute_dtype)

    def body(x, inputs):
        lp, conv, ssm = inputs
        x, conv_n, ssm_n = mamba_block(lp, cfg, x, conv_state=conv.astype(x.dtype),
                                       ssm_state=ssm)
        return x, (conv_n.astype(conv.dtype), ssm_n)

    with repeated(cfg.n_layers):
        x, (conv_n, ssm_n) = lax.scan(
            body, x, (params["layers"], cache["conv"], cache["ssm"]),
            length=cfg.n_layers)
    x = L.rmsnorm(x, params["ln_f"], cfg.norm_eps)
    logits = L.logits_head(x, params["embed"].T, cfg.engine)
    return logits, {"conv": conv_n, "ssm": ssm_n}
