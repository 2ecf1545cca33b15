"""Plain float32 forward pass of a dense GQA decoder (InternLM2's block).

One sequence, every position, no cache, no batching, no kernels: token
embedding; per layer pre-norm RMSNorm, grouped-query attention with
rotary embeddings (rotate-half, base ``rope_theta``) and a causal mask,
a SwiGLU MLP, residual adds; a final RMSNorm and the output head.  Query
head h reads key/value head h // (heads / kv_heads).

Every contraction goes through one function, ``dot``.  The default
computes in float32 at precision HIGHEST (a TPU drops a float32 product
to one bfloat16 pass otherwise).  The check's controls round both
operands of every contraction on a per-row and per-column scale, to int8
(:func:`int8_dot`) or float8 e4m3 (:func:`fp8_dot`); :func:`bf16_dot`
rounds them to bfloat16, a one-pass bfloat16 contraction.

The parameter tree is the one the benchmark makes (``bench/drivers/
serve.py``).  Its norm gains are stored as ``1 + w`` offsets, the layout
of the program's parameters; the gain applied is ``1 + w``.  Nothing here
comes from the program under test.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def exact_dot(eq: str, a, b, ca: int, cb: int):
    """float32 contraction at precision HIGHEST (``ca``/``cb`` name the
    contracted axes, for the control's quantization)."""
    return jnp.einsum(eq, a, b, precision=HIGHEST)


def _int8(x, axis: int):
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale > 0, scale, 1.0)
    return jnp.round(x / scale) * scale


def int8_dot(eq: str, a, b, ca: int, cb: int):
    """Both operands rounded to int8, each on the scale of its own row or
    column along the contracted axis (W8A8)."""
    return jnp.einsum(eq, _int8(a, ca), _int8(b, cb), precision=HIGHEST)


def bf16_dot(eq: str, a, b, ca: int, cb: int):
    """Both operands rounded to bfloat16, products summed in float32: a
    one-pass bfloat16 contraction on any backend."""
    def rnd(x):
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    return jnp.einsum(eq, rnd(a), rnd(b), precision=HIGHEST)


def _fp8(x, axis: int):
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 448.0
    scale = jnp.where(scale > 0, scale, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) \
        * scale


def fp8_dot(eq: str, a, b, ca: int, cb: int):
    """Both operands rounded to float8 (e4m3), each on the scale of its
    own row or column along the contracted axis."""
    return jnp.einsum(eq, _fp8(a, ca), _fp8(b, cb), precision=HIGHEST)


def rmsnorm(x, w, eps: float):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * (1.0 + w)


def rope(x, pos, theta: float):
    """x (L, heads, hd), rotate-half convention."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * freqs
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def forward(params, dims: dict, tokens, dot=exact_dot):
    """Logits (L, vocab) of one token sequence (L,)."""
    d, H, KV = dims["d"], dims["heads"], dims["kv_heads"]
    hd, eps, theta = d // H, dims["eps"], dims["rope_theta"]
    G = H // KV
    L = tokens.shape[0]
    x = params["embed"][tokens].astype(jnp.float32)
    pos = jnp.arange(L)
    causal = pos[None, :] <= pos[:, None]                     # (Lq, Lk)
    for i in range(dims["layers"]):
        lp = jax.tree.map(lambda a: a[i], params["layers"])
        at, mlp = lp["attn"], lp["mlp"]
        h = rmsnorm(x, lp["ln1"], eps)
        q = dot("ld,dp->lp", h, at["wq"], 1, 0).reshape(L, H, hd)
        k = dot("ld,dp->lp", h, at["wk"], 1, 0).reshape(L, KV, hd)
        v = dot("ld,dp->lp", h, at["wv"], 1, 0).reshape(L, KV, hd)
        q, k = rope(q, pos, theta), rope(k, pos, theta)
        qg = q.reshape(L, KV, G, hd) * hd ** -0.5
        s = dot("lkgd,mkd->kglm", qg, k, 3, 2)
        s = jnp.where(causal[None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        o = dot("kglm,mkd->lkgd", p, v, 3, 0).reshape(L, H * hd)
        x = x + dot("lp,pd->ld", o, at["wo"], 1, 0)
        h = rmsnorm(x, lp["ln2"], eps)
        g = jax.nn.silu(dot("ld,df->lf", h, mlp["w_gate"], 1, 0))
        u = dot("ld,df->lf", h, mlp["w_up"], 1, 0)
        x = x + dot("lf,fd->ld", g * u, mlp["w_down"], 1, 0)
    x = rmsnorm(x, params["ln_f"], eps)
    return dot("ld,dv->lv", x, params["lm_head"][:, :dims["vocab"]], 1, 0)


def served_gaps(params, dims: dict, tokens, targets, dot=exact_dot):
    """For each position, how far the reference logit of ``targets`` lies
    below the reference's best there; with ``dot`` other than the
    reference's, also the gap of the token that ``dot`` puts first.
    Returns (gaps of targets, gaps of the other precision's argmax)."""
    ref = forward(params, dims, tokens)
    best = jnp.max(ref, axis=-1)
    served = jnp.take_along_axis(ref, targets[:, None], axis=-1)[:, 0]
    if dot is exact_dot:
        return best - served, None
    other = jnp.argmax(forward(params, dims, tokens, dot), axis=-1)
    picked = jnp.take_along_axis(ref, other[:, None], axis=-1)[:, 0]
    return best - served, best - picked
