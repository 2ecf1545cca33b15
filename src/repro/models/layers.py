"""Layer library: norms, RoPE, GQA/flash attention, MLPs, embeddings.

All contractions route through ``cfg.engine`` (MatmulEngine), so any layer
can run its GEMMs through the paper's INT8 Ozaki emulation via
``--matmul_engine ozimmu_h-8:df32`` etc.
"""
from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.distributed.sharding import shard
from repro.obs.registry import repeated

NEG_INF = -1e30


def _edot(engine, lhs, rhs, dimension_numbers, out_dtype=None):
    """Batched contraction for the attention blocks.  With an ozimmu engine
    the score/output GEMMs (and their cotangents) run inside the INT8
    emulation as native batched ``dot_general``s.  For native specs — and
    for ``engine=None`` library use — this stays a plain lax.dot_general,
    bit-identical to the einsums it replaced: attention keeps its own
    mixed-precision discipline (f32 scores/probabilities feeding the online
    softmax and its backward), which an engine-dtype cast would truncate.
    The ``f32``/``f64`` reference specs ask for HIGHEST precision, which a
    TPU would otherwise drop to one bf16 pass."""
    if engine is None or not engine.is_ozimmu:
        precision = None if engine is None else engine.native_precision
        return lax.dot_general(lhs, rhs, dimension_numbers,
                               precision=precision,
                               preferred_element_type=out_dtype)
    return engine.dot_general(lhs, rhs, dimension_numbers,
                              out_dtype=out_dtype)


def rmsnorm(x: jax.Array, w: jax.Array, eps: float = 1e-5) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * (1.0 + w.astype(jnp.float32))).astype(dt)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope_cos_sin(positions: jax.Array, dim: int, theta: float
                 ) -> Tuple[jax.Array, jax.Array]:
    """positions (..., L) int32 -> cos/sin (..., L, dim//2) f32."""
    freqs = theta ** (-jnp.arange(0, dim // 2, dtype=jnp.float32) / (dim // 2))
    ang = positions[..., None].astype(jnp.float32) * freqs
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x (B, L, H, D); cos/sin (B, L, D/2) — rotate-half convention."""
    d2 = x.shape[-1] // 2
    x1, x2 = x[..., :d2], x[..., d2:]
    c = cos[:, :, None, :].astype(x.dtype)
    s = sin[:, :, None, :].astype(x.dtype)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------

def _scores_mask(q_pos, k_pos, causal: bool, window: Optional[int]):
    """(..., Lq, Lk) bool mask from absolute positions."""
    m = jnp.ones((q_pos.shape[-1], k_pos.shape[-1]), bool)
    if causal:
        m &= k_pos[None, :] <= q_pos[:, None]
    if window is not None:
        m &= k_pos[None, :] > q_pos[:, None] - window
    return m


def attention_flash(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: Optional[int] = None,
                    q_chunk: int = 1024, kv_chunk: int = 1024,
                    q_offset: int = 0, engine=None) -> jax.Array:
    """Chunked online-softmax (flash-style) GQA attention, pure JAX.

    q: (B, Lq, H, D); k, v: (B, Lk, KV, D/Dv) with H % KV == 0 (Dv may
    differ from D, e.g. MLA).  Memory: O(q_chunk * kv_chunk) score blocks
    instead of O(Lq * Lk) — in BOTH directions: the backward is a custom
    VJP that recomputes score blocks (true flash backward).  Without it,
    autodiff of the forward scan stacks per-block probability matrices as
    scan residuals — the full O(L^2) attention matrix in f32 (measured:
    4.3 GB/device/remat-block for the internlm2 train_4k cell).

    ``engine`` (a MatmulEngine, hashable, nondiff) routes the score and
    output contractions — forward AND the recomputed backward blocks —
    through ``engine.dot_general`` as batched-over-(B, KV) contractions.
    """
    return _flash(q, k, v, engine, bool(causal), window, int(q_chunk),
                  int(kv_chunk), int(q_offset))


def _flash_dims(q, k, v, q_chunk, kv_chunk):
    B, Lq, H, D = q.shape
    _, Lk, KV, _ = k.shape
    Dv = v.shape[-1]
    G = H // KV
    qc = min(q_chunk, Lq)
    kc = min(kv_chunk, Lk)
    nq, nk = -(-Lq // qc), -(-Lk // kc)
    return B, Lq, H, D, Lk, KV, Dv, G, qc, kc, nq, nk


def _flash_fwd_impl(q, k, v, engine, causal, window, q_chunk, kv_chunk,
                    q_offset):
    B, Lq, H, D, Lk, KV, Dv, G, qc, kc, nq, nk = _flash_dims(
        q, k, v, q_chunk, kv_chunk)
    q = jnp.pad(q, ((0, 0), (0, nq * qc - Lq), (0, 0), (0, 0)))
    k = jnp.pad(k, ((0, 0), (0, nk * kc - Lk), (0, 0), (0, 0)))
    v = jnp.pad(v, ((0, 0), (0, nk * kc - Lk), (0, 0), (0, 0)))
    scale = D ** -0.5
    qg = q.reshape(B, nq, qc, KV, G, D)
    kg = k.reshape(B, nk, kc, KV, D)
    vg = v.reshape(B, nk, kc, KV, Dv)

    def q_body(_, qi):
        qblk = qg[:, qi] * scale  # (B, qc, KV, G, D)
        q_pos = qi * qc + jnp.arange(qc) + q_offset

        def kv_body(carry, ki):
            m_run, l_run, acc = carry
            kblk = kg[:, ki]
            vblk = vg[:, ki]
            k_pos = ki * kc + jnp.arange(kc)
            # scores: einsum "bqkgd,bskd->bkgqs" as a (B, KV)-batched
            # dot_general (contract d) so an ozimmu engine can emulate it
            s = _edot(engine, qblk, kblk, (((4,), (3,)), ((0, 2), (0, 2))),
                      out_dtype=jnp.float32).transpose(0, 1, 3, 2, 4)
            mask = _scores_mask(q_pos, k_pos, causal, window)
            mask &= (k_pos < Lk)[None, :]
            s = jnp.where(mask[None, None, None], s, NEG_INF)
            m_new = jnp.maximum(m_run, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m_run - m_new)
            l_new = l_run * corr + p.sum(axis=-1)
            # output: einsum "bkgqs,bskd->bkgqd" (contract s)
            pv = _edot(engine, p.astype(v.dtype), vblk,
                       (((4,), (1,)), ((0, 1), (0, 2))),
                       out_dtype=jnp.float32)
            acc = acc * corr[..., None] + pv
            return (m_new, l_new, acc), None

        init = (jnp.full((B, KV, G, qc), NEG_INF, jnp.float32),
                jnp.zeros((B, KV, G, qc), jnp.float32),
                jnp.zeros((B, KV, G, qc, Dv), jnp.float32))
        with repeated(nk):
            (m_run, l_run, acc), _ = lax.scan(kv_body, init,
                                              jnp.arange(nk))
        out = acc / jnp.maximum(l_run, 1e-30)[..., None]
        # logsumexp per row; +inf on fully-masked (padding) rows so that
        # exp(s - lse) == 0 during backward recomputation
        lse = jnp.where(l_run > 0,
                        m_run + jnp.log(jnp.maximum(l_run, 1e-30)), jnp.inf)
        return None, (out, lse)  # (B, KV, G, qc, Dv), (B, KV, G, qc)

    with repeated(nq):
        _, (outs, lses) = lax.scan(q_body, None, jnp.arange(nq))
    out = outs.transpose(1, 0, 4, 2, 3, 5).reshape(B, nq * qc, H, Dv)
    return out[:, :Lq].astype(q.dtype), (outs, lses)


def _flash_bwd_impl(q, k, v, outs, lses, dout, engine, causal, window,
                    q_chunk, kv_chunk, q_offset):
    """True flash backward: recompute p blockwise; never materialize L^2."""
    B, Lq, H, D, Lk, KV, Dv, G, qc, kc, nq, nk = _flash_dims(
        q, k, v, q_chunk, kv_chunk)
    q_pad = jnp.pad(q, ((0, 0), (0, nq * qc - Lq), (0, 0), (0, 0)))
    k_pad = jnp.pad(k, ((0, 0), (0, nk * kc - Lk), (0, 0), (0, 0)))
    v_pad = jnp.pad(v, ((0, 0), (0, nk * kc - Lk), (0, 0), (0, 0)))
    dout = jnp.pad(dout.astype(jnp.float32),
                   ((0, 0), (0, nq * qc - Lq), (0, 0), (0, 0)))
    scale = D ** -0.5
    qg = q_pad.reshape(B, nq, qc, KV, G, D)
    kg = k_pad.reshape(B, nk, kc, KV, D)
    vg = v_pad.reshape(B, nk, kc, KV, Dv)
    # dout in (nq, B, KV, G, qc, Dv) to match outs/lses block layout
    dg = dout.reshape(B, nq, qc, KV, G, Dv).transpose(1, 0, 3, 4, 2, 5)
    # delta_i = rowsum(dout_i * out_i): (nq, B, KV, G, qc)
    delta = jnp.einsum("nbkgqd,nbkgqd->nbkgq", dg, outs)

    def kv_outer(dq_acc, ki):
        kblk = kg[:, ki]                       # (B, kc, KV, D)
        vblk = vg[:, ki]                       # (B, kc, KV, Dv)
        k_pos = ki * kc + jnp.arange(kc)

        def q_inner(carry, qi):
            dq_acc, dk_blk, dv_blk = carry
            qblk = qg[:, qi] * scale           # (B, qc, KV, G, D)
            q_pos = qi * qc + jnp.arange(qc) + q_offset
            # recomputed scores (same contraction as forward)
            s = _edot(engine, qblk, kblk, (((4,), (3,)), ((0, 2), (0, 2))),
                      out_dtype=jnp.float32).transpose(0, 1, 3, 2, 4)
            mask = _scores_mask(q_pos, k_pos, causal, window)
            mask &= (k_pos < Lk)[None, :]
            s = jnp.where(mask[None, None, None], s, NEG_INF)
            p = jnp.exp(s - lses[qi][..., None])            # (B,KV,G,qc,kc)
            do_blk = dg[qi]                                 # (B,KV,G,qc,Dv)
            # dv: einsum "bkgqs,bkgqd->bskd" (contract g, q)
            dv_blk = dv_blk + _edot(
                engine, p, do_blk, (((2, 3), (2, 3)), ((0, 1), (0, 1))),
                out_dtype=jnp.float32).transpose(0, 2, 1, 3)
            # dp: einsum "bkgqd,bskd->bkgqs" (contract d)
            dp = _edot(engine, do_blk, vblk.astype(jnp.float32),
                       (((4,), (3,)), ((0, 1), (0, 2))),
                       out_dtype=jnp.float32)
            ds = p * (dp - delta[qi][..., None])            # (B,KV,G,qc,kc)
            # dq: einsum "bkgqs,bskd->bqkgd" (contract s)
            dq_blk = _edot(engine, ds, kblk.astype(jnp.float32),
                           (((4,), (1,)), ((0, 1), (0, 2))),
                           out_dtype=jnp.float32
                           ).transpose(0, 3, 1, 2, 4) * scale
            dq_acc = dq_acc.at[:, qi].add(dq_blk)
            # dk: einsum "bkgqs,bqkgd->bskd" (contract g, q);
            # qblk already carries `scale`, so dk needs no extra factor
            dk_blk = dk_blk + _edot(
                engine, ds, qblk.astype(jnp.float32),
                (((2, 3), (3, 1)), ((0, 1), (0, 2))),
                out_dtype=jnp.float32).transpose(0, 2, 1, 3)
            return (dq_acc, dk_blk, dv_blk), None

        init = (dq_acc,
                jnp.zeros((B, kc, KV, D), jnp.float32),
                jnp.zeros((B, kc, KV, Dv), jnp.float32))
        with repeated(nq):
            (dq_acc, dk_blk, dv_blk), _ = lax.scan(q_inner, init,
                                                   jnp.arange(nq))
        return dq_acc, (dk_blk, dv_blk)

    dq0 = jnp.zeros((B, nq, qc, KV, G, D), jnp.float32)
    with repeated(nk):
        dq_acc, (dks, dvs) = lax.scan(kv_outer, dq0, jnp.arange(nk))
    dq = dq_acc.reshape(B, nq * qc, H, D)[:, :Lq]
    dk = dks.transpose(1, 0, 2, 3, 4).reshape(B, nk * kc, KV, D)[:, :Lk]
    dv = dvs.transpose(1, 0, 2, 3, 4).reshape(B, nk * kc, KV, Dv)[:, :Lk]
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash(q, k, v, engine, causal, window, q_chunk, kv_chunk, q_offset):
    return _flash_fwd_impl(q, k, v, engine, causal, window, q_chunk,
                           kv_chunk, q_offset)[0]


def _flash_fwd_rule(q, k, v, engine, causal, window, q_chunk, kv_chunk,
                    q_offset):
    out, (outs, lses) = _flash_fwd_impl(q, k, v, engine, causal, window,
                                        q_chunk, kv_chunk, q_offset)
    return out, (q, k, v, outs, lses)


def _flash_bwd_rule(engine, causal, window, q_chunk, kv_chunk, q_offset,
                    res, dout):
    q, k, v, outs, lses = res
    return _flash_bwd_impl(q, k, v, outs, lses, dout, engine, causal,
                           window, q_chunk, kv_chunk, q_offset)


_flash.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def decode_positions(cur_len, batch: int) -> jax.Array:
    """(B, 1) absolute position ``cur_len - 1`` of the token being decoded.

    ``cur_len`` is ``()`` (all slots in lock-step — the pre-serving
    contract) or ``(B,)`` (continuous batching: every slot at its own
    sequence position)."""
    c = (jnp.asarray(cur_len) - 1).astype(jnp.int32)
    if c.ndim == 0:
        return jnp.broadcast_to(c, (batch, 1))
    return c[:, None]


def ring_row_index(cur_len, cache_len: int):
    """Cache row a decode step at sequence position ``cur_len`` writes:
    ``(cur_len - 1) mod cache_len`` (the ring wrap covers windowed
    caches whose buffer is shorter than the sequence).  The single
    source of truth shared by :func:`cache_update_row` and the paged
    pool's row scatter (``repro.serving.kvcache.PagedKV``) — the two
    must agree or a paged write lands in the wrong block."""
    return (jnp.asarray(cur_len) - 1) % cache_len


def cache_update_row(buf: jax.Array, new: jax.Array, cur_len) -> jax.Array:
    """Write the decode-step row at position ``(cur_len - 1) mod L`` of a
    per-slot cache buffer.

    ``buf`` (B, L, ...); ``new`` (B, 1, ...); ``cur_len`` ``()`` or
    ``(B,)``.  The scalar form keeps the original
    ``dynamic_update_slice`` (one shared index); the vector form scatters
    one row per slot — an identical single-row replace, so the two forms
    are bitwise-equal when every slot shares a position.

    Vector slots with ``cur_len == 0`` are NO-OPS (the old row value is
    written back).  The serving runtime uses 0 for slots that are idle or
    not yet started inside a right-aligned prefill scan; without the
    guard their garbage k/v would land in row L-1 — harmless for per-row
    split scales (the row stays masked) but fatal under the oz2 GLOBAL
    digit grid, where one garbage row can shift every entry's scale."""
    c = jnp.asarray(cur_len)
    idx = ring_row_index(c, buf.shape[1])
    new = new.astype(buf.dtype)
    if c.ndim == 0:
        return lax.dynamic_update_slice_in_dim(buf, new, idx, axis=1)
    b_idx = jnp.arange(buf.shape[0])
    old = buf[b_idx, idx]
    live = (c > 0).reshape((-1,) + (1,) * (new.ndim - 2))
    return buf.at[b_idx, idx].set(jnp.where(live, new[:, 0], old))


def attention_decode(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     cur_len: jax.Array, *, window: Optional[int] = None,
                     engine=None) -> jax.Array:
    """Single-position attention against a (B, Lmax, KV, D) cache.

    q: (B, 1, H, D); cur_len: () or (B,) — number of valid cache positions
    INCLUDING the current token (already written at cur_len - 1).  The
    score and output contractions are (B, KV)-batched dot_generals routed
    through ``engine`` when given (ozimmu emulation at decode time).
    """
    B, _, H, D = q.shape
    Lmax, KV = k_cache.shape[1], k_cache.shape[2]
    Dv = v_cache.shape[-1]
    G = H // KV
    qg = (q * D ** -0.5).reshape(B, KV, G, D)
    # scores: einsum "bkgd,bskd->bkgs" (contract d)
    s = _edot(engine, qg, k_cache, (((3,), (3,)), ((0, 1), (0, 2))),
              out_dtype=jnp.float32)
    pos = jnp.arange(Lmax)
    cur = jnp.asarray(cur_len)
    cur = cur[:, None] if cur.ndim == 1 else cur[None, None]
    valid = pos[None, :] < cur                      # (B or 1, Lmax)
    if window is not None:
        valid &= pos[None, :] >= cur - window
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    # output: einsum "bkgs,bskd->bkgd" (contract s)
    out = _edot(engine, p.astype(v_cache.dtype), v_cache,
                (((3,), (1,)), ((0, 1), (0, 2))), out_dtype=jnp.float32)
    return out.reshape(B, 1, H, Dv).astype(q.dtype)


# ---------------------------------------------------------------------------
# projections / MLPs / embeddings
# ---------------------------------------------------------------------------

def swiglu(x, w_gate, w_up, w_down, engine):
    # layout hints for GSPMD only: "embed"/"mlp" are unsharded in the
    # default rules, and the mesh-native engine path triggers on mesh
    # presence + contraction divisibility, never on these annotations —
    # they exist so per-arch rule overrides CAN place the activations
    # without resharding churn around the engine's shard_map boundary
    x = shard(x, "batch", "seq", "embed")
    h = jax.nn.silu(engine(x, w_gate)) * engine(x, w_up)
    h = shard(h, "batch", "seq", "mlp")
    return engine(h, w_down)


def gelu_mlp(x, w_up, w_down, engine):
    x = shard(x, "batch", "seq", "embed")
    h = jax.nn.gelu(engine(x, w_up))
    h = shard(h, "batch", "seq", "mlp")
    return engine(h, w_down)


def embed_tokens(tokens: jax.Array, emb: jax.Array, dtype) -> jax.Array:
    out = jnp.take(emb, tokens, axis=0).astype(dtype)
    return shard(out, "batch", "seq", "embed")


def logits_head(x: jax.Array, emb_or_w: jax.Array, engine) -> jax.Array:
    """x (B, L, d) @ W (d, vocab) -> f32 logits, vocab-sharded."""
    x = shard(x, "batch", "seq", "embed")
    out = engine(x, emb_or_w).astype(jnp.float32)
    return shard(out, "batch", "seq", "vocab")
