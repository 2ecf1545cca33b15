"""Mixture-of-Experts family.

* ``deepseek-moe-16b``: fine-grained MoE — 2 shared + 64 routed experts,
  top-6, per-expert d_ff 1408, standard GQA attention.
* ``deepseek-v2-236b``: MLA (multi-head latent attention, kv_lora=512) +
  2 shared + 160 routed experts, top-6.

Routing is capacity-based (drop-on-overflow) with scatter dispatch into an
(E, C, d) expert buffer sharded over the ``experts``->model axis — the
standard dense-dispatch expert parallelism; the all-to-all shuffle is
generated by GSPMD from the sharding mismatch (tokens sharded over data,
experts over model).
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
# MoE FFN
# ---------------------------------------------------------------------------

def init_moe_ffn(rng, cfg: ModelConfig):
    d, fe, E = cfg.d_model, cfg.d_ff_expert, cfg.n_experts
    ks = jax.random.split(rng, 5)
    params = {
        "router": dense_param(ks[0], (d, E)),
        "w_gate": dense_param(ks[1], (E, d, fe)),
        "w_up": dense_param(ks[2], (E, d, fe)),
        "w_down": dense_param(ks[3], (E, fe, d), scale=fe ** -0.5),
    }
    axes = {
        "router": ("embed", None),
        "w_gate": ("experts", "embed", "expert_mlp"),
        "w_up": ("experts", "embed", "expert_mlp"),
        "w_down": ("experts", "expert_mlp", "embed"),
    }
    if cfg.n_shared_experts:
        shared_ff = cfg.d_ff_expert * cfg.n_shared_experts
        sh, sh_ax = T.init_mlp(ks[4], cfg, d_ff=shared_ff)
        params["shared"] = sh
        axes["shared"] = sh_ax
    return params, axes


_EXPERT_DNUMS = (((2,), (1,)), ((0,), (0,)))  # "ecd,edf->ecf": E-batched GEMM


def _router_gates(xt: jax.Array, router_w: jax.Array) -> jax.Array:
    """Router logits in f32 regardless of the engine dtype.

    Expert selection is discrete: engine-dtype (bf16) quantization of
    near-tied gate logits lets sub-ulp input perturbations (flash forward
    vs cached decode attention, resharding) flip top-k choices, which a
    continuous tolerance can never absorb.  Standard MoE practice
    (GShard/Switch) keeps the router in float32; the GEMM is a tiny
    (T, d) @ (d, E) — precision here costs nothing.
    """
    return jnp.dot(xt.astype(jnp.float32), router_w.astype(jnp.float32),
                   preferred_element_type=jnp.float32)


def moe_ffn(p, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    """x (B, S, d) -> (B, S, d).  Capacity-dropped top-k routing."""
    B, S, d = x.shape
    E, K = cfg.n_experts, cfg.topk
    eng = cfg.engine
    T_ = B * S
    cap = int(T_ * K * cfg.capacity_factor / E)
    cap = max(8, -(-cap // 8) * 8)  # pad to 8 for clean tiling
    xt = x.reshape(T_, d)

    gates = jax.nn.softmax(_router_gates(xt, p["router"]), axis=-1)  # (T, E)
    w, sel = lax.top_k(gates, K)                                   # (T, K)
    w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)            # renorm

    # position of each (token, slot) within its expert queue
    onehot = jax.nn.one_hot(sel, E, dtype=jnp.int32)               # (T, K, E)
    flat = onehot.reshape(T_ * K, E)
    pos = jnp.cumsum(flat, axis=0) * flat                          # 1-based
    pos = pos.max(axis=-1) - 1                                     # (T*K,)
    keep = (pos >= 0) & (pos < cap)
    e_idx = sel.reshape(T_ * K)
    slot = jnp.where(keep, pos, cap)                               # overflow -> cap (dropped)

    # dispatch: (E, cap+1, d) buffer; the +1 row absorbs drops
    buf = jnp.zeros((E, cap + 1, d), x.dtype)
    src = jnp.repeat(xt, K, axis=0)                                # (T*K, d)
    buf = buf.at[e_idx, slot].add(src, mode="drop")
    buf = shard(buf[:, :cap], "experts", None, "embed")

    # expert FFN: expert-batched GEMMs (E, cap, d) x (E, d, fe) through the
    # engine — an ozimmu spec emulates all E experts in one batched
    # dot_general instead of flattening them to 2-D
    h = eng.dot_general(buf, p["w_gate"].astype(x.dtype), _EXPERT_DNUMS)
    u = eng.dot_general(buf, p["w_up"].astype(x.dtype), _EXPERT_DNUMS)
    h = jax.nn.silu(h) * u
    h = shard(h, "experts", None, "expert_mlp")
    out_e = eng.dot_general(h, p["w_down"].astype(x.dtype), _EXPERT_DNUMS)
    out_e = shard(out_e, "experts", None, "embed")

    # combine: gather back and weight
    out_pad = jnp.concatenate(
        [out_e, jnp.zeros((E, 1, d), x.dtype)], axis=1)            # drops -> 0
    gathered = out_pad[e_idx, slot]                                # (T*K, d)
    wk = (w.reshape(T_ * K, 1) * keep[:, None]).astype(x.dtype)
    out = (gathered * wk).reshape(T_, K, d).sum(axis=1)

    if cfg.n_shared_experts:
        sp = p["shared"]
        out = out + L.swiglu(xt.reshape(B, S, d), sp["w_gate"], sp["w_up"],
                             sp["w_down"], cfg.engine).reshape(B * S, d)
    return out.reshape(B, S, d)


def _local_dispatch(xt, sel, w, E: int, cap: int):
    """Per-shard dense dispatch: (T, d) tokens -> (E, cap, d) buffers plus
    the (e_idx, slot, keep) bookkeeping needed to combine."""
    T_, d = xt.shape
    K = sel.shape[-1]
    onehot = jax.nn.one_hot(sel, E, dtype=jnp.int32)
    flat = onehot.reshape(T_ * K, E)
    pos = jnp.cumsum(flat, axis=0) * flat
    pos = pos.max(axis=-1) - 1
    keep = (pos >= 0) & (pos < cap)
    e_idx = sel.reshape(T_ * K)
    # dropped tokens scatter a ZERO into the last slot (no +1 pad row /
    # no concatenate pass; collisions are harmless because src is masked)
    slot = jnp.where(keep, pos, cap - 1)
    buf = jnp.zeros((E, cap, d), xt.dtype)
    src = jnp.repeat(xt, K, axis=0) * keep[:, None].astype(xt.dtype)
    buf = buf.at[e_idx, slot].add(src, mode="drop")
    return buf, e_idx, slot, keep


def _local_combine(out_e, e_idx, slot, w, keep, T_: int, K: int, d: int,
                   dtype):
    """Inverse of _local_dispatch: gather expert outputs back to tokens."""
    E, cap = out_e.shape[0], out_e.shape[1]
    gathered = out_e[e_idx, slot]           # dropped -> masked by wk below
    wk = (w.reshape(T_ * K, 1) * keep[:, None]).astype(dtype)
    return (gathered * wk).reshape(T_, K, d).sum(axis=1)


def _axis_size(mesh, names) -> int:
    total = 1
    for n in names:
        if n in mesh.axis_names:
            total *= mesh.shape[n]
    return total


def moe_ffn_a2a(p, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    """Expert-parallel MoE FFN with an explicit all-to-all token shuffle
    (shard_map over the data axes; the model axis stays GSPMD-auto).

    The GSPMD scatter path ("scatter") materializes a (E, cap, d) buffer
    that XLA ALL-REDUCES across the data axis (measured 3.1e14 bytes on
    deepseek-moe train_4k -- the dominant roofline term).  Here each data
    shard dispatches its own tokens locally, exchanges fixed-capacity
    per-expert blocks with one all-to-all (bytes ~ tokens*K*d, ~400x less),
    runs its resident experts, and all-to-alls back.
    """
    mesh = jax.sharding.get_abstract_mesh()
    dp_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    if not dp_axes or "data" not in mesh.axis_names or \
            cfg.n_experts % mesh.shape["data"] != 0:
        return moe_ffn(p, cfg, x)  # fallback: no mesh / indivisible experts

    B, S, d = x.shape
    E, K = cfg.n_experts, cfg.topk
    from jax.sharding import PartitionSpec as P
    # inside the shard_map body the mesh axes are already owned — a
    # mesh-native ozimmu engine must run with single-device semantics
    eng = cfg.engine.local()

    def body(xt, router_w, w_gate, w_up, w_down):
        # xt: (T_loc, d) local tokens; experts sharded over "data" only --
        # within a pod each data shard owns E_loc experts; the pod axis is
        # pure DP (experts replicated across pods).
        T_loc = xt.shape[0]
        E_loc = w_gate.shape[0]
        dp = E // E_loc
        # identical router math to the scatter path (f32 gates) so the
        # two dispatch modes route identically
        gates = jax.nn.softmax(_router_gates(xt, router_w), axis=-1)
        w, sel = lax.top_k(gates, K)
        w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)
        cap = max(8, -(-int(T_loc * K * cfg.capacity_factor / E) // 8) * 8)

        buf, e_idx, slot, keep = _local_dispatch(xt, sel, w, E, cap)
        # (E, cap, d) = (dp * E_loc, cap, d) -> exchange over the data axis:
        # shard i sends block j to shard j; receives (dp, E_loc, cap, d)
        recv = lax.all_to_all(buf.reshape(dp, E_loc, cap, d), "data",
                              split_axis=0, concat_axis=0, tiled=False)
        # recv[i, el] = source-shard i's tokens for local expert el
        recv = recv.transpose(1, 0, 2, 3).reshape(E_loc, dp * cap, d)

        h = eng.dot_general(recv, w_gate.astype(recv.dtype),
                            _EXPERT_DNUMS)
        u = eng.dot_general(recv, w_up.astype(recv.dtype),
                            _EXPERT_DNUMS)
        h = jax.nn.silu(h) * u
        out_e = eng.dot_general(h, w_down.astype(recv.dtype),
                                _EXPERT_DNUMS)

        back = lax.all_to_all(
            out_e.reshape(E_loc, dp, cap, d).swapaxes(0, 1), "data",
            split_axis=0, concat_axis=0, tiled=False)
        back = back.reshape(E, cap, d)
        return _local_combine(back, e_idx, slot, w, keep, T_loc, K, d,
                              xt.dtype)

    xt = x.reshape(B * S, d)
    out = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(dp_axes, None), P(), P("data", None, None),
                  P("data", None, None), P("data", None, None)),
        out_specs=P(dp_axes, None),
        axis_names=set(dp_axes),
        check_vma=False,
    )(xt, p["router"], p["w_gate"], p["w_up"], p["w_down"])

    if cfg.n_shared_experts:
        sp = p["shared"]
        out = out + L.swiglu(xt.reshape(B, S, d), sp["w_gate"], sp["w_up"],
                             sp["w_down"], cfg.engine).reshape(B * S, d)
    return out.reshape(B, S, d)


def moe_ffn_dispatch(p, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    if cfg.moe_dispatch == "a2a":
        return moe_ffn_a2a(p, cfg, x)
    return moe_ffn(p, cfg, x)


def aux_load_balance_loss(gates: jax.Array, sel: jax.Array, E: int) -> jax.Array:
    """Switch-style auxiliary loss (fraction * prob per expert)."""
    K = sel.shape[-1]
    frac = jnp.mean(jax.nn.one_hot(sel, E, dtype=jnp.float32), axis=(0, 1))
    prob = jnp.mean(gates, axis=0)
    return E * jnp.sum(frac * prob) / K


# ---------------------------------------------------------------------------
# MLA — multi-head latent attention (deepseek-v2)
# ---------------------------------------------------------------------------

def init_mla(rng, cfg: ModelConfig):
    d, H = cfg.d_model, cfg.n_heads
    dl, dr = cfg.kv_lora, cfg.rope_head_dim
    hd = cfg.hd                     # nope (non-rope) head dim
    vd = cfg.v_head_dim or hd
    ks = jax.random.split(rng, 6)
    params = {
        "w_dkv": dense_param(ks[0], (d, dl)),            # latent down-proj
        "w_krope": dense_param(ks[1], (d, dr)),          # shared rope key
        "w_q": dense_param(ks[2], (d, H * (hd + dr))),
        "w_uk": dense_param(ks[3], (dl, H * hd)),        # latent -> K up
        "w_uv": dense_param(ks[4], (dl, H * vd)),        # latent -> V up
        "w_o": dense_param(ks[5], (H * vd, d), scale=(H * vd) ** -0.5),
    }
    axes = {
        "w_dkv": ("embed", "kv_lora"),
        "w_krope": ("embed", None),
        "w_q": ("embed", "heads"),
        "w_uk": ("kv_lora", "heads"),
        "w_uv": ("kv_lora", "heads"),
        "w_o": ("heads", "embed"),
    }
    return params, axes


def mla_attention(p, cfg: ModelConfig, x, cos, sin, *, cache=None,
                  cur_len=None):
    """MLA: cache holds the compressed latent (B, L, kv_lora) + rope key
    (B, L, dr) — the paper's inference-time memory saving; K/V are
    up-projected on the fly."""
    eng = cfg.engine
    B, Lq, d = x.shape
    H, hd, dr = cfg.n_heads, cfg.hd, cfg.rope_head_dim
    vd = cfg.v_head_dim or hd

    latent = eng(x, p["w_dkv"])                           # (B, L, dl)
    k_rope = eng(x, p["w_krope"]).reshape(B, Lq, 1, dr)   # shared across heads
    k_rope = L.apply_rope(k_rope, cos, sin)
    q = eng(x, p["w_q"]).reshape(B, Lq, H, hd + dr)
    q_nope, q_rope = q[..., :hd], q[..., hd:]
    q_rope = L.apply_rope(q_rope, cos, sin)

    new_cache = None
    if cache is not None:
        lat_c, kr_c = cache
        cache_len = lat_c.shape[1]
        lat_c = L.cache_update_row(lat_c, latent, cur_len)
        kr_c = L.cache_update_row(kr_c, k_rope[:, :, 0], cur_len)
        new_cache = (lat_c, kr_c)
        latent_full = lat_c.astype(x.dtype)
        k_rope_full = kr_c[:, :, None].astype(x.dtype)
        valid = jnp.minimum(cur_len, cache_len)
    else:
        latent_full, k_rope_full, valid = latent, k_rope, None

    Lk = latent_full.shape[1]
    k_nope = eng(latent_full, p["w_uk"]).reshape(B, Lk, H, hd)
    v = eng(latent_full, p["w_uv"]).reshape(B, Lk, H, vd)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope_full, (B, Lk, H, dr))], axis=-1)
    qf = jnp.concatenate([q_nope, q_rope], axis=-1)
    qf = shard(qf, "batch", "seq", "heads", None)

    if cache is None:
        out = L.attention_flash(qf, k, v, causal=True,
                                q_chunk=cfg.q_chunk, kv_chunk=cfg.kv_chunk,
                                engine=eng)
    else:
        out = L.attention_decode(qf, k, v, valid, engine=eng)
    return eng(out.reshape(B, Lq, H * vd), p["w_o"]), new_cache


# ---------------------------------------------------------------------------
# model assembly (both MoE archs)
# ---------------------------------------------------------------------------

def init_layer(rng, cfg: ModelConfig):
    k1, k2 = jax.random.split(rng)
    if cfg.family == "mla_moe":
        attn, attn_ax = init_mla(k1, cfg)
    else:
        attn, attn_ax = T.init_attn(k1, cfg)
    ffn, ffn_ax = init_moe_ffn(k2, cfg)
    params = {"attn": attn, "moe": ffn,
              "ln1": jnp.zeros((cfg.d_model,)), "ln2": jnp.zeros((cfg.d_model,))}
    axes = {"attn": attn_ax, "moe": ffn_ax,
            "ln1": ("embed",), "ln2": ("embed",)}
    return params, axes


def init(rng, cfg: ModelConfig):
    k_emb, k_layers, k_head = jax.random.split(rng, 3)
    _, layer_ax = init_layer(k_layers, cfg)
    stacked = init_stacked(k_layers, cfg.n_layers,
                           lambda r: init_layer(r, cfg)[0])
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


def layer_fwd(lp, cfg: ModelConfig, x, cos, sin, cache=None, cur_len=None):
    if cfg.family == "mla_moe":
        xn = L.rmsnorm(x, lp["ln1"], cfg.norm_eps)
        attn_out, new_cache = mla_attention(lp["attn"], cfg, xn, cos, sin,
                                            cache=cache, cur_len=cur_len)
        x = x + attn_out
    else:
        x, new_cache = T.attn_block(
            {"attn": lp["attn"], "ln1": lp["ln1"]}, cfg, x, cos, sin,
            cache=cache, cur_len=cur_len)
    xn2 = L.rmsnorm(x, lp["ln2"], cfg.norm_eps)
    x = x + moe_ffn_dispatch(lp["moe"], cfg, xn2)
    return x, new_cache


def forward(params, cfg: ModelConfig, tokens, positions=None):
    B, Lq = tokens.shape
    x = L.embed_tokens(tokens, params["embed"], cfg.compute_dtype)
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(Lq, dtype=jnp.int32), (B, Lq))
    rope_dim = cfg.rope_head_dim if cfg.family == "mla_moe" else cfg.hd
    cos, sin = L.rope_cos_sin(positions, rope_dim, cfg.rope_theta)

    def body(lp, x, _):
        x, _ = layer_fwd(lp, cfg, x, cos, sin)
        return x, None

    x, _ = T.scan_layers(body, params["layers"], x, n_layers=cfg.n_layers,
                         remat_block=cfg.remat_block)
    x = L.rmsnorm(x, params["ln_f"], cfg.norm_eps)
    return L.logits_head(x, params["lm_head"], cfg.engine)


def init_cache(cfg: ModelConfig, batch: int, max_len: int):
    if cfg.family == "mla_moe":
        lat = jnp.zeros((cfg.n_layers, batch, max_len, cfg.kv_lora), jnp.bfloat16)
        kr = jnp.zeros((cfg.n_layers, batch, max_len, cfg.rope_head_dim),
                       jnp.bfloat16)
        lat = shard(lat, "layers", "cache_batch", None, "kv_lora")
        kr = shard(kr, "layers", "cache_batch", None, "cache_hd")
        return {"latent": lat, "k_rope": kr}
    return T.init_cache(cfg, batch, max_len)


def decode_step(params, cfg: ModelConfig, cache, tokens, cur_len):
    B = tokens.shape[0]
    x = L.embed_tokens(tokens, params["embed"], cfg.compute_dtype)
    pos = L.decode_positions(cur_len, B)
    rope_dim = cfg.rope_head_dim if cfg.family == "mla_moe" else cfg.hd
    cos, sin = L.rope_cos_sin(pos, rope_dim, cfg.rope_theta)

    if cfg.family == "mla_moe":
        def body(x, inputs):
            lp, lat, kr = inputs
            x, new_c = layer_fwd(lp, cfg, x, cos, sin, cache=(lat, kr),
                                 cur_len=cur_len)
            return x, new_c
        with repeated(cfg.n_layers):
            x, (lat_n, kr_n) = lax.scan(
                body, x, (params["layers"], cache["latent"],
                          cache["k_rope"]), length=cfg.n_layers)
        new_cache = {"latent": lat_n, "k_rope": kr_n}
    else:
        def body(x, inputs):
            lp, kc, vc = inputs
            x, new_c = layer_fwd(lp, cfg, x, cos, sin, cache=(kc, vc),
                                 cur_len=cur_len)
            return x, new_c
        with repeated(cfg.n_layers):
            x, (k_n, v_n) = lax.scan(
                body, x, (params["layers"], cache["k"], cache["v"]),
                length=cfg.n_layers)
        new_cache = {"k": k_n, "v": v_n}
    x = L.rmsnorm(x, params["ln_f"], cfg.norm_eps)
    return L.logits_head(x, params["lm_head"], cfg.engine), new_cache
