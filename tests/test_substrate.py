"""Substrate tests: data pipeline determinism, checkpoint save/restore,
optimizer semantics, gradient compression with error feedback."""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.checkpoint import Checkpointer
from repro.data import DataConfig, Pipeline
from repro import optim
from repro.optim import compress


# ---------------------------------------------------------------------------
# data pipeline
# ---------------------------------------------------------------------------

def test_pipeline_deterministic_and_resumable():
    cfg = DataConfig(seq_len=64, global_batch=8, vocab=128, seed=7)
    p1 = Pipeline(cfg)
    p2 = Pipeline(cfg)
    b1 = p1.batch_at(5)
    b2 = p2.batch_at(5)  # fresh instance, same step -> identical
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    assert b1["tokens"].shape == (8, 64)
    assert b1["tokens"].min() >= 0 and b1["tokens"].max() < 128
    # different steps differ
    assert not np.array_equal(b1["tokens"], p1.batch_at(6)["tokens"])


def test_pipeline_host_sharding_partitions_global_batch():
    cfg = DataConfig(seq_len=32, global_batch=8, vocab=64, seed=3)
    full = Pipeline(cfg, host_id=0, num_hosts=1).batch_at(2)["tokens"]
    parts = [Pipeline(cfg, host_id=h, num_hosts=4).batch_at(2)["tokens"]
             for h in range(4)]
    np.testing.assert_array_equal(np.concatenate(parts), full)


def test_pipeline_learnable_structure():
    """Planted copied spans -> bigram statistics beat chance."""
    cfg = DataConfig(seq_len=512, global_batch=4, vocab=512, seed=0)
    toks = Pipeline(cfg).batch_at(0)["tokens"]
    # repeated-span structure => some exact 8-gram appears twice per row
    found = 0
    for row in toks:
        s = row.tobytes()
        for i in range(0, len(row) - 8):
            pat = row[i:i + 8].tobytes()
            if s.count(pat) > 1:
                found += 1
                break
    assert found >= toks.shape[0] // 2


# ---------------------------------------------------------------------------
# checkpointing
# ---------------------------------------------------------------------------

def _tree(seed=0):
    k = jax.random.PRNGKey(seed)
    return {"w": jax.random.normal(k, (8, 4)),
            "nested": {"b": jnp.arange(5, dtype=jnp.int32)},
            "scalar": jnp.asarray(3, jnp.int32)}


def test_checkpoint_roundtrip(tmp_path):
    ck = Checkpointer(str(tmp_path))
    t = _tree()
    ck.save(10, t, blocking=True)
    restored, step = ck.restore(jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), t))
    assert step == 10
    for a, b in zip(jax.tree.leaves(t), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_async_latest_and_retention(tmp_path):
    ck = Checkpointer(str(tmp_path), keep=2)
    for s in (1, 2, 3, 4):
        ck.save(s, _tree(s))
    ck.wait()
    steps = ck.list_steps()
    assert steps[-1] == 4 and len(steps) <= 2  # retention kept newest
    _, step = ck.restore(jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), _tree()))
    assert step == 4


def test_checkpoint_restore_with_shardings(tmp_path):
    """Elastic-restore path: restore with explicit (single-device) shardings."""
    ck = Checkpointer(str(tmp_path))
    t = _tree()
    ck.save(1, t, blocking=True)
    dev = jax.devices()[0]
    sh = jax.tree.map(lambda _: jax.sharding.SingleDeviceSharding(dev), t)
    restored, _ = ck.restore(
        jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), t),
        shardings=sh)
    np.testing.assert_array_equal(np.asarray(restored["w"]),
                                  np.asarray(t["w"]))


# ---------------------------------------------------------------------------
# optimizer
# ---------------------------------------------------------------------------

def test_adamw_matches_reference():
    cfg = optim.OptConfig(lr=1e-2, betas=(0.9, 0.99), eps=1e-8,
                          weight_decay=0.01, grad_clip=1e9,
                          warmup_steps=0, total_steps=100, min_lr_frac=1.0)
    params = {"w": jnp.asarray([1.0, -2.0, 3.0])}
    grads = {"w": jnp.asarray([0.1, 0.2, -0.3])}
    state = optim.init(params, cfg=cfg)
    new_p, state, _ = optim.step(grads, params, state, cfg)
    # manual AdamW reference (bias-corrected, decoupled decay)
    g = np.asarray(grads["w"])
    m = 0.1 * g
    v = 0.01 * g * g
    mh = m / (1 - 0.9)
    vh = v / (1 - 0.99)
    ref = np.asarray(params["w"]) - 1e-2 * (
        mh / (np.sqrt(vh) + 1e-8) + 0.01 * np.asarray(params["w"]))
    np.testing.assert_allclose(np.asarray(new_p["w"]), ref, rtol=1e-6)


def test_grad_clip_applies():
    cfg = optim.OptConfig(lr=1.0, grad_clip=0.5, warmup_steps=0,
                          total_steps=10, weight_decay=0.0, min_lr_frac=1.0)
    params = {"w": jnp.zeros(4)}
    grads = {"w": jnp.full(4, 10.0)}  # norm 20 >> clip 0.5
    state = optim.init(params, cfg=cfg)
    _, _, metrics = optim.step(grads, params, state, cfg)
    assert float(metrics["grad_norm"]) == pytest.approx(20.0)


def test_lr_schedule_warmup_cosine():
    cfg = optim.OptConfig(lr=1.0, warmup_steps=10, total_steps=110,
                          min_lr_frac=0.1)
    assert float(optim.lr_at(cfg, jnp.asarray(0))) == 0.0
    assert float(optim.lr_at(cfg, jnp.asarray(10))) == pytest.approx(1.0)
    assert float(optim.lr_at(cfg, jnp.asarray(110))) == pytest.approx(0.1)


def test_zero_axes_augmentation():
    params = {"w": jnp.zeros((8, 6)), "b": jnp.zeros((7,))}
    axes = {"w": (None, "mlp"), "b": (None,)}
    from repro.distributed.sharding import use_rules
    with use_rules({"mlp": "model", "zero": ("data",)}):
        out = optim.zero_axes(axes, params, zero_divisor=4)
    assert out["w"] == ("zero", "mlp")   # dim0=8 divisible by 4
    assert out["b"] == (None,)           # 7 not divisible -> untouched


# ---------------------------------------------------------------------------
# gradient compression (error feedback)
# ---------------------------------------------------------------------------

def test_compress_error_feedback_exact_recovery():
    """quantized + residual == original, exactly (power-of-two scales)."""
    rng = np.random.default_rng(0)
    g = jnp.asarray(rng.standard_normal((16, 32)) * 3.0, jnp.float32)
    err = jnp.zeros_like(g)
    digits, scale, new_err = compress.compress(g, err)
    recon = compress.decompress(digits, scale, g.shape)
    np.testing.assert_allclose(np.asarray(recon + new_err), np.asarray(g),
                               rtol=0, atol=0)  # exact
    assert digits.dtype == jnp.int8


def test_compress_error_feedback_converges():
    """Repeated compression of a constant gradient: error stays bounded and
    the long-run mean of transmitted values approaches the gradient."""
    rng = np.random.default_rng(1)
    g = jnp.asarray(rng.standard_normal((8, 16)), jnp.float32)
    err = jnp.zeros_like(g)
    sent = jnp.zeros_like(g)
    for _ in range(50):
        digits, scale, err = compress.compress(g, err)
        sent = sent + compress.decompress(digits, scale, g.shape)
    np.testing.assert_allclose(np.asarray(sent / 50), np.asarray(g),
                               atol=np.abs(np.asarray(g)).max() * 0.02)


def test_train_restart_equivalence(tmp_path):
    """Fault-tolerance: train N steps straight == train N/2, 'crash',
    restore from checkpoint, train to N (bitwise-equal losses thereafter)."""
    from repro.launch.train import train
    losses_ref = train("internlm2_1_8b", smoke=True, n_steps=4,
                       global_batch=2, seq_len=32, log_every=0,
                       seed=3)[1]
    ck = str(tmp_path / "ck")
    train("internlm2_1_8b", smoke=True, n_steps=2, global_batch=2,
          seq_len=32, ckpt_dir=ck, ckpt_every=2, log_every=0, seed=3)
    losses_resumed = train("internlm2_1_8b", smoke=True, n_steps=4,
                           global_batch=2, seq_len=32, ckpt_dir=ck,
                           ckpt_every=10, log_every=0, seed=3)[1]
    np.testing.assert_allclose(losses_resumed, losses_ref[2:], rtol=1e-5)


def test_compile_cache_directory(monkeypatch, tmp_path):
    """The entry points' compile cache: JAX_COMPILATION_CACHE_DIR wins and
    no other directory is set; otherwise one fixed directory inside the
    checkout.  (The directory is redirected under tmp_path and
    jax.config.update is recorded, not applied: the tests never turn the
    cache on.)"""
    from pathlib import Path

    from repro.launch import entry
    checkout = Path(__file__).resolve().parents[1]
    assert entry.CHECKOUT_CACHE_DIR == checkout / ".jax_cache"
    calls = []
    monkeypatch.setattr(entry.jax.config, "update",
                        lambda name, value: calls.append((name, value)))
    monkeypatch.setattr(entry, "CHECKOUT_CACHE_DIR", tmp_path / ".jax_cache")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/placed/by/the/user")
    assert entry.enable_compile_cache() == "/placed/by/the/user"
    assert calls == [] and not (tmp_path / ".jax_cache").exists()
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    where = entry.enable_compile_cache()
    assert where == str(tmp_path / ".jax_cache")
    assert (tmp_path / ".jax_cache").is_dir()
    assert calls == [("jax_compilation_cache_dir", where)]
    assert entry.enable_compile_cache() == where


def test_exact_rounding_flag(monkeypatch):
    """The entry points turn XLA's excess precision off before a backend
    starts, and refuse to pretend they did once one has."""
    from repro.launch import entry
    flag = entry.EXACT_ROUNDING_FLAG
    monkeypatch.setattr(entry.xla_bridge, "backends_are_initialized",
                        lambda: False)
    monkeypatch.setenv("XLA_FLAGS", "--xla_dump_to=x")
    entry.require_exact_rounding()
    assert os.environ["XLA_FLAGS"] == f"--xla_dump_to=x {flag}"
    entry.require_exact_rounding()          # already there: unchanged
    assert os.environ["XLA_FLAGS"] == f"--xla_dump_to=x {flag}"
    monkeypatch.delenv("XLA_FLAGS")
    entry.require_exact_rounding()
    assert os.environ["XLA_FLAGS"] == flag
    monkeypatch.setattr(entry.xla_bridge, "backends_are_initialized",
                        lambda: True)
    monkeypatch.setenv("XLA_FLAGS", "")
    with pytest.raises(RuntimeError, match="backend started"):
        entry.require_exact_rounding()
    assert os.environ["XLA_FLAGS"] == ""
