"""Whole runs of ``bench/run.py`` on the CPU at smoke size, with the look
for a chip skipped: a sound program comes out correct, and the timed path
broken underneath comes out not correct, once for each fault a cell can
have.  A GEMM cell can return a wrong answer; a serving cell can serve an
altered token or run a decode step that leaves its KV state unchanged.
(No cell trains, and none spans chips.)  Each control, the reference at
the nearest lower precision, fails the check too."""
from __future__ import annotations

import json

import jax
import numpy as np
import pytest

from bench import harness as H
from bench import run as bench_run
from bench.drivers import gemm, serve
from bench.reference import dense_gqa

SMOKE_SERVE = {"hidden_size": 64, "intermediate_size": 96,
               "num_attention_heads": 4, "num_key_value_heads": 2,
               "num_hidden_layers": 2, "vocab_size": 256}
SMOKE_TRAFFIC = {"prompt_len": {"dist": "uniform", "min": 4, "max": 12},
                 "output_len": {"dist": "log_uniform", "min": 8,
                                "max": 24},
                 "ramp_rounds": 2, "trace_seconds": 0.2}


@pytest.fixture
def smoke(monkeypatch):
    """run.main at smoke size on the CPU: every file is the committed
    one except for the sizes."""
    load_config, load_traffic = H.load_config, H.load_traffic

    def config(name, *a):
        cfg = load_config(name, *a)
        if cfg["driver"] == "serve":
            cfg.update(SMOKE_SERVE)
            cfg["serving"] = dict(cfg["serving"], slots=2, max_len=40,
                                  page_block=8, prefill_chunk=16,
                                  spec="ozimmu_h-auto:df32")
        else:
            cfg["spec"] = "ozimmu_h-auto:df32"
        return cfg

    def traffic(name, *a):
        tr = load_traffic(name, *a)
        if tr["kind"] == "gemm":
            return dict(tr, m=128, n=256, p=128)
        return dict(tr, **SMOKE_TRAFFIC)

    monkeypatch.setattr(H, "load_config", config)
    monkeypatch.setattr(H, "load_traffic", traffic)
    monkeypatch.setattr(H, "require_chips", lambda jax_, n: jax.devices()[:1])
    peaks = H.load_peaks
    monkeypatch.setattr(H, "load_peaks", lambda kind: peaks("TPU v5 lite"))
    import repro.launch.entry as entry
    monkeypatch.setattr(entry, "prepare_entry_point", lambda: "unused")


def result(capsys, workload, seed=2 ** 33 + 1):
    assert bench_run.main(["--workload", workload, "--seed", str(seed),
                           "--seconds", "1", "--trace", "0"]) == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert list(out)[-1] == "checks"
    return out


def test_gemm_sound_and_wrong_answer(smoke, capsys, monkeypatch):
    assert result(capsys, "dgemm.square8192")["correct"] is True
    fn = gemm.gemm_fn
    monkeypatch.setattr(gemm, "gemm_fn", lambda spec: jax.jit(
        lambda a, b: fn(spec)(a, b) * (1 + 2.0 ** -10)))
    assert result(capsys, "dgemm.square8192")["correct"] is False


@pytest.mark.parametrize("label", sorted(gemm.CONTROLS))
def test_gemm_control_in_the_programs_place(smoke, capsys, monkeypatch,
                                            label):
    monkeypatch.setattr(gemm, "gemm_fn", lambda spec: gemm.control_fn(label))
    assert result(capsys, "dgemm.square8192")["correct"] is False


def test_serve_sound_run_is_correct(smoke, capsys):
    out = result(capsys, "internlm2_1_8b.decode_long")
    assert out["correct"] is True and out["failed"] == 0
    assert set(out["metrics"]) == {"decode_tok_s", "itl_p95_ms",
                                   "setup_s"}


def test_serve_altered_token(smoke, capsys, monkeypatch):
    from repro.serving.runtime import ServingRuntime
    greedy = ServingRuntime._greedy

    def altered(self, logits, live):
        nxt, finite = greedy(self, logits, live)
        return (nxt + 1) % self.cfg.vocab, finite

    monkeypatch.setattr(ServingRuntime, "_greedy", altered)
    assert result(capsys, "internlm2_1_8b.decode_long")["correct"] is False


def test_serve_decode_leaves_state_unchanged(smoke, capsys, monkeypatch):
    from repro.serving.kvcache import PagedKV
    monkeypatch.setattr(
        PagedKV, "_scatter_rows_impl",
        lambda self, pool, tables, cache, cur_len, active, state:
        (pool, state))
    assert result(capsys, "internlm2_1_8b.decode_long")["correct"] is False


@pytest.mark.parametrize("label", serve.CONTROLS)
def test_serve_control_fails_the_check(label):
    """Each control, at each position of a prompt, picks tokens whose
    reference gaps exceed the check's limits.  Its gaps come from near-ties
    among the largest logits, so the test takes a vocabulary, a depth and a
    context large enough to hold as many as the cell's (the widths are half
    the model's; int8 reads about the chip's 0.004-0.007 mean gap here)."""
    cfg = H.load_config("internlm2_1_8b")
    cfg.update(hidden_size=1024, intermediate_size=4096,
               num_attention_heads=8, num_key_value_heads=4,
               num_hidden_layers=8, vocab_size=32768)
    dims = serve.dims_of(cfg)
    params = jax.jit(serve.make_weights, static_argnums=1)(
        H.seed_key(jax, 5), serve._frozen(dims))
    toks = jax.numpy.asarray(
        np.random.default_rng(2).integers(0, dims["vocab"], 256))
    dot = {"int8": dense_gqa.int8_dot, "fp8": dense_gqa.fp8_dot}[label]
    _, ctrl = dense_gqa.served_gaps(params, dims, toks, toks, dot=dot)
    ok, _ = serve.gap_checks(np.asarray(ctrl), cfg["check"]["limits"])
    assert ok is False
