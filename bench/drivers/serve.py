"""Window driver for served-model cells: ``repro.serving.ServingRuntime``
driven through ``submit`` and ``step`` by a closed loop of clients.

Set-up makes the weights on the device from the seed in one jitted call,
builds the runtime (which freezes the weights' int8 splits), warms every
program the traffic reaches (the decode step, one prefill program per
prompt bucket and one cache write-back per span of blocks), then starts
one client per slot and runs ``ramp_rounds`` scheduler rounds, in which
the clients' first prompts are prefilled.

The generator is general and reads the traffic file: a fixed pool of
(prompt length, output length) pairs drawn once from ``shape_seed``, so
every seed serves the same sizes; ``--seed`` orders the pool and draws
the prompt tokens.  Each client sends its next request when the last one
finishes (a closed loop).

The harness stamps each output token after the ``runtime.step()`` that
produced it.  Over the window:

* ``decode_tok_s``: output tokens stamped in the window over its length;
* ``itl_p95_ms``: 95th percentile of the gaps between consecutive output
  tokens of a request, both inside the window.

The check runs the plain float32 reference (``bench/reference/
dense_gqa.py``) over a sample of the run's requests drawn from the seed,
the one with the most served tokens among them, each prompt with the
tokens served to it so far, and reads how far each served token's
reference logit lies below the reference's best at its position (its
gap).  Greedy decoding serves the program's argmax, so a wrong token, a
wrong cache row or a lower precision raises the gaps.  Two numbers are
compared: the widest gap (``serve_gap``) and the mean gap over every
served position of the sample (``serve_gap_mean``).  The mean grows with
the square of the logits' error, since both the share of positions whose
argmax flips and the size of each flip grow with it, so it separates a
lower precision that the widest gap, which swings with one near-tie,
does not.
"""
from __future__ import annotations

import dataclasses
import time
from contextlib import ExitStack
from typing import Any, Dict, List, Optional

import numpy as np

from bench import harness as H
from bench.reference import dense_gqa

TRAFFIC_KINDS = ("closed_loop",)


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------

def dims_of(config: dict) -> dict:
    return {"d": int(config["hidden_size"]),
            "heads": int(config["num_attention_heads"]),
            "kv_heads": int(config["num_key_value_heads"]),
            "ff": int(config["intermediate_size"]),
            "vocab": int(config["vocab_size"]),
            "padded_vocab": -(-int(config["vocab_size"]) // 256) * 256,
            "layers": int(config["num_hidden_layers"]),
            "eps": float(config["rms_norm_eps"]),
            "rope_theta": float(config["rope_theta"])}


def model_config(config: dict):
    from repro.models.common import ModelConfig
    dm, sv = dims_of(config), config["serving"]
    return ModelConfig(name=config["name"], family="dense",
                       n_layers=dm["layers"], d_model=dm["d"],
                       n_heads=dm["heads"], n_kv_heads=dm["kv_heads"],
                       d_ff=dm["ff"], vocab=dm["vocab"],
                       rope_theta=dm["rope_theta"], norm_eps=dm["eps"],
                       dtype=sv["dtype"], engine_spec=sv["spec"])


def make_weights(key, dims: dict):
    """The parameter tree the dense family consumes, in float32, made on
    the device (call under jit with ``dims`` static)."""
    import jax
    import jax.numpy as jnp
    dims = dict(dims)
    d, H, KV = dims["d"], dims["heads"], dims["kv_heads"]
    hd, f, L, V = d // H, dims["ff"], dims["layers"], dims["padded_vocab"]
    ks = iter(jax.random.split(key, 16))

    def w(shape, fan_in):
        return jax.random.normal(next(ks), shape, jnp.float32) \
            * fan_in ** -0.5

    def gain(shape):
        return 0.1 * jax.random.normal(next(ks), shape, jnp.float32)

    layers = {"attn": {"wq": w((L, d, H * hd), d),
                       "wk": w((L, d, KV * hd), d),
                       "wv": w((L, d, KV * hd), d),
                       "wo": w((L, H * hd, d), H * hd)},
              "mlp": {"w_gate": w((L, d, f), d), "w_up": w((L, d, f), d),
                      "w_down": w((L, f, d), f)},
              "ln1": gain((L, d)), "ln2": gain((L, d))}
    return {"embed": w((V, d), 1), "layers": layers, "ln_f": gain((d,)),
            "lm_head": w((d, V), d)}


# ---------------------------------------------------------------------------
# the traffic
# ---------------------------------------------------------------------------

def draw(spec: dict, rng: np.random.Generator, n: int) -> np.ndarray:
    """n whole numbers from a length distribution of the traffic file."""
    lo, hi = int(spec["min"]), int(spec["max"])
    kind = spec["dist"]
    if kind == "uniform":
        x = rng.integers(lo, hi + 1, n)
    elif kind == "log_uniform":
        x = np.exp(rng.uniform(np.log(lo), np.log(hi + 1), n))
    else:
        raise H.BenchError(f"unknown length distribution {kind!r}")
    return np.clip(np.floor(x), lo, hi).astype(np.int64)


class Stream:
    """The request sequence of one run: the traffic's fixed pool of sizes
    in an order drawn from the seed, prompt tokens drawn from the seed."""

    def __init__(self, traffic: dict, seed: int, vocab: int):
        pool_rng = np.random.default_rng(int(traffic["shape_seed"]))
        n = int(traffic["pool"])
        self.prompts = draw(traffic["prompt_len"], pool_rng, n)
        self.outputs = draw(traffic["output_len"], pool_rng, n)
        self.rng = np.random.default_rng(int(seed))
        self.order = self.rng.permutation(n)
        self.vocab, self.i = vocab, 0

    def next(self):
        j = self.order[self.i % len(self.order)]
        plen, out = int(self.prompts[j]), int(self.outputs[j])
        self.i += 1
        return self.rng.integers(0, self.vocab, plen).astype(np.int32), out


# ---------------------------------------------------------------------------
# bookkeeping of served tokens
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Book:
    submitted: Dict[int, float] = dataclasses.field(default_factory=dict)
    stamps: Dict[int, List[float]] = dataclasses.field(default_factory=dict)
    live: Dict[int, Any] = dataclasses.field(default_factory=dict)
    finished: List[Any] = dataclasses.field(default_factory=list)
    done_at: Dict[int, float] = dataclasses.field(default_factory=dict)

    def submit(self, rt, stream: Stream, now: float):
        prompt, out = stream.next()
        req = rt.submit(prompt, out)
        self.submitted[req.rid] = now
        self.stamps[req.rid] = []
        self.live[req.rid] = req

    def after_step(self, rt, stream: Stream, now: float):
        """Stamp new tokens; replace each finished request (closed loop).
        Returns (tokens stamped, first tokens among them)."""
        new = first = 0
        for rid, req in list(self.live.items()):
            stamps = self.stamps[rid]
            k = len(req.generated) - len(stamps)
            if k:
                first += not stamps
                stamps.extend([now] * k)
                new += k
            if req.finished_at is not None:
                del self.live[rid]
                self.finished.append(req)
                self.done_at[rid] = now
                self.submit(rt, stream, now)
        return new, first


@dataclasses.dataclass
class State:
    rt: Any
    params: Any
    dims: dict
    book: Book
    stream: Stream


def warm_prompt_lengths(traffic: dict, block: int, chunk: int) -> List[int]:
    """One prompt length for each (prefill bucket, cache span) pair that
    the traffic's prompts reach: the programs the window will run."""
    from repro.serving.scheduler import bucket_pow2
    seen, out = set(), []
    for n in range(int(traffic["prompt_len"]["min"]),
                   int(traffic["prompt_len"]["max"]) + 1):
        if n > chunk:
            raise H.BenchError("prompts longer than prefill_chunk are not "
                               "warmed by this driver")
        key = (bucket_pow2(n), -(-n // block))
        if key not in seen:
            seen.add(key)
            out.append(n)
    return out


def setup(run: H.Run) -> State:
    import jax
    from repro.serving.runtime import ServingRuntime
    sv, dims = run.config["serving"], dims_of(run.config)
    cfg = model_config(run.config)
    with run.span("weights"):
        params = jax.jit(make_weights, static_argnums=1)(
            H.seed_key(jax, run.seed), _frozen(dims))
        jax.block_until_ready(params)
    with run.span("runtime"):
        rt = ServingRuntime(cfg, params, slots=int(sv["slots"]),
                            max_len=int(sv["max_len"]),
                            page_block=int(sv["page_block"]),
                            prefill_chunk=int(sv["prefill_chunk"]),
                            presplit=bool(sv["presplit"]))
    with run.span("warmup"):
        rng = np.random.default_rng(0)
        for n in warm_prompt_lengths(run.traffic, int(sv["page_block"]),
                                     int(sv["prefill_chunk"])):
            rt.submit(rng.integers(0, dims["vocab"], n).astype(np.int32), 2)
        while rt.step():
            pass
    book = Book()
    stream = Stream(run.traffic, run.seed, dims["vocab"])
    now = time.perf_counter()
    for _ in range(int(sv["slots"])):
        book.submit(rt, stream, now)
    with run.span("ramp"):
        for _ in range(int(run.traffic["ramp_rounds"])):
            rt.step()
            book.after_step(rt, stream, time.perf_counter())
    return State(rt, params, dims, book, stream)


def _frozen(dims: dict):
    return tuple(sorted(dims.items()))


def window(state: State, run: H.Run) -> dict:
    rt, book, stream = state.rt, state.book, state.stream
    m = rt.metrics
    traced: Optional[dict] = None
    trace_after = float(run.traffic["trace_seconds"])
    stack = ExitStack()
    with run.span("window"):
        t0 = time.perf_counter()
        if run.trace:
            stack.enter_context(run.traced_span())
            traced = {"t0": time.perf_counter(), "occ": []}
        now = t0
        while now - t0 < run.seconds:
            decodes = m.decode_steps
            with run.span("step"):
                rt.step()
            now = time.perf_counter()
            new, first = book.after_step(rt, stream, now)
            if traced is not None and "t1" not in traced:
                if m.decode_steps > decodes:
                    traced["occ"].append(new - first)
                if now - t0 >= trace_after:
                    stack.close()
                    traced["t1"] = time.perf_counter()
        t1 = now
    stack.close()
    rec = window_record(book, t0, t1)
    H.log(f"serve window {t1 - t0:.2f} s: {rec['tokens']} tokens, "
          f"{rec['itl_gaps']} gaps, "
          f"{m.decode_steps} decode and {m.prefill_calls} prefill calls "
          f"since the ramp began")
    if traced is not None:
        traced.setdefault("t1", t1)
        traced.update(traced_work(book, state, traced["t0"], traced["t1"]))
        rec["traced"] = traced
    return rec


def window_record(book: Book, t0: float, t1: float) -> dict:
    tokens, gaps, attempted, failed = 0, [], 0, 0
    reqs = {r.rid: r for r in book.finished + list(book.live.values())}
    for rid, stamps in book.stamps.items():
        sub = book.submitted[rid]
        done = book.done_at.get(rid)
        if sub > t1 or (done is not None and done < t0):
            continue
        attempted += 1
        if done is not None and len(reqs[rid].generated) != \
                reqs[rid].max_new:
            failed += 1
        inside = [s for s in stamps if t0 <= s <= t1]
        tokens += len(inside)
        gaps.extend(b - a for a, b in zip(inside, inside[1:]))
    e2e = {"decode_tok_s": tokens / (t1 - t0)}
    if gaps:
        e2e["itl_p95_ms"] = 1e3 * float(np.percentile(gaps, 95))
    return {"e2e": e2e, "attempted": attempted, "failed": failed,
            "window_s": t1 - t0, "tokens": tokens, "itl_gaps": len(gaps)}


def traced_work(book: Book, state: State, t0: float, t1: float) -> dict:
    """Tokens and model operations of the traced stretch: every token the
    model processed there (prompt tokens fed, output tokens decoded) at
    2 x its matmul weights plus attention over its context, and the head
    for each token that yields logits."""
    dm = state.dims
    d, H, KV, f, L = dm["d"], dm["heads"], dm["kv_heads"], dm["ff"], \
        dm["layers"]
    hd = d // H
    layer_w = L * (d * H * hd + 2 * d * KV * hd + H * hd * d + 3 * d * f)
    head_w = d * dm["vocab"]
    ops, out_tokens = 0.0, 0
    reqs = {r.rid: r for r in list(book.live.values()) + book.finished}
    for rid, stamps in book.stamps.items():
        req = reqs.get(rid)
        if req is None:
            continue
        plen = len(req.prompt)
        for i, s in enumerate(stamps):
            if not t0 <= s <= t1:
                continue
            out_tokens += 1
            if i == 0:
                # the prompt's prefill: every prompt position, and the
                # head once for the first token
                ops += plen * 2 * layer_w + 2 * head_w
                ops += 4 * L * H * hd * plen * (plen + 1) / 2
            else:
                ctx = plen + i
                ops += 2 * (layer_w + head_w) + 4 * L * H * hd * ctx
    return {"model_ops": ops, "out_tokens": out_tokens}


# ---------------------------------------------------------------------------
# the check
# ---------------------------------------------------------------------------

def sample_requests(book: Book, seed: int, count: int):
    """Requests drawn from the seed among all that were served tokens,
    finished or not, with the one served the most tokens."""
    served = [r for r in book.finished + list(book.live.values())
              if r.generated]
    if not served:
        return []
    longest = max(served, key=lambda r: len(r.generated))
    rest = [r for r in served if r is not longest]
    rng = np.random.default_rng(int(seed) + 1)
    pick = rng.permutation(len(rest))[:max(0, count - 1)]
    return [longest] + [rest[i] for i in sorted(pick)]


def reference_gaps(params, dims: dict, reqs, max_len: int, dot=None):
    """The gaps of every served position of the sampled requests, one
    array; with ``dot``, those of the tokens ``dot``'s precision puts
    first at the same positions instead."""
    import jax
    import jax.numpy as jnp
    fn = jax.jit(lambda p, t, y: dense_gqa.served_gaps(
        p, dims, t, y, **({} if dot is None else {"dot": dot})))
    out = []
    for r in reqs:
        gen = np.asarray(r.generated, np.int32)
        seq = np.concatenate([r.prompt, gen[:-1]]).astype(np.int32)
        plen, n = len(r.prompt), len(gen)
        toks = np.zeros((max_len,), np.int32)
        toks[:len(seq)] = seq
        tgt = np.zeros((max_len,), np.int32)
        tgt[plen - 1:plen - 1 + n] = gen
        g, c = jax.device_get(fn(params, jnp.asarray(toks),
                                 jnp.asarray(tgt)))
        out.append((g if dot is None else c)[plen - 1:plen - 1 + n])
    return np.concatenate(out)


def gap_checks(gaps: np.ndarray, limits: dict):
    """The compared numbers of a set of gaps beside their limits."""
    entries = [H.check_entry("serve_gap", float(np.max(gaps)),
                             float(limits["serve_gap"])),
               H.check_entry("serve_gap_mean", float(np.mean(gaps)),
                             float(limits["serve_gap_mean"]))]
    return all(e["ok"] for e in entries), entries


def _sample_and_free(state: State, run: H.Run):
    reqs = sample_requests(state.book, run.seed,
                           int(run.config["check"]["requests"]))
    state.rt = None                     # free the program's state first
    import gc
    gc.collect()
    return reqs


def check(state: State, run: H.Run, rec: dict):
    chk = run.config["check"]
    reqs = _sample_and_free(state, run)
    if not reqs:
        return False, [H.check_entry(n, float("inf"), float(v))
                       for n, v in chk["limits"].items()]
    gaps = reference_gaps(state.params, state.dims, reqs,
                          int(run.config["serving"]["max_len"]))
    H.log(f"serve check: {len(reqs)} requests, {gaps.size} served tokens")
    return gap_checks(gaps, chk["limits"])


# The controls, each of which has to come out not correct: the reference
# in the program's place with int8 (W8A8) and with float8 (e4m3)
# contractions, the step below the bfloat16 model the configuration
# states.  ``bf16`` (one-pass bfloat16 contractions) is read beside them:
# it is not the step below a bfloat16 model, and PERF.md gives its
# readings.
CONTROLS = ("int8", "fp8")
READINGS = ("bf16",)


def control(state: State, run: H.Run, rec: dict) -> dict:
    """Each control's check on the same prompts and served positions: the
    gaps of the tokens the control's precision puts first."""
    dots = {"int8": dense_gqa.int8_dot, "fp8": dense_gqa.fp8_dot,
            "bf16": dense_gqa.bf16_dot}
    reqs = _sample_and_free(state, run)
    if not reqs:
        return {}
    return {label: gap_checks(
        reference_gaps(state.params, state.dims, reqs,
                       int(run.config["serving"]["max_len"]),
                       dot=dots[label]),
        run.config["check"]["limits"]) for label in CONTROLS + READINGS}
