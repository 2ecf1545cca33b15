"""The benchmark's yardsticks that need no chip: kernel operations and
bytes against hand counts, the traffic generator's determinism, and the
trace reduction's interval arithmetic."""
from __future__ import annotations

import numpy as np
import pytest

from bench import harness as H
from bench import kernel_cost as K
from bench import trace as T
from bench.drivers import serve


def test_group_gemm_ops_and_bytes_by_hand():
    # one call of 3 pairs of (2 x 4) @ (4 x 5) int8 slices: 3 * 2*4*5
    # multiply-adds, 3*(8 + 20) int8 bytes in, 2*5 int32 out
    assert K.group_gemm_ops(2, 4, 5, 3) == 3 * 2 * (2 * 4 * 5)
    assert K.group_gemm_bytes(2, 4, 5, 3, 1) == 3 * (8 + 20) + 4 * 10
    # 21 pairs of 8192^3 in 6 calls: 23.09 TOP; 2.82 GB in, 1.61 GB out
    assert K.group_gemm_ops(8192, 8192, 8192, 21) == pytest.approx(
        23.09e12, rel=1e-3)
    assert K.group_gemm_bytes(8192, 8192, 8192, 21, 6) == \
        21 * 2 * 8192 ** 2 + 6 * 4 * 8192 ** 2


def test_scale_accum_bytes_by_hand():
    # int32 product in, hi and lo in and out, one row and one column scale
    assert K.scale_accum_bytes(2, 3, 1) == 4 * 6 + 8 * 6 + 8 * 6 + 4 * 5
    assert K.scale_accum_bytes(16384, 16384, 5) == 5 * (
        20 * 16384 ** 2 + 4 * 2 * 16384)


def test_roofline_share_takes_the_binding_bound():
    # 1 TOP at 100 TOP/s is 10 ms; 1 GB at 1 TB/s is 1 ms: compute-bound
    assert K.roofline_share(1e12, 1e9, 0.02, 100e12, 1e12) == \
        pytest.approx(50.0)
    assert K.roofline_share(0.0, 1e9, 0.002, 100e12, 1e12) == \
        pytest.approx(50.0)
    assert K.roofline_share(1.0, 1.0, 0.0, 1.0, 1.0) is None


TRAFFIC = {"kind": "closed_loop",
           "prompt_len": {"dist": "uniform", "min": 16, "max": 32},
           "output_len": {"dist": "log_uniform", "min": 256, "max": 1024},
           "pool": 64, "shape_seed": 0, "ramp_rounds": 1,
           "trace_seconds": 1}


def _requests(seed, n=20):
    s = serve.Stream(TRAFFIC, seed, vocab=1000)
    return [s.next() for _ in range(n)]


def test_traffic_same_seed_same_stream():
    a, b = _requests(2 ** 33 + 5), _requests(2 ** 33 + 5)
    assert all(np.array_equal(x[0], y[0]) and x[1] == y[1]
               for x, y in zip(a, b))
    c = _requests(7)
    assert any(len(x[0]) != len(y[0]) or not np.array_equal(x[0], y[0])
               for x, y in zip(a, c))


def test_traffic_every_seed_serves_the_same_sizes():
    sizes = []
    for seed in (1, 2, 2 ** 40 + 3):
        s = serve.Stream(TRAFFIC, seed, vocab=1000)
        got = [s.next() for _ in range(64)]       # one pass over the pool
        sizes.append(sorted((len(p), o) for p, o in got))
    assert sizes[0] == sizes[1] == sizes[2]


def test_traffic_lengths_stay_in_range():
    rng = np.random.default_rng(0)
    for spec in (TRAFFIC["prompt_len"], TRAFFIC["output_len"]):
        x = serve.draw(spec, rng, 5000)
        assert x.min() >= spec["min"] and x.max() <= spec["max"]


def test_warm_lengths_cover_every_bucket_and_span():
    lens = serve.warm_prompt_lengths(TRAFFIC, block=16, chunk=64)
    assert lens == [16, 17]      # (bucket 16, 1 block), (bucket 32, 2)


def test_union_and_gaps():
    iv = [(0, 2), (1, 3), (5, 6), (5.5, 5.7)]
    assert T.union_length(iv) == 4
    assert T.gaps(iv, 0, 8) == [(3, 5), (6, 8)]
    assert T.gaps(iv, -1, 2) == [(-1, 0)]


def test_seed_key_takes_seeds_beyond_32_bits():
    import jax
    k1 = H.seed_key(jax, 2 ** 31 + 11)
    k2 = H.seed_key(jax, 2 ** 33 + 11)
    assert not np.array_equal(jax.random.key_data(k1),
                              jax.random.key_data(k2))
