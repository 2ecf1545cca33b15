"""Record ``tpu_serve_tiny.xplane.pb``: decode rounds of a tiny dense
model served through the serve driver on a TPU, traced as a
``--trace 1`` run traces the serve cell.

    python bench/tests/data/record_serve_trace.py OUT.xplane.pb [SECONDS]

Two layers at d = 256, 4 slots, ``ozimmu_h-auto:df32:fused``, a 0.2 s
window; the traced stretch ends after the first round that ends
``SECONDS`` or more into the window.  At the default, 0.08, that is the
first round, since the profiler takes longer to start (on a TPU v5e:
one decode round, 0.72 MB); a trace of the whole window holds 49 rounds
in 6.8 MB.  The ``/host:metadata`` plane, the programs' HLO that no
reduction reads, is left out of the file.  The profiler's own option
cannot do that: with ``ProfileOptions.enable_hlo_proto`` off the plane
is gone on the CPU, but a TPU v5e still writes it (1.8 MB of a 3.2 MB
trace of 12 rounds), so the file's planes are filtered here.
"""
from __future__ import annotations

import glob
import shutil
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[3]

CONFIG = {
    "name": "tiny_dense", "driver": "serve", "hidden_size": 256,
    "intermediate_size": 512, "num_attention_heads": 4,
    "num_key_value_heads": 2, "num_hidden_layers": 2, "vocab_size": 1024,
    "rope_theta": 10000, "rms_norm_eps": 1e-5,
    "serving": {"spec": "ozimmu_h-auto:df32:fused", "dtype": "bfloat16",
                "presplit": True, "page_block": 16, "prefill_chunk": 16,
                "max_len": 128, "slots": 4},
}
TRAFFIC = {"kind": "closed_loop",
           "prompt_len": {"dist": "uniform", "min": 8, "max": 16},
           "output_len": {"dist": "log_uniform", "min": 32, "max": 64},
           "pool": 16, "shape_seed": 0, "ramp_rounds": 2,
           "trace_seconds": 0.08}
DROPPED_PLANE = b"/host:metadata"


def _varint(b: bytes, i: int):
    value = shift = 0
    while True:
        x = b[i]
        i += 1
        value |= (x & 0x7F) << shift
        shift += 7
        if x < 0x80:
            return value, i


def drop_plane(space: bytes, name: bytes) -> bytes:
    """A serialized ``XSpace`` without its planes called ``name``: each
    plane is a length-delimited field 1, whose name is its field 2."""
    out, i = bytearray(), 0
    while i < len(space):
        start = i
        key, i = _varint(space, i)
        if key & 7 == 0:
            _, i = _varint(space, i)
        elif key & 7 == 2:
            n, i = _varint(space, i)
            body, i = space[i:i + n], i + n
            if key >> 3 == 1 and _plane_name(body) == name:
                continue
        else:
            raise ValueError(f"unexpected wire type {key & 7} in XSpace")
        out += space[start:i]
    return bytes(out)


def _plane_name(plane: bytes) -> bytes:
    i = 0
    while i < len(plane):
        key, i = _varint(plane, i)
        wt = key & 7
        if wt == 0:
            _, i = _varint(plane, i)
        elif wt == 1 or wt == 5:
            i += 8 if wt == 1 else 4
        else:
            n, i = _varint(plane, i)
            if key >> 3 == 2:
                return plane[i:i + n]
            i += n
    return b""


def main(out: str, trace_seconds: float) -> None:
    from repro.launch.entry import prepare_entry_point
    prepare_entry_point()
    import jax
    from bench import harness as H
    from bench.drivers import serve
    devices = H.require_chips(jax, 1)
    tmp = tempfile.mkdtemp(prefix="serve_trace_")
    run = H.Run(cell={"name": "tiny_dense.record"}, config=CONFIG,
                traffic=dict(TRAFFIC, trace_seconds=trace_seconds),
                seed=1, seconds=0.2, trace=True, devices=devices, peaks={},
                trace_dir=tmp)
    state = serve.setup(run)
    serve.window(state, run)
    src = sorted(glob.glob(f"{tmp}/plugins/profile/*/*.xplane.pb"))[-1]
    Path(out).write_bytes(drop_plane(Path(src).read_bytes(), DROPPED_PLANE))
    shutil.rmtree(tmp, ignore_errors=True)
    print(f"{out}: {Path(out).stat().st_size} bytes")


if __name__ == "__main__":
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    main(sys.argv[1], float(sys.argv[2]) if len(sys.argv) > 2
         else TRAFFIC["trace_seconds"])
