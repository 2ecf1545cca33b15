"""Pallas TPU kernels for the scheme's three hot spots:

  * split_fused      — steps (i)/(ii): k-slice extraction in one HBM pass
  * group_gemm       — steps (iii)+(iv) merged: int8 GEMM with int32 VMEM
                       group accumulation (Alg. 6/7 on the MXU)
  * scale_accum      — step (iv) epilogue: fused convert+scale+compensated-add
  * flash_attention  — fused online-softmax attention fwd (removes the
                       O(L^2) HBM score traffic identified in §Perf Cell A)

Each has a pure-jnp oracle in ref.py.  The wrappers in ops.py compile
the kernels through Mosaic when a program lowers for a TPU and interpret
them when it lowers for the CPU, where the tests sweep shapes/dtypes;
tests/test_tpu_compile.py compiles them for a described v5e.
"""
