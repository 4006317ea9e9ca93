"""Pallas TPU kernels for SALR's performance-critical compute paths.

Each kernel ships three pieces:
  * ``<name>.py``  -- pl.pallas_call + explicit BlockSpec VMEM tiling
  * ``ops.py``     -- jit'd public wrapper (padding, batching, dispatch)
  * ``ref.py``     -- pure-jnp oracle the kernel is allclose-tested against

Kernels (see DESIGN.md §3 for the GPU->TPU adaptation rationale):
  bitmap_spmm  -- fused bitmap-decode + GEMM (two-stage pipeline)
  nm_spmm      -- 2:4 semi-structured decode + GEMM (select-network)
  salr_spmm    -- bitmap GEMM + concatenated-adapter GEMM in one kernel
  fused_lora   -- concatenated multi-adapter GEMM (adapter path alone)
  nf4_spmm     -- NF4 dequant + GEMM (QSALR)
  grouped_spmm -- ragged grouped GEMM over expert-stacked bases (MoE
                  k-way dispatch; tile->expert map via scalar prefetch)
  bitmap_decode -- the in-kernel tiled-bitmap decode every bitmap-family
                  GEMM shares (shifts, MXU prefix popcount, select), and
                  the same decode alone writing W^T (the train route)

See docs/kernels.md for the kernel-authoring guide (wrapper decorator
contract, tiled layout, custom-VJP convention, grouped grid design).
"""
from repro.kernels import ops, ref
from repro.kernels.ops import (bitmap_matmul, grouped_dense_matmul,
                               grouped_nm_matmul, grouped_qsalr_matmul,
                               grouped_salr_matmul, lora_matmul,
                               nf4_encode_2d, nf4_matmul, nm_matmul,
                               salr_matmul)

__all__ = ["ops", "ref", "bitmap_matmul", "lora_matmul", "nf4_encode_2d",
           "nf4_matmul", "nm_matmul", "salr_matmul",
           "grouped_dense_matmul", "grouped_salr_matmul",
           "grouped_qsalr_matmul", "grouped_nm_matmul"]
