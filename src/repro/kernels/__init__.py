"""Pallas TPU kernels for the paper's perf-critical memory-boundary ops.

quant_cast    — tiled fake-quant Q(I,F) (paper §2.1 conversion)
pack/unpack   — k N-bit values <-> int32 lanes ("N-bit memory" on TPU HBM)
quant_matmul  — int8-weight matmul, dequant-in-VMEM, per-channel scales
paged_kv_attention — decode and chunk attention over a paged int8/int4 KV
                     pool (page-table gather via scalar prefetch)

Use via ``repro.kernels.ops`` (jit'd, interpret-mode auto on CPU); oracles in
``repro.kernels.ref``.
"""
from . import ops, ref

__all__ = ["ops", "ref"]
