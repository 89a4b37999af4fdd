"""Hand-written Pallas TPU kernels.

Analog slot of the reference's custom CUDA kernels + NVRTC runtime
compilation (ref: src/common/rtc.cc, src/operator/nn/cudnn/,
src/kvstore/gradient_compression.cu): ops where XLA's automatic fusion
isn't enough get explicit MXU/VMEM tiling here. Everything has a pure
jnp fallback so CPU runs (and the virtual-device test mesh) work
unchanged; on TPU the Pallas path is selected automatically.

Module contract (enforced by mxlint MX012): every kernel module
exports a reference implementation (``*_reference`` / ``*_jnp``) with
identical semantics, takes an ``interpret=`` path so the CPU tier-1
suite runs the real kernel code in interpreter mode, and is registered
in ``KERNEL_BENCH`` below — the map from kernel module to the
``BENCH_MODEL`` that prices it (``fused_kernels`` is the shared gate
for the PR 9 campaign kernels: >=1.5x vs the XLA baseline on a real
backend, interpret-mode parity + ULP/bitwise bound on CPU). Kernel
first-builds register in ``profiler.record_compile`` via
``_compile_attr.attributed`` and appear in the Compile table
(docs/OBSERVABILITY.md).
"""
from .flash_attention import flash_attention  # noqa: F401
from .compression import (quantize_2bit, dequantize_2bit,  # noqa: F401
                          quantize_2bit_jnp, dequantize_2bit_jnp)
from .batchnorm_fused import fused_batch_norm  # noqa: F401
from .optimizer_apply import packed_apply  # noqa: F401
from .quantized_matmul import quantized_matmul  # noqa: F401

# kernel module -> the BENCH_MODEL whose gate prices it (mxlint MX012
# requires every kernel module to appear here; bench.py
# BENCH_MODEL=fused_kernels iterates the 'fused_kernels' entries)
KERNEL_BENCH = {
    "flash_attention": "transformer",
    "grouped_matmul": "transformer",
    "moe_rows": "transformer",
    "compression": "comm_overlap",
    "conv_fused": "resnet50",
    "batchnorm_fused": "fused_kernels",
    "optimizer_apply": "fused_kernels",
    "quantized_matmul": "fused_kernels",
}
