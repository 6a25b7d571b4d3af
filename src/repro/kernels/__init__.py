"""Pallas TPU kernels for QuantEase's compute hot-spots.

* quantease_cd.py — intra-block CD sweep (the PTQ-time hot loop).
* dequant_matmul.py — fused dequant+GEMM (the serve-time hot loop).
* ops.py — jit'd dispatchers (TPU Mosaic vs CPU interpret).
* ref.py — pure-jnp oracles, the contract for tests.
"""

# Scoped VMEM every kernel here asks Mosaic for.  A TPU v5e core has 128 MiB
# of VMEM; Mosaic's default scope (16 MiB) is too small for the CD kernels'
# resident (p × TQ) slabs at published widths.  The fit gates in ops.py
# budget against a margin below this.
VMEM_LIMIT_BYTES = 96 * 1024 * 1024
