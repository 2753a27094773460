"""Pallas TPU kernels: interpret mode on the CPU, compiled on the TPU.

tests/test_chip_compile.py compiles the main-path kernels for a described
v5e chip; the direct circulant_matvec kernel does not compile there and
runs only in CPU tests.

Paper hot spots: circulant_matvec (Algs. 4-8), soft_threshold (Eq. 4 fused),
spectral_pointwise (CPADMM freq-domain update), cpadmm_tail (the whole
elementwise iteration tail in one VMEM pass), banded_conv (Sec. 7 blur).
LM substrate: flash_attention (identified by the roofline analysis).
Each subpackage: kernel.py (pallas_call + BlockSpec) + ops.py + ref.py.
"""
