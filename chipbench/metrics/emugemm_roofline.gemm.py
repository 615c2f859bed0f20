"""Emulated sites' roofline share in the GEMM cells (moves gemm_tflops)."""

from chipbench import readers

read = readers.emugemm_roofline
