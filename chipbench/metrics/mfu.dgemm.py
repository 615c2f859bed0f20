"""Traced calls' int8 operations as a share of the int8 peak, DGEMM cell (moves gemm_tflops)."""

from chipbench import readers

read = readers.mfu_gemm
