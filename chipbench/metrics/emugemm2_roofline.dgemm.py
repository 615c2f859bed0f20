"""The Scheme-II residue kernel's least time over its device time, DGEMM cell (moves gemm_tflops)."""

from chipbench import scheme2_trace

read = scheme2_trace.kernel_roofline
