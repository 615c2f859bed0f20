"""Device time under the Scheme-II CRT scopes per traced call, DGEMM cell (moves gemm_tflops)."""

from chipbench import scheme2_trace

read = scheme2_trace.crt_ms
