"""Device time under the Scheme-II encode scopes per traced call, DGEMM cell (moves gemm_tflops)."""

from chipbench import scheme2_trace

read = scheme2_trace.encode_ms
