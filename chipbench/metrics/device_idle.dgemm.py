"""Share of the traced window with no op on the device, DGEMM cell (moves gemm_tflops)."""

from chipbench import readers

read = readers.device_idle
