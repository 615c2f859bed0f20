"""Share of the traced window with no op on the device, GEMM cells (moves gemm_tflops)."""

from chipbench import readers

read = readers.device_idle
