"""Share of the traced window with no op on the device, prefill-heavy (moves ttft_p90_ms)."""

from chipbench import readers

read = readers.device_idle
