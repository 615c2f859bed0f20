"""Share of the traced window with no op on the device, decode-heavy (moves output_tokens_per_s)."""

from chipbench import readers

read = readers.device_idle
