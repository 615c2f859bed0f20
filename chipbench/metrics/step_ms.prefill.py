"""Traced window over engine steps in the prefill-heavy cell (moves ttft_p90_ms)."""

from chipbench import readers

read = readers.step_ms
