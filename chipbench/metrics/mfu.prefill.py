"""Useful model FLOPs over engine-step time, share of the bf16 peak (moves ttft_p90_ms)."""

from chipbench import readers

read = readers.mfu_serve
