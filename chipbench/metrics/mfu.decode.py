"""Useful model FLOPs over engine-step time, share of the bf16 peak (moves output_tokens_per_s)."""

from chipbench import readers

read = readers.mfu_serve
