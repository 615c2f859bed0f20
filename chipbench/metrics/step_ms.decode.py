"""Traced window over engine steps in the decode-heavy cell (moves output_tokens_per_s)."""

from chipbench import readers

read = readers.step_ms
