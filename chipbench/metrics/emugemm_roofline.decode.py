"""Emulated sites' roofline share in the decode-heavy serving cell (moves output_tokens_per_s)."""

from chipbench import readers

read = readers.emugemm_roofline
