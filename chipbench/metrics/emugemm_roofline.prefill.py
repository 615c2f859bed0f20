"""Emulated sites' roofline share in the prefill-heavy serving cell (moves ttft_p90_ms)."""

from chipbench import readers

read = readers.emugemm_roofline
