"""Device idle time inside the engine's step spans per executed step, decode-heavy (moves output_tokens_per_s)."""

from chipbench import engine_trace

read = engine_trace.read_host_gap_ms
