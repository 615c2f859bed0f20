"""Device idle time inside the engine's step spans per executed step, prefill-heavy (moves ttft_p90_ms)."""

from chipbench import engine_trace

read = engine_trace.read_host_gap_ms
