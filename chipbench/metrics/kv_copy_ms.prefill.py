"""Device time under the engine's KV gather and scatter scopes per executed step, prefill-heavy (moves ttft_p90_ms)."""

from chipbench import engine_trace

read = engine_trace.read_kv_copy_ms
