"""Device time under the engine's KV gather and scatter scopes per executed step, decode-heavy (moves output_tokens_per_s)."""

from chipbench import engine_trace

read = engine_trace.read_kv_copy_ms
