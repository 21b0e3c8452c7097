"""``engine.flight_ms.serve``: mean milliseconds a batch was in flight, from launch to the end of the engine's device wait, online cells (moves p50_ms)."""
from bench import engine_spans

read = engine_spans.flight_ms
