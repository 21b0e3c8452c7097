"""``engine.queue_ms.serve``: mean milliseconds a request waited in the engine's queue, from arrival to its batch's prepare, online cells (moves p50_ms)."""
from bench import engine_spans

read = engine_spans.queue_ms
