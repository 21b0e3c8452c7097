"""``engine.self_ms.serve``: the engine's own host milliseconds per batch in prepare, launch and complete less its device wait, online cells (moves p50_ms)."""
from bench import engine_spans

read = engine_spans.self_ms
