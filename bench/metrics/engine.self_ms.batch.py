"""``engine.self_ms.batch``: the engine's own host milliseconds per batch in prepare, launch and complete less its device wait, candidate generation (moves users_per_s)."""
from bench import engine_spans

read = engine_spans.self_ms
