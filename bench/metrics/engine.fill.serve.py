"""``engine.fill.serve``: live rows over the padded rows the engine sent to the device (%), online cells (moves p50_ms)."""
from bench import engine_spans

read = engine_spans.fill
