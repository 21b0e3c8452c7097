"""``engine.host_ms.serve``: host milliseconds per dispatched batch in the engine's prepare, launch and complete, online cells (moves p50_ms)."""
from bench import layers

read = layers.host_ms
