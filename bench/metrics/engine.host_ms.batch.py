"""``engine.host_ms.batch``: host milliseconds per dispatched batch in the engine's prepare, launch and complete, candidate generation (moves users_per_s)."""
from bench import layers

read = layers.host_ms
