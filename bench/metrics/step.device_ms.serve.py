"""``step.device_ms.serve``: device milliseconds per execution of the serve program, online cells (moves p50_ms)."""
from bench import layers

read = layers.step_device_ms
