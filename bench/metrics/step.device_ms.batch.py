"""``step.device_ms.batch``: device milliseconds per execution of the serve program, candidate generation (moves users_per_s)."""
from bench import layers

read = layers.step_device_ms
