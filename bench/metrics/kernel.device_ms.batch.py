"""``kernel.device_ms.batch``: device milliseconds of the fused PQTopK kernel per serve step, candidate generation (moves users_per_s)."""
from bench import layers

read = layers.kernel_device_ms
