"""``kernel.device_ms.serve``: device milliseconds of the fused PQTopK kernel per serve step, online cells (moves p50_ms)."""
from bench import layers

read = layers.kernel_device_ms
