"""``mfu.serve``: useful model FLOPs over serve-step device time, % of the bf16 peak, online cells (moves p50_ms)."""
from bench import layers

read = layers.step_mfu
