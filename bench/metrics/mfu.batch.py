"""``mfu.batch``: useful model FLOPs over serve-step device time, % of the bf16 peak, candidate generation (moves users_per_s)."""
from bench import layers

read = layers.step_mfu
