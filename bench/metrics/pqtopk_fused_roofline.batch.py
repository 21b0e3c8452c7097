"""``pqtopk_fused_roofline.batch``: the fused PQTopK kernel's share of its roofline, %, candidate generation (moves users_per_s)."""
from bench import layers

read = layers.kernel_roofline
