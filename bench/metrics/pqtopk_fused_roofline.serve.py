"""``pqtopk_fused_roofline.serve``: the fused PQTopK kernel's share of its roofline, %, online cells (moves p50_ms)."""
from bench import layers

read = layers.kernel_roofline
