"""Device time of the grouped LoRA Pallas kernels (``grouped_lora_fwd``
and ``grouped_lora_bwd``) inside the training steps, per traced step (ms)."""
from devtime import kernel_ms_per_step


def read(ctx):
    return kernel_ms_per_step(ctx["trace"], "grouped_lora_fwd", "grouped_lora_bwd")
