"""The whole serving step's share of the chip's bf16 peak, over the window:
operations the model needs (model_math) / (seconds x chips x peak).
host_clock."""
from readers import step_mfu as read  # noqa: F401
