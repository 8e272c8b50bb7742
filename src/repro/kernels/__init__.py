"""Pallas TPU kernels: batched probing, paged attention, fused decode."""
import jax


def on_tpu() -> bool:
    """Whether the program runs on a TPU: the one answer both the kernels'
    interpret mode (interpreted everywhere else) and the engine's choice of
    decode-attention path read."""
    return jax.default_backend() == "tpu"
