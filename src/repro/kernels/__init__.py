"""Pallas TPU kernels of the hot path, each package a ``kernel.py``
(the Pallas body), an ``ops.py`` (padding + dispatch) and a ``ref.py``
(the jnp oracle)."""
import jax
import jax.numpy as jnp


def use_pallas(mode: str) -> bool:
    """The dispatch policy every kernel package shares:

      * ``"auto"``      — compiled Pallas on TPU, the jnp oracle elsewhere;
      * ``"pallas"``    — compiled Pallas (TPU only);
      * ``"interpret"`` — Pallas in interpret mode (CPU validation path);
      * ``"ref"``       — the jnp oracle.
    """
    if mode == "auto":
        return jax.default_backend() == "tpu"
    return mode in ("pallas", "interpret")


def f32_precision(dtype):
    """Matmul precision for exact distances: ``HIGHEST`` (full f32
    contraction) for f32 operands, since XLA's default f32 matmul on TPU
    is one bf16 pass and an exact index must not rest on a backend
    default; ``None`` (the native pass) for bf16 operands, which Mosaic
    refuses to contract at f32 precision."""
    if jnp.dtype(dtype) == jnp.float32:
        return jax.lax.Precision.HIGHEST
    return None
