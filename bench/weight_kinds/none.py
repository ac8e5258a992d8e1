"""``quantization: none``: a decoder matmul is a plain bfloat16 tensor ``[in, out]``."""
import jax
import jax.numpy as jnp


def make(key, shape, std):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(jnp.bfloat16)


def to_float32(w):
    return w.astype(jnp.float32)


def nbytes(shape):
    return 2 * shape[0] * shape[1]
