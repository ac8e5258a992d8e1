"""``quantization: int8``: a decoder matmul is an int8 tensor ``q`` ``[in, out]`` with one float32 scale per output channel, the weight being ``q * s`` (the program's ``ops/quant.py`` leaf). The int8 values ARE the weights: drawn directly, no wider tensor rounded."""
import jax
import jax.numpy as jnp

# std of the int8 integers; 127/42 ~ 3 sigma, so ~0.3% of draws clip at +-127
Q_STD = 42.0


def make(key, shape, std):
    kq, ks = jax.random.split(key)
    q = jnp.clip(jnp.round(jax.random.normal(kq, shape, jnp.float32) * Q_STD),
                 -127, 127).astype(jnp.int8)
    s = (std / Q_STD) * (
        1.0 + 0.25 * jax.random.uniform(ks, (shape[1],), jnp.float32, -1.0, 1.0))
    return {"q": q, "s": s}


def to_float32(w):
    return w["q"].astype(jnp.float32) * w["s"][None, :]


def nbytes(shape):
    return shape[0] * shape[1] + 4 * shape[1]
