"""Plain references the benchmark checks the served outputs against.

They import nothing of the program under test: they are written from the
configuration's published description (paper Eqs. 1-5 for KAN layers,
the Qwen2 architecture for the transformer) in straightforward
``jax.numpy`` at float32, every contraction at ``Precision.HIGHEST``.
``matmul`` also computes the lower precisions a control check uses.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST

# The contraction precisions the references understand:
#   highest -- float32 (the reference itself)
#   bf16x3  -- three bf16 passes (hi*hi + hi*lo + lo*hi), what the TPU's
#              Precision.HIGH computes: the control for float32 at highest
#   fp8     -- both operands rounded to float8_e4m3 under a per-tensor
#              scale: the control for bfloat16
MODES = ("highest", "bf16x3", "fp8")

# Rounding goes through ``reduce_precision``, which XLA keeps: a round
# trip through a narrower dtype may be folded away as excess precision.
# fp8 is e4m3 with IEEE exponent rules, largest finite value 240.
_FP8_MAX = 240.0


def _round(x: jax.Array, exponent_bits: int, mantissa_bits: int
           ) -> jax.Array:
    return jax.lax.reduce_precision(x, exponent_bits=exponent_bits,
                                    mantissa_bits=mantissa_bits)


def _fp8(a: jax.Array) -> jax.Array:
    scale = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / _FP8_MAX
    return _round(a / scale, 4, 3) * scale


def matmul(a: jax.Array, b: jax.Array, mode: str = "highest") -> jax.Array:
    """``a @ b`` in float32 operands, contracted as ``mode`` says."""
    a = a.astype(jnp.float32)
    b = b.astype(jnp.float32)
    if mode == "highest":
        return jnp.matmul(a, b, precision=HIGHEST,
                          preferred_element_type=jnp.float32)
    if mode == "bf16x3":
        def split(x):
            hi = _round(x, 8, 7)
            return hi, _round(x - hi, 8, 7)

        (ah, al), (bh, bl) = split(a), split(b)

        def d(x, y):
            # bf16-valued operands: their products are exact in f32
            return jnp.matmul(x, y, precision=HIGHEST,
                              preferred_element_type=jnp.float32)

        return d(ah, bh) + (d(ah, bl) + d(al, bh))
    if mode == "fp8":
        return jnp.matmul(_fp8(a), _fp8(b), precision=HIGHEST,
                          preferred_element_type=jnp.float32)
    raise ValueError(f"unknown contraction mode {mode!r}; one of {MODES}")
