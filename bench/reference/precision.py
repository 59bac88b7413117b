"""Rounding to a narrower float, for the references' controls.

A reference runs in float32. Its control runs the same code with values
rounded, where the configuration's program rounds to its own compute
type, to the next narrower one. ``rounder(bits)`` rounds the mantissa
of every value to ``bits`` explicit bits (bfloat16: 7, float8 e4m3: 3)
on the way forward, and the cotangent the same way on the way back, so
that both passes of a training step run at that precision. The exponent
keeps float32's range: this is the per-tensor-scaled use of a narrow
type, not its overflow.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

#: explicit mantissa bits of the types a control may run in
MANTISSA_BITS = {"float32": 23, "bfloat16": 7, "float8_e4m3": 3}


def round_mantissa(x, bits: int):
    """Round to nearest on ``bits`` explicit mantissa bits."""
    x = jnp.asarray(x, jnp.float32)
    if bits >= 23:
        return x
    if bits == 7:                        # bfloat16 itself, nearest-even
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    m, e = jnp.frexp(x)                  # x = m · 2^e, 0.5 <= |m| < 1
    scale = float(2 ** (bits + 1))
    return jnp.ldexp(jnp.round(m * scale) / scale, e)


def rounder(dtype: str):
    """Identity for float32; else a straight-through rounding whose
    backward pass rounds the cotangent to the same precision."""
    bits = MANTISSA_BITS[dtype]
    if bits >= 23:
        return lambda x: x

    @jax.custom_vjp
    def rnd(x):
        return round_mantissa(x, bits)

    def fwd(x):
        return round_mantissa(x, bits), None

    def bwd(_, g):
        return (round_mantissa(g, bits),)

    rnd.defvjp(fwd, bwd)
    return rnd
