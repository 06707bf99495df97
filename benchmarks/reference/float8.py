"""float8's precision for the references' controls ("How correct is decided",
step 2): an operand is rounded to e4m3's 3 mantissa bits on the way into a
matmul or convolution and its cotangent to e5m2's 2 on the way back, with an
ideal scale, i.e. the exponent is kept. It is what a later PR that moves the
bf16 multiplies to fp8 would compute."""

import jax
import jax.numpy as jnp


def round_mantissa(x, bits: int):
    """Round a float32 to `bits` explicit mantissa bits, exponent kept."""
    drop = 23 - bits
    u = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
    u = (u + jnp.uint32(1 << (drop - 1))) & jnp.uint32(~((1 << drop) - 1) & 0xFFFFFFFF)
    return jax.lax.bitcast_convert_type(u, jnp.float32)


@jax.custom_vjp
def fp8(x):
    return round_mantissa(x, 3)


fp8.defvjp(lambda x: (round_mantissa(x, 3), None), lambda _, g: (round_mantissa(g, 2),))
