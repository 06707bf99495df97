"""Rotary positions: the angles of a position, with or without YaRN's
frequency scaling, and the rotation itself. Both the latent attention of
models/moe.py and the ``full`` / ``window`` mixers of models/transformer.py
take them from here.

A pair j of a vector of ``dim`` entries turns by position times
``theta^(-2j / dim)``. YaRN (Peng et al., arXiv:2309.00071) leaves the fast
pairs alone, divides the slow pairs' frequencies by ``factor``, blends the
two over a ramp between the pairs that turn ``beta_fast`` and ``beta_slow``
times in the ``original_len`` positions the model was first trained at, and
multiplies cos and sin by ``attention_factor``, so the logits of a layer that
rotates both queries and keys carry its square. The scaling is static: it
applies at every length.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class Yarn:
    factor: float
    original_len: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 0.0  # 0: 0.1 ln(factor) + 1

    @property
    def scale(self) -> float:
        return self.attention_factor or 0.1 * math.log(self.factor) + 1.0

    def ramp_bounds(self, dim: int, theta: float) -> tuple[int, int]:
        """The pairs between which the frequencies are blended: below ``low``
        a pair keeps its frequency, above ``high`` it has it divided."""

        def pair_turning(turns: float) -> float:
            return dim * math.log(self.original_len / (2 * math.pi * turns)) / (2 * math.log(theta))

        low = max(math.floor(pair_turning(self.beta_fast)), 0)
        high = min(math.ceil(pair_turning(self.beta_slow)), dim - 1)
        return low, high


@dataclasses.dataclass(frozen=True)
class Rotary:
    """A mixer kind's rule: the base, and YaRN's record where it is scaled."""

    theta: float
    yarn: Optional[Yarn] = None


def rotary_angles(positions: jax.Array, dim: int, theta: float, yarn: Optional[Yarn] = None):
    """cos and sin, (S, dim / 2) in float32, of position times
    theta^(-2j / dim) for the pair j; under ``yarn`` with its frequencies and
    its factor."""
    inv_freq = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    if yarn is not None:
        low, high = yarn.ramp_bounds(dim, theta)
        ramp = jnp.clip(
            (jnp.arange(dim // 2, dtype=jnp.float32) - low) / max(high - low, 1e-3), 0.0, 1.0
        )
        inv_freq = inv_freq * (1.0 - ramp) + inv_freq / yarn.factor * ramp
    angles = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    if yarn is None:
        return jnp.cos(angles), jnp.sin(angles)
    return jnp.cos(angles) * yarn.scale, jnp.sin(angles) * yarn.scale


def rotary(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """Rotate the pairs (j, j + dim / 2) of the last axis (the half-split
    pairing) by their angle; ``cos`` and ``sin`` broadcast against the halves.
    In float32, back in x's dtype."""
    first, second = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate(
        [first * cos - second * sin, second * cos + first * sin], axis=-1
    ).astype(x.dtype)
