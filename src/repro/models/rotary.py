"""Rotary position embeddings (RoPE), including partial-dim RoPE for MLA
and YaRN context extension (DeepSeek-V2)."""

from __future__ import annotations

import dataclasses
import math

import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class YaRN:
    """YaRN rope scaling (arXiv:2309.00071) as DeepSeek-V2's released
    modelling code computes it: frequencies below ``beta_slow`` rotations
    over the original context are divided by ``factor``, those above
    ``beta_fast`` kept, with a linear ramp between; cos and sin are scaled by
    ``mscale(mscale) / mscale(mscale_all_dim)`` and the softmax scale by
    ``mscale(mscale_all_dim)**2``."""

    factor: float = 40.0
    original_max_position_embeddings: int = 4096
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 0.707
    mscale_all_dim: float = 0.707

    def attn_factor(self, m: float) -> float:
        return 0.1 * m * math.log(self.factor) + 1.0 if self.factor > 1 else 1.0

    @property
    def cos_sin_scale(self) -> float:
        return self.attn_factor(self.mscale) / self.attn_factor(self.mscale_all_dim)

    @property
    def softmax_scale(self) -> float:
        """Factor on the ``qk_dim ** -0.5`` softmax scale."""
        return self.attn_factor(self.mscale_all_dim) ** 2

    def ramp_bounds(self, dim: int, theta: float) -> tuple[int, int]:
        """Channel pairs ``[low, high]`` between which the ramp runs."""
        def corr(rot):
            return (dim * math.log(self.original_max_position_embeddings
                                   / (rot * 2 * math.pi))
                    / (2 * math.log(theta)))
        low = max(math.floor(corr(self.beta_fast)), 0)
        high = min(math.ceil(corr(self.beta_slow)), dim - 1)
        return low, high


def rope_freqs(head_dim: int, theta: float = 10000.0,
               scaling: YaRN | None = None) -> jnp.ndarray:
    """Inverse frequencies, shape (head_dim // 2,), float32."""
    exponents = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    freqs = 1.0 / (theta ** exponents)
    if scaling is None:
        return freqs
    low, high = scaling.ramp_bounds(head_dim, theta)
    i = jnp.arange(head_dim // 2, dtype=jnp.float32)
    ramp = jnp.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    return freqs / scaling.factor * ramp + freqs * (1.0 - ramp)


def rope_cos_sin(positions: jnp.ndarray, head_dim: int, theta: float = 10000.0,
                 scaling: YaRN | None = None):
    """cos/sin tables for given positions.

    positions: (...,) int32  ->  cos, sin: (..., head_dim // 2) float32
    """
    freqs = rope_freqs(head_dim, theta, scaling)
    angles = positions[..., None].astype(jnp.float32) * freqs
    if scaling is None:
        return jnp.cos(angles), jnp.sin(angles)
    m = scaling.cos_sin_scale
    return jnp.cos(angles) * m, jnp.sin(angles) * m


def apply_rope(x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray) -> jnp.ndarray:
    """Apply rotary embedding.

    x: (..., S, H, D) with cos/sin (..., S, D//2); broadcasting over heads.
    Uses the "split-half" convention (as in Llama/Gemma reference code).
    """
    d2 = x.shape[-1] // 2
    x1, x2 = x[..., :d2], x[..., d2:]
    # cos/sin: (..., S, d2) -> (..., S, 1, d2) to broadcast over the head dim.
    cos_b = cos[..., None, :]
    sin_b = sin[..., None, :]
    out1 = x1 * cos_b - x2 * sin_b
    out2 = x2 * cos_b + x1 * sin_b
    return jnp.concatenate([out1, out2], axis=-1).astype(x.dtype)


def apply_rope_partial(x: jnp.ndarray, cos, sin, rope_dim: int) -> jnp.ndarray:
    """RoPE on the *last* ``rope_dim`` channels only (DeepSeek MLA layout)."""
    if rope_dim == x.shape[-1]:
        return apply_rope(x, cos, sin)
    pass_dim = x.shape[-1] - rope_dim
    x_pass, x_rope = x[..., :pass_dim], x[..., pass_dim:]
    return jnp.concatenate([x_pass, apply_rope(x_rope, cos, sin)], axis=-1)
