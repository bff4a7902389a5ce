"""Rotary position embeddings (Llama-style, with Llama-3 rope scaling).

TPU notes: computed in float32 then cast back — RoPE precision matters for
long context, and the VPU handles the elementwise work fused into the
surrounding matmuls by XLA.
"""

from __future__ import annotations

import math

import jax.numpy as jnp


def rope_frequencies(
    head_dim: int, theta: float, scaling: dict | None = None
) -> jnp.ndarray:
    """Inverse frequencies [head_dim/2], with optional llama3 scaling."""
    inv_freq = 1.0 / (
        theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)
    )
    if not scaling:
        return inv_freq
    kind = scaling.get("rope_type", scaling.get("type"))
    if kind in (None, "default"):
        return inv_freq
    if kind == "linear":
        return inv_freq / scaling["factor"]
    if kind != "llama3":
        raise ValueError(f"unsupported rope_scaling type: {kind!r}")
    factor = scaling["factor"]
    low = scaling.get("low_freq_factor", 1.0)
    high = scaling.get("high_freq_factor", 4.0)
    old_ctx = scaling.get("original_max_position_embeddings", 8192)
    wavelen = 2 * math.pi / inv_freq
    low_wl = old_ctx / low
    high_wl = old_ctx / high
    smooth = (old_ctx / wavelen - low) / (high - low)
    return jnp.where(
        wavelen > low_wl,
        inv_freq / factor,
        jnp.where(
            wavelen < high_wl,
            inv_freq,
            (1 - smooth) * inv_freq / factor + smooth * inv_freq,
        ),
    )


def apply_rope(
    x: jnp.ndarray, positions: jnp.ndarray, inv_freq: jnp.ndarray
) -> jnp.ndarray:
    """Rotate ``x``: [..., T, H, D] by per-token ``positions``: [..., T].

    Uses the HF "half-split" convention (rotate_half), matching Llama
    checkpoints: pairs are (x[i], x[i + D/2]).
    """
    angles = positions[..., None].astype(jnp.float32) * inv_freq  # [..., T, D/2]
    return _rotate(x, angles)


def _rotate(x: jnp.ndarray, angles: jnp.ndarray) -> jnp.ndarray:
    """``x`` [..., T, H, D] turned by ``angles`` [..., T, D/2], pairs
    (x[i], x[i + D/2]), in float32, cast back."""
    dtype = x.dtype
    cos = jnp.cos(angles)[..., None, :]  # [..., T, 1, D/2]
    sin = jnp.sin(angles)[..., None, :]
    xf = x.astype(jnp.float32)
    half = xf.shape[-1] // 2
    x1, x2 = xf[..., :half], xf[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(dtype)


def apply_mrope(
    x: jnp.ndarray,
    positions: jnp.ndarray,
    inv_freq: jnp.ndarray,
    sections: tuple[int, ...],
) -> jnp.ndarray:
    """:func:`apply_rope` by sections (multimodal rotary): ``positions`` is
    ``[3, ..., T]`` (temporal, height, width) and rotary pair ``i`` of a
    head turns by the component whose section holds it, the first
    ``sections[0]`` pairs by the temporal position, the next
    ``sections[1]`` by the height, the rest by the width
    (``sum(sections)`` = D / 2). Each pair's angle is the one product
    ``apply_rope`` makes, of the component it reads: equal components give
    ``apply_rope``'s result bit for bit."""
    if sum(sections) != inv_freq.shape[0] or positions.shape[0] != len(sections):
        raise ValueError(
            f"sections {sections} do not cover {inv_freq.shape[0]} rotary pairs "
            f"by {positions.shape[0]} position components"
        )
    bounds = [sum(sections[:i]) for i in range(len(sections) + 1)]
    angles = jnp.concatenate([
        positions[c][..., None].astype(jnp.float32) * inv_freq[lo:hi]
        for c, (lo, hi) in enumerate(zip(bounds, bounds[1:]))
    ], axis=-1)  # [..., T, D/2]
    return _rotate(x, angles)
