"""RMSNorm, numerically matching HF's ``LlamaRMSNorm``.

The reference got this from transformers' CUDA path; the contract (variance in
float32, scale multiply in the input dtype) is reproduced so layerwise scores
match the reference bit-for-bit at fp32 and within tolerance at fp16/bf16.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def rms_norm(
    x: jax.Array, scale: jax.Array, eps: float, unit_offset: bool = False
) -> jax.Array:
    """y = scale * x / sqrt(mean(x^2) + eps), variance computed in float32.

    ``unit_offset=True`` is the Gemma convention (HF PR #29402): multiply by
    ``(1 + scale)`` and do that multiply IN FLOAT32 before the downcast —
    Llama instead downcasts first and multiplies by ``scale`` in the input
    dtype. The cast order is quality-relevant at bf16, so both are
    reproduced exactly.
    """
    with jax.named_scope("rms_norm"):
        x32 = x.astype(jnp.float32)
        var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
        normed = x32 * jax.lax.rsqrt(var + eps)
        if unit_offset:
            return (normed * (1.0 + scale.astype(jnp.float32))).astype(x.dtype)
        return scale * normed.astype(x.dtype)
