"""Pure-jnp oracle for the fused masked top-k similarity search."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def topk_search_ref(q: jax.Array, corpus: jax.Array, mask: jax.Array,
                    k: int) -> tuple[jax.Array, jax.Array]:
    """q: (Q, D), corpus: (N, D), mask: (N,) bool. Returns
    (scores (Q, k) f32 desc, idx (Q, k) i32). Masked rows score -inf.
    Full fp32 products on every backend, so the oracle is exact on the
    chip too (its default f32 matmul rounds inputs to bf16)."""
    scores = jnp.dot(q.astype(jnp.float32), corpus.astype(jnp.float32).T,
                     precision=jax.lax.Precision.HIGHEST)
    scores = jnp.where(mask[None, :], scores, -jnp.inf)
    top_s, top_i = jax.lax.top_k(scores, k)
    return top_s, top_i.astype(jnp.int32)


def topk_search_q8_ref(qs: jax.Array, c8: jax.Array, mask: jax.Array,
                       k: int) -> tuple[jax.Array, jax.Array]:
    """Oracle for the quantized scan: exact dequantized asymmetric
    distance. ``qs`` is the scale-folded fp32 query block, ``c8`` the
    int8 corpus — (qs . c8_row) IS q . dequantize(c8_row)."""
    scores = jnp.dot(qs.astype(jnp.float32), c8.astype(jnp.float32).T,
                     precision=jax.lax.Precision.HIGHEST)
    scores = jnp.where(mask[None, :], scores, -jnp.inf)
    top_s, top_i = jax.lax.top_k(scores, k)
    return top_s, top_i.astype(jnp.int32)
