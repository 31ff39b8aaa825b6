"""Pure-jnp oracles for every Pallas kernel in this package."""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = [
    "vertex_butterflies_ref",
    "edge_wedge_matrix_ref",
    "bloom_update_ref",
    "flash_attention_ref",
    "pair_wedge_counts_ref",
    "support_update_ref",
]


def pair_wedge_counts_ref(slots: jax.Array):
    """Row-sum oracle for the blocked wedge-count kernel: W = Σ slots,
    bf = C(W, 2)."""
    w = jnp.sum(slots.astype(jnp.float32), axis=1)
    return w, w * (w - 1.0) * 0.5


def support_update_ref(pe1, pe2, alive, W):
    """Oracle for the blocked support-update kernel, pairs-major layout.

    Inputs are (n_rows, K) f32 flags (pe1/pe2 = "slot's edge i peeled",
    alive = wedge alive) plus per-row alive wedge counts W; rows are
    graph pairs (CD path) or the flattened partition×pair stack (the
    in-loop FD path) — the algebra is row-local either way.  Returns
    (contrib1, contrib2, c): the per-slot butterfly losses charged to
    each slot's two edges and the dying-wedge count per row."""
    pe1 = pe1.astype(jnp.float32)
    pe2 = pe2.astype(jnp.float32)
    alive = alive.astype(jnp.float32)
    dies = alive * jnp.maximum(pe1, pe2)
    c = jnp.sum(dies, axis=1)
    surv_loss = (alive - dies) * c[:, None]
    widow = dies * (W.astype(jnp.float32) - 1.0)[:, None]
    return (
        (1.0 - pe1) * widow + surv_loss,
        (1.0 - pe2) * widow + surv_loss,
        c,
    )


def vertex_butterflies_ref(A: jax.Array) -> jax.Array:
    """⋈_u per row of A: Σ_{u'≠u} C(W[u,u'], 2) with W = A Aᵀ."""
    W = jnp.dot(A, A.T, preferred_element_type=jnp.float32)
    W = W * (1.0 - jnp.eye(W.shape[0], dtype=W.dtype))
    return jnp.sum(W * (W - 1.0) * 0.5, axis=1)


def edge_wedge_matrix_ref(A: jax.Array) -> jax.Array:
    """M = (W − 1) · A with W = A Aᵀ; per-edge counts are
    M[u,v] − (d_u − 1) gathered at the edge list."""
    W = jnp.dot(A, A.T, preferred_element_type=jnp.float32)
    return jnp.dot(W - 1.0, A, preferred_element_type=jnp.float32)


def bloom_update_ref(pe, pt, alive, canon, k_alive):
    """Per-bloom batch support update (alg.6 inner loop), dense layout.

    Inputs are [nb, K] bloom-major matrices (padded with alive=False) plus
    per-bloom pair counts k_alive [nb].  Returns (contrib [nb,K], c [nb]):
    c = dying pairs per bloom; contrib = per-link support loss to be
    scattered onto link_edge by the caller.
    """
    pair_dies = alive & (pe | pt)
    c = jnp.sum((pair_dies & canon).astype(jnp.float32), axis=1)
    widow = alive & ~pe & pt
    surv = alive & ~pair_dies
    contrib = (
        jnp.where(widow, k_alive[:, None] - 1.0, 0.0)
        + jnp.where(surv, c[:, None], 0.0)
    )
    return contrib, c


def flash_attention_ref(q, k, v, causal: bool = True, scale=None):
    """Plain softmax attention — oracle for the blockwise kernel.

    q: [B, H, Sq, D], k/v: [B, H, Sk, D] (kv heads already broadcast).
    """
    if scale is None:
        scale = q.shape[-1] ** -0.5
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * scale
    if causal:
        sq, sk = q.shape[2], k.shape[2]
        # last query aligns with last key (supports sk >= sq prefill)
        qi = jnp.arange(sq)[:, None] + (sk - sq)
        ki = jnp.arange(sk)[None, :]
        logits = jnp.where(ki <= qi, logits, -jnp.inf)
    p = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v)
