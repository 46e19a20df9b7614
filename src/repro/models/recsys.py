"""MIND: Multi-Interest Network with Dynamic Routing (Li et al., CIKM'19).

User behavior sequence -> B2I dynamic-routing capsules (n_interests) ->
label-aware attention training with sampled-softmax negatives; serving scores
candidates by max-over-interests dot product (``retrieval_cand`` = one user
vs 10^6 candidates as a batched matmul + top-k, never a loop).

The item table is the semi-external object here: rows sharded over the model
axis, O(batch) activation state; the user-profile multi-hot fields go through
the EmbeddingBag primitive (Pallas kernel on TPU, XLA fallback otherwise).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..configs.base import RecsysConfig
from .params import Spec
from ..kernels import ops as kops

F32 = jnp.float32


def mind_param_specs(cfg: RecsysConfig) -> dict:
    D = cfg.embed_dim
    return {
        "item_embed": Spec((cfg.n_items, D), F32, ("rows", "embed"), scale=0.1),
        "profile_embed": Spec((cfg.profile_vocab, D), F32, ("rows", "embed"),
                              scale=0.1),
        "bilinear": Spec((D, D), F32, ("embed", "embed2")),  # routing S matrix
        "profile_proj": Spec((cfg.n_profile_fields * D, D), F32, (None, "embed")),
        "mlp": {
            "w1": Spec((2 * D, cfg.mlp_dim), F32, ("embed", "mlp")),
            "b1": Spec((cfg.mlp_dim,), F32, ("mlp",), init="zeros"),
            "w2": Spec((cfg.mlp_dim, D), F32, ("mlp", "embed")),
            "b2": Spec((D,), F32, ("embed",), init="zeros"),
        },
    }


def _squash(z, axis=-1):
    n2 = jnp.sum(z * z, axis=axis, keepdims=True)
    return (n2 / (1.0 + n2)) * z / jnp.sqrt(n2 + 1e-9)


def dynamic_routing(e, mask, n_interests: int, iters: int):
    """B2I routing: behaviors e (B, L, D) -> interest capsules (B, K, D)."""
    B, L, D = e.shape
    K = n_interests
    logits = jnp.zeros((B, K, L), F32)
    caps = jnp.zeros((B, K, D), F32)
    neg = jnp.asarray(-1e30, F32)
    for _ in range(iters):
        w = jax.nn.softmax(jnp.where(mask[:, None, :], logits, neg), axis=1)
        z = jnp.einsum("bkl,bld->bkd", w * mask[:, None, :], e)
        caps = _squash(z)
        logits = logits + jnp.einsum("bkd,bld->bkl", caps, e)
    return caps


def user_interests(params, cfg: RecsysConfig, hist_ids, profile_ids,
                   use_pallas_bag: bool = False):
    """(B, hist_len) history + (B, fields, bag) profile -> (B, K, D)."""
    B = hist_ids.shape[0]
    D = cfg.embed_dim
    mask = hist_ids >= 0
    e = jnp.take(params["item_embed"], jnp.maximum(hist_ids, 0), axis=0)
    e = e @ params["bilinear"]  # shared bilinear map (B2I)
    caps = dynamic_routing(e, mask, cfg.n_interests, cfg.capsule_iters)
    # profile: one EmbeddingBag per multi-hot field
    flat = profile_ids.reshape(B * cfg.n_profile_fields, -1)
    bags = kops.embedding_bag(
        params["profile_embed"], flat, mode="mean",
        use_pallas=use_pallas_bag,
    ).reshape(B, cfg.n_profile_fields * D)
    prof = bags @ params["profile_proj"]  # (B, D)
    h = jnp.concatenate(
        [caps, jnp.broadcast_to(prof[:, None, :], caps.shape)], axis=-1)
    m = params["mlp"]
    out = jax.nn.relu(h @ m["w1"] + m["b1"]) @ m["w2"] + m["b2"]
    return out  # (B, K, D)


def label_aware_attention(caps, target_e, p: float = 2.0):
    """MIND eq. (6): soft attention of the label over interests."""
    s = jnp.einsum("bkd,bd->bk", caps, target_e)
    w = jax.nn.softmax((jnp.abs(s) + 1e-9) ** p * jnp.sign(s), axis=-1)
    return jnp.einsum("bk,bkd->bd", w, caps)


def mind_train_loss(params, cfg: RecsysConfig, batch: dict) -> jax.Array:
    """Sampled softmax: target vs `num_sampled_negatives` uniform negatives."""
    caps = user_interests(params, cfg, batch["hist_ids"], batch["profile_ids"])
    tgt = jnp.take(params["item_embed"], batch["target_id"], axis=0)  # (B, D)
    user = label_aware_attention(caps, tgt)
    negs = jnp.take(params["item_embed"], batch["negative_ids"], axis=0)  # (B,M,D)
    pos_logit = jnp.einsum("bd,bd->b", user, tgt)[:, None]
    neg_logit = jnp.einsum("bd,bmd->bm", user, negs)
    logits = jnp.concatenate([pos_logit, neg_logit], axis=1)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -logp[:, 0].mean()


def mind_serve(params, cfg: RecsysConfig, batch: dict) -> jax.Array:
    """Online inference: user interest vectors (serve_p99 / serve_bulk)."""
    return user_interests(params, cfg, batch["hist_ids"], batch["profile_ids"])


def mind_retrieval(params, cfg: RecsysConfig, batch: dict, top_k: int = 100):
    """Score one user's interests against `n_candidates` items (batched dot)."""
    caps = user_interests(params, cfg, batch["hist_ids"], batch["profile_ids"])
    cand = jnp.take(params["item_embed"], batch["candidate_ids"], axis=0)  # (C,D)
    scores = jnp.einsum("bkd,cd->bkc", caps, cand).max(axis=1)  # (B, C)
    vals, idx = jax.lax.top_k(scores, min(top_k, scores.shape[-1]))
    return vals, idx
