"""Cell assembly: (arch x shape x mesh) -> jit-ready step fn + avals + shardings.

Sharding policy per cell family (DESIGN.md §5):

* LM train      — batch over (pod, data); Megatron TP over `model` (heads /
                  mlp / vocab / expert); FSDP over `data` on the embed dim
                  (2D param sharding); optimizer state mirrors params
                  (int8-moment state shards its block dim over data).
* LM prefill    — batch over (pod, data), heads over model.
* LM decode_32k — cache batch over (pod, data), cache sequence over model.
* LM long_500k  — batch=1: cache sequence over *all* axes (flash-combine);
                  weights TP over model.
* GNN           — edge arrays over all axes flattened; node state replicated
                  (the decomposition engine's semi-external layout).
* RecSys        — embedding-table rows over model; batch over (pod, data).
* CoreGraph     — the paper's engine: shards over all axes, core replicated.
"""
from __future__ import annotations

import os
from dataclasses import dataclass
from functools import partial
from typing import Any, Callable

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..configs import get_config, input_specs
from ..configs.base import LMConfig, GNNConfig, RecsysConfig, CoreGraphConfig
from ..models import transformer as tfm
from ..models import gnn as gnn_m
from ..models import recsys as rec_m
from ..models.params import tree_avals, tree_shardings, Spec, tree_num_params
from ..optim import AdamWConfig, adamw_update, adamw_state_avals


@dataclass
class StepBundle:
    name: str
    fn: Callable
    args: tuple                 # avals, positional
    in_shardings: tuple
    out_shardings: Any
    donate_argnums: tuple = ()
    num_params: int = 0
    static: dict | None = None


def _batch_axes(mesh: Mesh):
    return tuple(a for a in mesh.axis_names if a != "model")


def _all_axes(mesh: Mesh):
    return tuple(mesh.axis_names)


def _ns(mesh, *spec):
    return NamedSharding(mesh, P(*spec))


def _ba_rule(mesh: Mesh):
    ba = _batch_axes(mesh)
    return ba if len(ba) > 1 else ba[0]


def _lm_rules(mesh: Mesh, step_kind: str) -> dict:
    """TP over model; experts 2D (expert x embed-over-batch-axes); weights
    otherwise replicated over batch axes.  (Full FSDP on dense weights is
    opt-in via REPRO_FSDP=1: XLA's partitioner currently resolves it with
    involuntary remat, inflating per-layer flops ~8x — see EXPERIMENTS §Perf.)
    """
    rules = {"heads": "model", "kv_heads": "model", "mlp": "model",
             "vocab": "model", "expert": "model", "rows": "model",
             "embed": None, "expert_embed": _ba_rule(mesh)}
    if step_kind == "train" and os.environ.get("REPRO_FSDP") == "1":
        rules["embed"] = _ba_rule(mesh)
    return rules


def _zero1_rules(rules: dict, mesh: Mesh) -> dict:
    """Optimizer-state rules: additionally shard the embed dim over batch
    axes (ZeRO-1) — states live 2D even where weights stay replicated."""
    return {**rules, "embed": _ba_rule(mesh)}


def _opt_shardings(param_specs, mesh, rules, opt: AdamWConfig):
    param_sh = tree_shardings(param_specs, mesh, rules)
    if not opt.quantize_moments:
        mu = jax.tree.map(lambda s: {"m": s, "v": s}, param_sh,
                          is_leaf=lambda x: isinstance(x, NamedSharding))
    else:
        ba = _batch_axes(mesh)
        q = _ns(mesh, ba, None)
        s = _ns(mesh, ba)
        mu = jax.tree.map(lambda _: {"m_q": q, "m_s": s, "v_q": q, "v_s": s},
                          param_sh, is_leaf=lambda x: isinstance(x, NamedSharding))
    return {"step": _ns(mesh), "mu": mu}


# ===================================================================== LM
def _build_lm(cfg: LMConfig, shape_name, step_kind, avals, mesh, opt, reduced):
    ba = _batch_axes(mesh)
    rules = _lm_rules(mesh, step_kind)
    pspecs = tfm.lm_param_specs(cfg)
    p_avals = tree_avals(pspecs)
    p_shard = tree_shardings(pspecs, mesh, rules)
    n_params = tree_num_params(pspecs)

    if step_kind == "train":
        o_avals = adamw_state_avals(p_avals, opt)
        o_shard = _opt_shardings(pspecs, mesh, _zero1_rules(rules, mesh), opt)
        # gradient accumulation: bound per-chip live tokens per microbatch
        B, S = avals["tokens"].shape
        data_shards = int(np.prod([mesh.shape[a] for a in ba]))
        tokens_per_chip = B * S // max(data_shards, 1)
        budget = int(os.environ.get("REPRO_ACCUM_TOKENS", 8192))
        want = max(1, -(-tokens_per_chip // budget))
        accum = 1
        for cand in range(min(want, B), 0, -1):  # microbatch stays shardable
            if B % cand == 0 and (B // cand) % data_shards == 0:
                accum = cand
                break

        def step(params, opt_state, tokens, labels):
            if accum == 1:
                loss, grads = jax.value_and_grad(tfm.lm_loss)(
                    params, cfg, tokens, labels)
            else:
                # keep each microbatch batch-sharded over the data axes
                mb_spec = P(None, ba if len(ba) > 1 else ba[0], None)
                mb_tok = jax.lax.with_sharding_constraint(
                    tokens.reshape(accum, B // accum, S), mb_spec)
                mb_lbl = jax.lax.with_sharding_constraint(
                    labels.reshape(accum, B // accum, S), mb_spec)

                def micro(carry, mb):
                    t, l = mb
                    loss, g = jax.value_and_grad(tfm.lm_loss)(params, cfg, t, l)
                    return jax.tree.map(jnp.add, carry[0], g), carry[1] + loss

                from ..models.layers import _unroll_scans
                zeros = jax.tree.map(
                    lambda p: jnp.zeros(p.shape, jnp.float32), params)
                (grads, loss), _ = jax.lax.scan(
                    lambda c, mb: (micro(c, mb), None),
                    (zeros, jnp.float32(0)), (mb_tok, mb_lbl),
                    unroll=accum if _unroll_scans() else 1)
                grads = jax.tree.map(lambda g: g / accum, grads)
                loss = loss / accum
            params, opt_state = adamw_update(params, grads, opt_state, opt)
            return params, opt_state, loss

        tok_sh = _ns(mesh, ba, None)
        return StepBundle(
            name="train_step", fn=step,
            args=(p_avals, o_avals, avals["tokens"], avals["labels"]),
            in_shardings=(p_shard, o_shard, tok_sh, tok_sh),
            out_shardings=(p_shard, o_shard, _ns(mesh)),
            donate_argnums=(0, 1), num_params=n_params,
            static={"opt": opt, "cfg": cfg, "accum": accum, "rules": rules,
                    "pspecs": pspecs},
        )

    if step_kind == "prefill":
        def step(params, tokens):
            return tfm.serve_prefill(params, cfg, tokens)

        return StepBundle(
            name="serve_prefill", fn=step,
            args=(p_avals, avals["tokens"]),
            in_shardings=(p_shard, _ns(mesh, ba, None)),
            out_shardings=_ns(mesh, ba, None, "model"),
            num_params=n_params,
        )

    # decode
    long_ctx = shape_name == "long_500k"
    if long_ctx:
        seq_axes = _all_axes(mesh)
        cache_b, cache_t = None, seq_axes
    else:
        cache_b, cache_t = ba, "model"

    def cache_sharding(aval_key):
        if aval_key == "len":
            return _ns(mesh)
        # (L, B, T, ...) — rank 4 (MLA: ckv/kr) or 5 (k/v)
        rank = 5 if cfg.mla is None else 4
        trailing = (None,) * (rank - 3)
        return _ns(mesh, None, cache_b, cache_t, *trailing)

    c_shard = {k: cache_sharding(k) for k in avals["caches"]}

    def step(params, tokens, caches):
        return tfm.serve_decode(params, cfg, tokens, caches)

    return StepBundle(
        name="serve_decode", fn=step,
        args=(p_avals, avals["tokens"], avals["caches"]),
        in_shardings=(p_shard, _ns(mesh, cache_b, None), c_shard),
        out_shardings=(_ns(mesh, cache_b, None, "model"),
                       {**c_shard}),
        donate_argnums=(2,), num_params=n_params,
    )


# ===================================================================== GNN
def _build_gnn(cfg: GNNConfig, shape_name, step_kind, avals, mesh, opt, reduced):
    batch_avals = avals["batch"]
    N = avals["num_nodes"]
    sh = SHAPE_FEAT_DIM = batch_avals.get("x")
    d_in = batch_avals["x"].shape[-1] if "x" in batch_avals else 0
    pspecs = gnn_m.gnn_param_specs(cfg, d_in)
    p_avals = tree_avals(pspecs)
    p_shard = tree_shardings(pspecs, mesh, {})  # replicated (small models)
    n_params = tree_num_params(pspecs)
    o_avals = adamw_state_avals(p_avals, opt)
    o_shard = _opt_shardings(pspecs, mesh, {}, opt)

    edge_sh = _ns(mesh, _all_axes(mesh))
    repl = _ns(mesh)
    b_shard = {
        k: edge_sh if k in ("src", "dst") else repl for k in batch_avals
    }

    def step(params, opt_state, batch):
        def loss_fn(p):
            return gnn_m.gnn_loss(p, cfg, {**batch, "num_nodes": N})

        loss, grads = jax.value_and_grad(loss_fn)(params)
        params, opt_state = adamw_update(params, grads, opt_state, opt)
        return params, opt_state, loss

    return StepBundle(
        name="train_step", fn=step,
        args=(p_avals, o_avals, batch_avals),
        in_shardings=(p_shard, o_shard, b_shard),
        out_shardings=(p_shard, o_shard, repl),
        donate_argnums=(0, 1), num_params=n_params,
        static={"opt": opt, "cfg": cfg},
    )


# ================================================================== recsys
def _build_recsys(cfg: RecsysConfig, shape_name, step_kind, avals, mesh, opt,
                  reduced):
    ba = _batch_axes(mesh)
    rules = {"rows": "model", "embed": None, "mlp": "model", "embed2": None}
    pspecs = rec_m.mind_param_specs(cfg)
    p_avals = tree_avals(pspecs)
    p_shard = tree_shardings(pspecs, mesh, rules)
    n_params = tree_num_params(pspecs)

    def batch_shard(k, aval):
        if k == "candidate_ids":
            return _ns(mesh, ba)
        if aval.shape[0] == 1:  # retrieval: a single user, replicated
            return _ns(mesh)
        return _ns(mesh, ba, *([None] * (len(aval.shape) - 1)))

    b_shard = {k: batch_shard(k, v) for k, v in avals.items()}

    if step_kind == "train":
        o_avals = adamw_state_avals(p_avals, opt)
        o_shard = _opt_shardings(pspecs, mesh, rules, opt)

        def step(params, opt_state, batch):
            loss, grads = jax.value_and_grad(rec_m.mind_train_loss)(
                params, cfg, batch)
            params, opt_state = adamw_update(params, grads, opt_state, opt)
            return params, opt_state, loss

        return StepBundle(
            name="train_step", fn=step,
            args=(p_avals, o_avals, avals),
            in_shardings=(p_shard, o_shard, b_shard),
            out_shardings=(p_shard, o_shard, _ns(mesh)),
            donate_argnums=(0, 1), num_params=n_params,
            static={"opt": opt, "cfg": cfg},
        )

    if step_kind == "serve":
        def step(params, batch):
            return rec_m.mind_serve(params, cfg, batch)

        return StepBundle(
            name="serve_step", fn=step, args=(p_avals, avals),
            in_shardings=(p_shard, b_shard),
            out_shardings=_ns(mesh, ba, None, None), num_params=n_params,
        )

    def step(params, batch):
        return rec_m.mind_retrieval(params, cfg, batch)

    return StepBundle(
        name="retrieval_step", fn=step, args=(p_avals, avals),
        in_shardings=(p_shard, b_shard),
        out_shardings=(_ns(mesh), _ns(mesh)), num_params=n_params,
    )


# =============================================================== coregraph
def _build_coregraph(cfg: CoreGraphConfig, shape_name, step_kind, avals, mesh,
                     opt, reduced):
    # one cond-gated SemiCore* superstep of the shard backend (chunk=1), the
    # §Perf measurement unit: its HLO contains exactly the per-superstep
    # collectives (one all_gather of owned core slices + the scalar psum)
    from ..core.resident import build_shard_chunk_fn

    specs = avals["specs"]
    num_probes = avals["num_probes"]
    fn = build_shard_chunk_fn(mesh, "semicore*", cfg.n, num_probes, chunk=1)
    args = (specs["core0"], specs["cnt"], specs["active"], specs["nactive"],
            specs["dst"], specs["edge_mask"], specs["lsegptr"],
            specs["owned_ids"], specs["owned_mask"])
    return StepBundle(
        name="decompose", fn=fn, args=args,
        in_shardings=None,  # already a jit-wrapped fn with shardings
        out_shardings=None, num_params=0,
    )


def build_step(arch_id: str, shape_name: str, mesh: Mesh, *,
               reduced: bool = False, opt: AdamWConfig | None = None,
               quantize_moments: bool | None = None,
               depth_override: int | None = None) -> StepBundle:
    cfg = get_config(arch_id)
    if reduced:
        cfg = cfg.reduced()
    if depth_override is not None and cfg.kind == "lm":
        from dataclasses import replace as _replace
        cfg = _replace(cfg, n_layers=depth_override)
    if opt is None:
        big = cfg.kind == "lm" and cfg.d_model >= 7000
        opt = AdamWConfig(quantize_moments=big if quantize_moments is None
                          else quantize_moments)
    num_shards = int(np.prod(mesh.devices.shape))
    step_kind, avals = input_specs(cfg, shape_name, num_shards=num_shards,
                                   reduced=reduced)
    builder = {"lm": _build_lm, "gnn": _build_gnn, "recsys": _build_recsys,
               "coregraph": _build_coregraph}[cfg.kind]
    return builder(cfg, shape_name, step_kind, avals, mesh, opt, reduced)
