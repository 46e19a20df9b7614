"""Training driver: step loop + checkpoint/resume + data prefetch.

Runs any arch cell (reduced configs on CPU; production shapes on a pod).
Fault tolerance: checkpoints (params, opt_state, step) via the atomic
CheckpointManager; resume picks up from the latest committed step and the
step-indexed data sources regenerate exactly the in-flight batches.
"""
from __future__ import annotations

import time
from dataclasses import dataclass

import numpy as np
import jax

from ..configs import get_config
from ..launch.mesh import make_host_mesh
from ..launch.steps import build_step
from ..optim import adamw_init
from ..models.params import tree_init
from ..data.pipeline import (TokenSource, GNNFullGraphSource, RecsysSource,
                             SampledGraphSource, Prefetcher)
from .checkpoint import CheckpointManager

__all__ = ["TrainLoop", "make_source"]


def make_source(cfg, shape_name: str, reduced: bool):
    from ..configs import input_specs

    step_kind, avals = input_specs(cfg, shape_name, reduced=reduced)
    if cfg.kind == "lm":
        B, S = avals["tokens"].shape
        return TokenSource(B, S, cfg.vocab)
    if cfg.kind == "recsys":
        B = avals["hist_ids"].shape[0]
        return RecsysSource(cfg, B)
    # gnn
    from ..graph import chung_lu
    from ..configs.shapes import SHAPES_BY_KIND

    batch = avals["batch"]
    N = avals["num_nodes"]
    mode = SHAPES_BY_KIND["gnn"][shape_name]["mode"]
    if mode == "molecule":  # static random disjoint-union batch
        rng = np.random.default_rng(0)
        G = batch["y"].shape[0] if "y" in batch else batch["labels"].shape[0]
        n1 = N // G
        e1 = batch["src"].shape[0] // (2 * G)
        src1 = rng.integers(0, n1, e1)
        dst1 = (src1 + 1 + rng.integers(0, n1 - 1, e1)) % n1
        offs = np.repeat(np.arange(G) * n1, e1)
        s = np.concatenate([np.tile(src1, G) + offs, np.tile(dst1, G) + offs])
        d = np.concatenate([np.tile(dst1, G) + offs, np.tile(src1, G) + offs])
        data = {"src": s.astype(np.int32), "dst": d.astype(np.int32),
                "graph_ids": np.repeat(np.arange(G), n1).astype(np.int32)}
        if "z" in batch:
            data["z"] = rng.integers(1, 90, N).astype(np.int32)
        if "pos" in batch:
            data["pos"] = rng.normal(size=(N, 3)).astype(np.float32)
        if "x" in batch:
            data["x"] = rng.normal(size=batch["x"].shape).astype(np.float32)
        if "y" in batch:
            data["y"] = rng.normal(size=G).astype(np.float32)
        if "labels" in batch:
            data["labels"] = rng.integers(0, cfg.num_classes, G).astype(np.int32)
        return lambda step: data
    if mode == "sampled":
        sh = SHAPES_BY_KIND["gnn"][shape_name]
        B = batch["labels"].shape[0] if "labels" in batch else batch["y"].shape[0]
        fanout = (3, 2) if reduced else sh["fanout"]
        g = chung_lu(max(N * 2, 4096), max(N * 8, 16384), seed=1)
        d_feat = batch["x"].shape[-1] if "x" in batch else 8
        return SampledGraphSource(g, d_feat, cfg.num_classes, B, fanout)
    # full graph: specs reserve one dummy sink node -> real graph has N-1
    e_target = batch["src"].shape[0] // 2
    g = chung_lu(N - 1, e_target, seed=1)
    d_feat = batch["x"].shape[-1] if "x" in batch else 0
    return GNNFullGraphSource(g, d_feat, cfg.num_classes, cfg.arch, pad_nodes=1)


@dataclass
class TrainLoop:
    arch: str
    shape: str = None
    reduced: bool = True
    checkpoint_dir: str | None = None
    checkpoint_every: int = 50
    log_every: int = 10
    lr: float = 3e-3

    def __post_init__(self):
        from ..optim import AdamWConfig

        # single data shard by choice: TrainLoop drives *reduced* cells whose
        # batch sizes (e.g. 2) need not divide a forced multi-device host
        # (the CI device matrix); production data parallelism goes through
        # launch/steps.py on a real mesh, not this harness.  Pass
        # make_host_mesh(max_data=None) here to span every visible device.
        self.mesh = make_host_mesh(max_data=1)
        cfg = get_config(self.arch)
        if self.reduced:
            cfg = cfg.reduced()
        self.cfg = cfg
        if self.shape is None:
            self.shape = {"lm": "train_4k", "gnn": "full_graph_sm",
                          "recsys": "train_batch"}[cfg.kind]
        self.bundle = build_step(self.arch, self.shape, self.mesh,
                                 reduced=self.reduced,
                                 opt=AdamWConfig(lr=self.lr))
        assert self.bundle.name == "train_step", "TrainLoop needs a train cell"
        self.fn = jax.jit(self.bundle.fn, in_shardings=self.bundle.in_shardings,
                          out_shardings=self.bundle.out_shardings,
                          donate_argnums=self.bundle.donate_argnums)
        self.ckpt = (CheckpointManager(self.checkpoint_dir)
                     if self.checkpoint_dir else None)

    def _init_state(self):
        if self.cfg.kind == "lm":
            from ..models.transformer import lm_param_specs
            specs = lm_param_specs(self.cfg)
        elif self.cfg.kind == "recsys":
            from ..models.recsys import mind_param_specs
            specs = mind_param_specs(self.cfg)
        else:
            from ..models.gnn import gnn_param_specs
            from ..configs import input_specs
            _, av = input_specs(self.cfg, self.shape, reduced=self.reduced)
            d_in = av["batch"]["x"].shape[-1] if "x" in av["batch"] else 0
            specs = gnn_param_specs(self.cfg, d_in)
        params = tree_init(specs, jax.random.PRNGKey(0))
        opt_state = adamw_init(params, self.bundle.static["opt"])
        return params, opt_state

    def run(self, num_steps: int, resume: bool = True) -> dict:
        params, opt_state = self._init_state()
        start = 0
        if self.ckpt and resume:
            try:
                (params, opt_state), start = self.ckpt.restore_latest(
                    (params, opt_state))
                start += 1
            except FileNotFoundError:
                pass
        source = make_source(self.cfg, self.shape, self.reduced)
        prefetch = Prefetcher(source, start_step=start)
        losses = []
        t0 = time.time()
        with jax.set_mesh(self.mesh):
            for i in range(start, start + num_steps):
                step_idx, batch = next(prefetch)
                batch = jax.tree.map(jax.numpy.asarray, batch)
                if self.cfg.kind == "lm":
                    params, opt_state, loss = self.fn(
                        params, opt_state, batch["tokens"], batch["labels"])
                else:
                    params, opt_state, loss = self.fn(params, opt_state, batch)
                losses.append(float(loss))
                if self.log_every and (i + 1) % self.log_every == 0:
                    print(f"step {i + 1}: loss {losses[-1]:.4f}", flush=True)
                if self.ckpt and (i + 1) % self.checkpoint_every == 0:
                    self.ckpt.save(i, (params, opt_state))
        prefetch.close()
        if self.ckpt:
            self.ckpt.save(start + num_steps - 1, (params, opt_state))
            self.ckpt.wait()
        return {"losses": losses, "steps_per_s": len(losses) / (time.time() - t0),
                "final_loss": losses[-1] if losses else float("nan")}
