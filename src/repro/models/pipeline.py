"""Pipeline parallelism over the ``pod`` axis (beyond paper).

The multi-pod mesh's top axis is a DCN boundary — exactly where GPipe
wants its stage cut: instead of replicating all 94 layers on both pods
(the FL/data-parallel default), each pod owns HALF the layer stack and
microbatches stream between pods via ``lax.ppermute`` (one DCN hop per
microbatch per direction, vs. the all-reduce of the full gradient set).

Mechanics:
  * stacked layer params keep their (L, ...) leaves; the leading dim is
    sharded ``P("pod", ...)`` so each pod materializes only its
    L/n_stages slice — inside ``shard_map`` (manual over "pod", auto
    over data/model) the local leaf IS the stage's layer stack;
  * the classic GPipe schedule: M microbatches, n_stages + M - 1 ticks;
    at each tick every stage runs its scan over its local layers on the
    microbatch it holds, then the activations rotate one stage forward;
  * embed on stage 0, loss head on the last stage; the loss is psum'd
    so every pod reports the same scalar; jax.grad differentiates
    through the whole schedule (the transpose of ppermute is the
    reverse ppermute — backward pipeline for free).

Numerically identical to the unpipelined model (tests/test_pipeline.py
checks loss AND grads on a forged 2-pod mesh).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models import common
from repro.models.sharding import ShardingPolicy
from repro.models.transformer import embed_inputs, logits_fn, make_block_fn


def pipeline_spec_rule(base_rule):
    """Wrap a spec rule: stacked layer leaves get 'pod' on the stage dim."""
    def rule(path: str, shape) -> P:
        spec = base_rule(path, shape)
        if path.startswith("layers/"):
            parts = list(spec)
            parts[0] = "pod"  # leading layer dim -> pipeline stages
            return P(*parts)
        return spec
    return rule


def make_pp_loss_fn(cfg: ModelConfig, policy: ShardingPolicy, mesh: Mesh,
                    n_micro: int, window: Optional[int] = None):
    """Pipelined (params, batch) -> (loss, metrics) over mesh axis 'pod'.

    Requires n_layers % n_stages == 0 and batch % n_micro == 0.
    """
    n_stages = mesh.shape["pod"]
    assert cfg.n_layers % n_stages == 0
    block = make_block_fn(cfg, policy, window)

    def stage_forward(layers_local, x):
        (x, aux), _ = jax.lax.scan(block, (x, jnp.zeros((), jnp.float32)),
                                   layers_local)
        return x, aux

    def pp_body(params, batch, stage_arr):
        # stage index arrives as a pod-sharded arange instead of
        # lax.axis_index: partial-auto shard_map on JAX 0.4.x lowers
        # axis_index to a PartitionId op the CPU SPMD partitioner rejects
        stage = stage_arr[0]
        tokens = batch["tokens"]          # full batch (replicated on pod)
        labels = batch["labels"]
        b = tokens.shape[0]
        mb = b // n_micro

        # embed everything up front (stage 0's work; cheap) — each
        # microbatch enters the pipe as its embedding
        x_all, n_prefix, n_pad = embed_inputs(params, batch, cfg)
        s_pad = x_all.shape[1]
        micros = x_all.reshape(n_micro, mb, s_pad, x_all.shape[-1])

        n_ticks = n_micro + n_stages - 1
        zero = jnp.zeros((mb, s_pad, x_all.shape[-1]), x_all.dtype)
        total_loss = jnp.zeros((), jnp.float32)
        total_aux = jnp.zeros((), jnp.float32)

        def tick(carry, t):
            total_loss, total_aux, live = carry
            # stage 0 ingests microbatch t (when one remains)
            incoming = jax.lax.dynamic_index_in_dim(
                micros, jnp.clip(t, 0, n_micro - 1), axis=0, keepdims=False)
            x = jnp.where(stage == 0, incoming, live)
            x, aux = stage_forward(params["layers"], x)
            # last stage computes the loss for the microbatch that has
            # now passed through all stages (valid ticks only)
            m_idx = t - (n_stages - 1)
            valid = jnp.logical_and(m_idx >= 0, m_idx < n_micro)
            lbl = jax.lax.dynamic_index_in_dim(
                labels.reshape(n_micro, mb, -1),
                jnp.clip(m_idx, 0, n_micro - 1), axis=0, keepdims=False)
            xl = common.rmsnorm(params["ln_f"], x, cfg.norm_eps)
            s_text = lbl.shape[1]
            x_text = jax.lax.dynamic_slice_in_dim(xl, n_prefix, s_text,
                                                  axis=1)
            logits = logits_fn(params, x_text, cfg)
            mb_loss = common.softmax_xent(logits, lbl, cfg.vocab_size)
            is_last = stage == n_stages - 1
            take = jnp.logical_and(valid, is_last).astype(jnp.float32)
            total_loss = total_loss + take * mb_loss
            total_aux = total_aux + jnp.where(valid, aux, 0.0)
            # rotate activations one stage forward
            fwd = [(i, (i + 1) % n_stages) for i in range(n_stages)]
            live = jax.lax.ppermute(x, "pod", perm=fwd)
            return (total_loss, total_aux, live), None

        (total_loss, total_aux, _), _ = jax.lax.scan(
            tick, (total_loss, total_aux, zero), jnp.arange(n_ticks))
        # broadcast the last stage's loss everywhere (psum of one term)
        loss = jax.lax.psum(total_loss, "pod") / n_micro
        aux = jax.lax.psum(total_aux, "pod") / (n_ticks * n_stages)
        metrics = {"xent": loss}
        if cfg.moe is not None:
            metrics["moe_aux"] = aux
            loss = loss + cfg.moe.router_aux_weight * aux
        return loss, metrics

    # manual over pod; data/model stay under GSPMD inside
    def loss_fn(params, batch):
        param_specs = jax.tree_util.tree_map_with_path(
            lambda path, leaf: P(*(("pod",) + (None,) * (leaf.ndim - 1)))
            if _path_str(path).startswith("layers/")
            else P(*((None,) * leaf.ndim)),
            params)
        return jax.shard_map(
            pp_body, mesh=mesh,
            in_specs=(param_specs,
                      jax.tree.map(lambda _: P(), batch),
                      P("pod")),
            out_specs=(P(), {"xent": P()} if cfg.moe is None else
                       {"xent": P(), "moe_aux": P()}),
            axis_names={"pod"}, check_vma=False,
        )(params, batch, jnp.arange(n_stages, dtype=jnp.int32))

    return loss_fn


def _path_str(path) -> str:
    toks = []
    for pp in path:
        if hasattr(pp, "key"):
            toks.append(str(pp.key))
        elif hasattr(pp, "idx"):
            toks.append(str(pp.idx))
        else:
            toks.append(str(pp))
    return "/".join(toks)
