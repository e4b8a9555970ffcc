"""The Model interface every architecture implements.

A ``Model`` is a bundle of pure functions over a plain-dict param pytree.
The FL layer, the launcher, and the dry-run all program against this
interface only — adding an architecture means registering one builder
that returns a ``Model``.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.sharding import ShardingPolicy


@dataclass
class Model:
    config: ModelConfig
    policy: ShardingPolicy
    # rng -> params
    init: Callable[[jax.Array], Any]
    # (params, batch) -> (loss, metrics)
    loss_fn: Callable[[Any, Dict[str, jnp.ndarray]], Any]
    # (params, batch) -> (last_logits, decode_state)
    prefill_fn: Optional[Callable] = None
    # (params, state, batch) -> (logits, state)
    decode_fn: Optional[Callable] = None
    # (batch_size, cache_len) -> concrete zero state (smoke tests)
    init_decode_state: Optional[Callable] = None
    # path-based sharding rule: (path str, shape) -> PartitionSpec
    spec_rule: Optional[Callable] = None
    # decode-state sharding rule: (path str, shape) -> PartitionSpec
    state_spec_rule: Optional[Callable] = None
    # False when ``loss_fn`` cannot be vmapped over clients (a layer runs
    # a kernel that does not batch, the grouped expert matmul); such a
    # model trains its clients one after another
    batches_over_clients: bool = True

    # ------------------------------------------------------------------
    def param_shapes(self, rng=None):
        # repro-lint: disable=RPL002 (shape-only default for eval_shape)
        rng = rng if rng is not None else jax.random.key(0)
        return jax.eval_shape(self.init, rng)

    def param_pspecs(self):
        """Pytree of PartitionSpec mirroring the param tree (via spec_rule)."""
        from jax.sharding import PartitionSpec as P
        shapes = self.param_shapes()
        rule = self.spec_rule or (lambda path, shape: P())

        def _one(path, leaf):
            return rule(_path_str(path), tuple(leaf.shape))

        return jax.tree_util.tree_map_with_path(_one, shapes)

    def state_pspecs(self, batch_size: int, cache_len: int):
        from jax.sharding import PartitionSpec as P
        if self.init_decode_state is None:
            return None
        shapes = jax.eval_shape(
            lambda: self.init_decode_state(batch_size, cache_len))
        rule = self.state_spec_rule or (lambda path, shape: P())

        def _one(path, leaf):
            return rule(_path_str(path), tuple(leaf.shape))

        return jax.tree_util.tree_map_with_path(_one, shapes)


def _path_str(path) -> str:
    toks = []
    for p in path:
        if hasattr(p, "key"):
            toks.append(str(p.key))
        elif hasattr(p, "idx"):
            toks.append(str(p.idx))
        elif hasattr(p, "name"):
            toks.append(str(p.name))
        else:
            toks.append(str(p))
    return "/".join(toks)


def make_train_step(model: Model, optimizer):
    """(params, opt_state, batch) -> (params, opt_state, metrics)."""

    def train_step(params, opt_state, batch):
        (loss, metrics), grads = jax.value_and_grad(
            model.loss_fn, has_aux=True)(params, batch)
        params, opt_state = optimizer.update(params, grads, opt_state)
        metrics = dict(metrics)
        metrics["loss"] = loss
        return params, opt_state, metrics

    return train_step


def make_grad_step(model: Model):
    """(params, batch) -> (grads, loss) — the FL clients' local step."""

    def grad_step(params, batch):
        (loss, _), grads = jax.value_and_grad(
            model.loss_fn, has_aux=True)(params, batch)
        return grads, loss

    return grad_step


def make_serve_step(model: Model):
    """(params, state, batch) -> (logits, state) — one decode token."""
    assert model.decode_fn is not None

    def serve_step(params, state, batch):
        return model.decode_fn(params, state, batch)

    return serve_step
