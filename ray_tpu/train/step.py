"""Sharded training-step builder: one function from (model, mesh, rules) to a
compiled SPMD train step with DP/FSDP/TP/SP/PP composed as mesh axes.

This is the compute-plane heart of the Train layer (the reference's
equivalent moment is DDP wrapping in ``train/torch/train_loop_utils.py:49``
— here the "wrap" is sharding annotations + XLA collectives, and pipeline
stages replace none-existent reference PP, SURVEY §2.5).
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ray_tpu.models import transformer
from ray_tpu.models.transformer import TransformerConfig
from ray_tpu.observability import goodput
from ray_tpu.parallel import (ShardingRules, batch_sharding, pipeline_apply,
                              replicated)


def make_lm_train_step(cfg: TransformerConfig, mesh: Mesh,
                       rules: Optional[ShardingRules] = None,
                       optimizer: Optional[optax.GradientTransformation] = None,
                       num_microbatches: int = 4):
    """Build (init_fn, step_fn) for language-model training on ``mesh``.

    - pipe axis > 1: transformer blocks run under the GPipe schedule
      (``pipeline_apply``); embed/head compute on every stage (cheap).
    - seq axis > 1: attention inside blocks uses ring attention.
    - fsdp/tensor axes shard params per ``transformer.logical_axes``.
    - data (+fsdp) shards the batch; XLA inserts the gradient psum.

    step_fn(state, tokens) -> (state, metrics); state = (params, opt_state).
    ``metrics`` are device values (reading one is the caller's sync):
    ``loss``, ``grad_norm`` and, for a configuration with an exit gate
    (``cfg.exit_beta``), ``exit_p`` [n_passes] (the mean probability of
    leaving at each pass) and ``exit_entropy`` (the mean entropy of that
    distribution).

    Raises ``ValueError`` for pipe > 1 with ``cfg.n_passes`` > 1: the GPipe
    schedule sends a microbatch through the stages once, and a looped stack
    would have to come back round to the first stage. And for a stack with
    a kind of layer other than ``dense`` (``cfg.layer_kinds``): their
    operations are forward only.
    """
    forward_only = sorted(set(cfg.kinds) - {transformer.DENSE})
    if forward_only:
        raise ValueError(
            f"layer kinds {forward_only} have no backward pass: a stack "
            f"with them ({cfg.kinds}) is served, not trained")
    rules = rules or ShardingRules()
    optimizer = optimizer or optax.adamw(3e-4, weight_decay=0.01)
    pipe = mesh.shape.get("pipe", 1)
    if pipe > 1:
        if cfg.n_layers % pipe != 0:
            raise ValueError(f"n_layers {cfg.n_layers} not divisible by "
                             f"pipe={pipe}")
        if cfg.n_passes > 1:
            raise ValueError(
                f"pipe={pipe} with n_passes={cfg.n_passes}: the pipeline "
                "schedule applies the stack once; a looped stack cannot be "
                "pipelined yet")
        if mesh.shape.get("seq", 1) > 1:
            raise ValueError(
                f"pipe={pipe} with seq={mesh.shape['seq']}: ring attention "
                "cannot nest inside the pipeline's shard_map")
        # Stage-shard the stacked layer dim so each stage holds only its
        # layers' params.
        rules = rules.with_overrides(layers="pipe")

    def loss_fn(params, tokens):
        if pipe == 1:
            return transformer.loss_and_metrics(params, tokens, cfg, mesh,
                                                rules)
        # Pipeline path: embed -> pipelined blocks -> head.
        inputs, targets = tokens[:, :-1], tokens[:, 1:]
        with jax.named_scope("embed"):
            x = params["embed"].astype(cfg.dtype)[inputs]
        layers_per_stage = cfg.n_layers // pipe
        # mesh=None: a stage already runs per device, inside
        # pipeline_apply's shard_map.
        stage_fn = functools.partial(transformer.apply_layers, cfg=cfg,
                                     mesh=None)
        # blocks leaves: [n_layers, ...] -> [pipe, layers_per_stage, ...]
        stage_params = jax.tree.map(
            lambda p: p.reshape((pipe, layers_per_stage) + p.shape[1:]),
            params["blocks"])
        x = pipeline_apply(stage_fn, stage_params, x, mesh,
                           num_microbatches=num_microbatches)
        return transformer.loss_from_states(params, x[None], targets, cfg)

    def init(key) -> Tuple[Any, Any]:
        params = transformer.init_params(key, cfg)
        return params, optimizer.init(params)

    def step(state, tokens):
        params, opt_state = state
        (loss, metrics), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, tokens)
        with jax.named_scope("optimizer"):
            updates, opt_state = optimizer.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
            gnorm = optax.global_norm(grads)
        return (params, opt_state), {"loss": loss, "grad_norm": gnorm,
                                     **metrics}

    # One layout for the train state, going in and coming out: init_fn makes
    # it there and step_fn returns it there, so the second step sees what
    # the first saw and the step compiles once. Left to the compiler, the
    # state came back in another layout than it went in (the optimizer's
    # count committed to the mesh, norm weights sharded over fsdp) and the
    # second call compiled the whole step again.
    param_shardings = jax.tree.map(
        lambda axes: rules.sharding(mesh, axes),
        transformer.logical_axes(cfg),
        is_leaf=lambda axes: isinstance(axes, tuple))
    state_shardings = (param_shardings, optax.tree_utils.tree_map_params(
        optimizer, lambda _, sharding: sharding,
        jax.eval_shape(init, jax.ShapeDtypeStruct((2,), jnp.uint32))[1],
        param_shardings,
        transform_non_params=lambda _: replicated(mesh)))
    init_fn = jax.jit(init, out_shardings=state_shardings)
    step_fn = jax.jit(step, donate_argnums=(0,),
                      out_shardings=(state_shardings, None))

    def shard_batch(tokens):
        return jax.device_put(tokens, batch_sharding(mesh, rules, ndim=2))

    # Goodput compile detection: the first call per (state, tokens)
    # signature traces+compiles the whole step — pipeline stages, ring
    # attention and the gradient psum included, since parallel/ runs
    # inline under this jit — and lands in the ledger's ``compile``
    # category; a new tokens shape mid-run is a recompile (runtime
    # mirror of lint rule R21).
    return init_fn, goodput.instrument_jit(step_fn, name="train.step_fn"), \
        shard_batch
