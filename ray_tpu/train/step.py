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
from ray_tpu.parallel import (ShardingRules, batch_sharding, expert,
                              pipeline_apply, replicated)

# the kinds of layer whose operations have no backward pass
FORWARD_ONLY = frozenset({transformer.SPARSE, transformer.LINEAR,
                          transformer.SHORTCUT, transformer.MAMBA,
                          *transformer.SAMBAY})
BIAS = "router_bias"        # a mixture layer's leaf that load moves


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

    A stack of ``transformer.PARTS``' kinds (a mixer and an FFN chosen
    apart) trains on a mesh of one device. Where its FFN is a routed mixture
    the metrics carry the step's loads too, ``moe_load`` [n_moe, 4] and
    ``moe_counts`` [n_moe, n_routed] (``transformer.loss_and_metrics``), and
    the host's ``moe_routed_pairs_total`` counters are fed from them, a step
    late, when the device has them (``expert.record_load_when_ready``; no
    call-back in the step, whose program stays in the compile cache). A
    router's ``choice_bias`` is no parameter: the optimizer never sees it
    (no moments, no decay, no update: ``_trained``), its gradient is zero
    (the choice is discrete), and the step moves it after ``apply_updates``
    by the load its own forward saw (``expert.moved_bias``). The default
    optimizer is AdamW(3e-4, decay 0.01), its rate warmed up over
    ``cfg.warmup_steps`` where the configuration asks for that.

    Raises ``ValueError`` for pipe > 1 with ``cfg.n_passes`` > 1: the GPipe
    schedule sends a microbatch through the stages once, and a looped stack
    would have to come back round to the first stage. And for a stack with a
    kind of layer that has no backward pass (``FORWARD_ONLY``).
    """
    forward_only = sorted(set(cfg.kinds) & FORWARD_ONLY)
    if forward_only:
        raise ValueError(
            f"layer kinds {forward_only} have no backward pass: a stack "
            f"with them ({cfg.kinds}) is served, not trained")
    rules = rules or ShardingRules()
    optimizer = optimizer or _default_optimizer(cfg)
    # a stack of ``PARTS``' kinds with a mixture; its router has a bias
    mixture = any(transformer.PARTS.get(kind, ("", ""))[1] == "moe"
                  for kind in cfg.kinds)
    biased = mixture and cfg.experts.choice_bias

    def trained(tree):      # what the optimizer sees of the parameters' tree
        return _trained(tree) if biased else tree
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
        return params, optimizer.init(trained(params))

    def step(state, tokens):
        params, opt_state = state
        (loss, metrics), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, tokens)
        with jax.named_scope("optimizer"):
            updates, opt_state = optimizer.update(trained(grads), opt_state,
                                                  trained(params))
            updated = optax.apply_updates(trained(params), updates)
            gnorm = optax.global_norm(grads)
        if biased:
            updated = _with_moved_biases(updated, params,
                                         metrics["moe_counts"], cfg)
        return (updated, opt_state), {"loss": loss, "grad_norm": gnorm,
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
        trained(param_shardings),
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
    step_fn = goodput.instrument_jit(step_fn, name="train.step_fn")
    if mixture:
        step_fn = _recording_load(step_fn, cfg.experts)
    return init_fn, step_fn, shard_batch


def _default_optimizer(cfg: TransformerConfig, rate: float = 3e-4
                       ) -> optax.GradientTransformation:
    """AdamW at ``rate``, decay 0.01; with ``cfg.warmup_steps`` the rate
    rises linearly from ``rate / warmup_steps`` over that many steps."""
    if cfg.warmup_steps:
        rate = optax.linear_schedule(rate / cfg.warmup_steps, rate,
                                     cfg.warmup_steps)
    return optax.adamw(rate, weight_decay=0.01)


def _trained(tree):
    """A parameters' tree (or one shaped like it) of ``PARTS``' kinds without
    the routers' biases."""
    return {**tree, "blocks": {
        kind: {name: p for name, p in stack.items() if name != BIAS}
        for kind, stack in tree["blocks"].items()}}


def _with_moved_biases(trained, params, counts, cfg: TransformerConfig):
    """``trained`` (``_trained``'s tree after the update) with every mixture
    layer's bias of ``params`` moved by the step's load: ``counts`` [n_moe,
    n_routed] over the stack's mixture layers in order, dealt to the kinds'
    stacked leaves."""
    rows = {kind: [] for kind in dict.fromkeys(cfg.kinds)}
    moe = (kind for kind in cfg.kinds if transformer.PARTS[kind][1] == "moe")
    for at, kind in enumerate(moe):
        rows[kind].append(at)
    blocks = dict(trained["blocks"])
    with jax.named_scope("moe"), jax.named_scope("router"):
        for kind, mine in rows.items():
            if mine:
                blocks[kind] = {**blocks[kind], BIAS: expert.moved_bias(
                    params["blocks"][kind][BIAS], counts[jnp.array(mine)],
                    cfg.experts)}
    return {**trained, "blocks": blocks}


def _recording_load(step_fn, experts):
    """``step_fn`` with the mixture layers' loads of each step handed to the
    host's counters when the device has them (``moe_load`` stays in the
    metrics). ``__wrapped__`` stays the jitted step."""
    def step(state, tokens):
        state, metrics = step_fn(state, tokens)
        expert.record_load_when_ready(metrics["moe_load"], experts)
        return state, metrics

    step.__wrapped__ = step_fn.__wrapped__
    return step
