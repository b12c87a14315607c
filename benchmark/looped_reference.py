"""The plain reference of the looped decoder (Ouro / LoopLM,
``OuroForCausalLM``): forward pass, exit distribution, loss and gradients
in straightforward ``jax.numpy`` and float32, Python loops over passes and
layers, no kernel, no cache, no recomputation, nothing imported from
``ray_tpu``. (The one loop that is not Python's is over the sample's
sequences in ``loss_and_grads``, so that the chip holds one sequence's
intermediates at a time.)

The equations (tokens ``[B, S]``; ``T`` = ``total_ut_steps``; ``L`` layers;
``N(x; g)`` = ``x / sqrt(mean(x^2) + rms_norm_eps) * g``)::

    x_0 = E[tokens]
    for t = 1..T:                        # the same L layers' weights every time
        h = x_{t-1}
        for l = 1..L:
            h = h + N(Attn_l(N(h; g1_l)); g2_l)     # causal MHA, rotate-half RoPE, no biases
            h = h + N(SwiGLU_l(N(h; g3_l)); g4_l)   # silu(h Wgate) * (h Wup), then Wdown
        x_t = N(h; g_f)                  # the final norm, shared, after every pass; feeds pass t + 1
        lambda_t = sigmoid(x_t . w_e + b_e)         # the exit gate, per position
        logits_t = x_t W_head
    p_1 = lambda_1;  p_t = lambda_t * prod_{j<t} (1 - lambda_j) for t < T;  p_T = prod_{j<T} (1 - lambda_j)
    served (early_exit_threshold = 1): logits_T
    trained: loss = mean over positions of [ sum_t p_t * CE(logits_t, next token) - beta * H(p) ],
             H(p) = - sum_t p_t log p_t

It takes the program's parameter tree as data: ``embed`` [V, d]; ``blocks``
stacked on a leading layer axis with ``attn.wq`` / ``wk`` / ``wv`` [d, h, k],
``attn.wo`` [h, k, d], ``mlp.wi`` gate, ``mlp.wg`` up, ``mlp.wo`` down,
``ln1`` = g1, ``ln1_post`` = g2, ``ln2`` = g3, ``ln2_post`` = g4; ``ln_f`` =
g_f; ``lm_head`` [d, V]; ``exit_gate.w`` [d], ``exit_gate.b`` []. What the
published ``config.json`` does not say (``beta``, the gate's shape, the final
norm between passes, the last pass taking the remainder) is listed under
``assumed`` in ``benchmark/configs/ouro-2.6b.json``.

On a TPU a float32 product runs in lower precision unless asked otherwise,
so every entry point runs under ``jax.default_matmul_precision("highest")``.
"""

from __future__ import annotations

import math
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp

Params = Dict[str, Any]


def _rmsnorm(x, weight, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                        + eps) * weight


def _rotary(x, theta):
    """x: [B, S, heads, k]. Pair i of a head is (x[i], x[i + k/2]), turned
    by position * theta^(-2i/k)."""
    length, k = x.shape[1], x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, k, 2, dtype=jnp.float32) / k)
    angle = jnp.arange(length, dtype=jnp.float32)[:, None] * inv_freq
    cos = jnp.concatenate([jnp.cos(angle), jnp.cos(angle)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(angle), jnp.sin(angle)], -1)[:, None, :]
    x1, x2 = x[..., :k // 2], x[..., k // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def _block(p, h, dims):
    eps, theta = dims["rms_norm_eps"], dims["rope_theta"]
    groups = dims["n_heads"] // dims["n_kv_heads"]
    y = _rmsnorm(h, p["ln1"], eps)
    q = _rotary(jnp.einsum("bsd,dhk->bshk", y, p["attn"]["wq"]), theta)
    k = _rotary(jnp.einsum("bsd,dgk->bsgk", y, p["attn"]["wk"]), theta)
    v = jnp.einsum("bsd,dgk->bsgk", y, p["attn"]["wv"])
    k, v = jnp.repeat(k, groups, axis=2), jnp.repeat(v, groups, axis=2)
    scores = jnp.einsum("bqhk,bshk->bhqs", q, k) / math.sqrt(q.shape[-1])
    length = h.shape[1]
    causal = jnp.tril(jnp.ones((length, length), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    attn = jnp.einsum("bhqs,bshk->bqhk", jax.nn.softmax(scores, -1), v)
    attn = jnp.einsum("bqhk,hkd->bqd", attn, p["attn"]["wo"])
    h = h + _rmsnorm(attn, p["ln1_post"], eps)
    y = _rmsnorm(h, p["ln2"], eps)
    ff = (jax.nn.silu(y @ p["mlp"]["wi"]) * (y @ p["mlp"]["wg"])
          ) @ p["mlp"]["wo"]
    return h + _rmsnorm(ff, p["ln2_post"], eps)


def exit_states(params: Params, tokens, dims) -> List[jax.Array]:
    """Tokens [B, S] -> x_1 .. x_T, each pass's states after the final
    norm."""
    x = params["embed"][tokens]
    n_layers = params["blocks"]["ln1"].shape[0]
    out = []
    for _ in range(dims["total_ut_steps"]):
        h = x
        for i in range(n_layers):
            h = _block(jax.tree.map(lambda p: p[i], params["blocks"]), h,
                       dims)
        x = _rmsnorm(h, params["ln_f"], dims["rms_norm_eps"])
        out.append(x)
    return out


def exit_probabilities(params: Params, states: List[jax.Array]
                       ) -> List[jax.Array]:
    """p_1 .. p_T, each [B, S]: the gate's lambda_t times the probability
    of not having left before; the last pass takes what is left."""
    gate = params["exit_gate"]
    probs, left = [], 1.0
    for x in states[:-1]:
        lam = jax.nn.sigmoid(jnp.sum(x * gate["w"], axis=-1) + gate["b"])
        probs.append(lam * left)
        left = left * (1.0 - lam)
    return probs + [left * jnp.ones(states[-1].shape[:-1], jnp.float32)]


def _float32(params: Params) -> Params:
    return jax.tree.map(lambda p: p.astype(jnp.float32), params)


def _objective(params: Params, tokens, dims
               ) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array]]:
    states = exit_states(params, tokens[:, :-1], dims)
    probs = exit_probabilities(params, states)
    expected = entropy = 0.0
    for x, p in zip(states, probs):
        logp = jax.nn.log_softmax(x @ params["lm_head"], axis=-1)
        nll = -jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)[..., 0]
        expected = expected + p * nll
        # p log p -> 0 as p -> 0
        entropy = entropy - jnp.where(p > 0, p * jnp.log(
            jnp.where(p > 0, p, 1.0)), 0.0)
    value = jnp.mean(expected - dims["exit_beta"] * entropy)
    return value, (jnp.stack([jnp.mean(p) for p in probs]),
                   jnp.mean(entropy))


def every_exit_logits(params: Params, tokens, dims) -> List[jax.Array]:
    """Tokens [B, S] -> logits_1 .. logits_T, each [B, S, V]."""
    with jax.default_matmul_precision("highest"):
        params = _float32(params)
        return [x @ params["lm_head"]
                for x in exit_states(params, tokens, dims)]


def exit_distribution(params: Params, tokens, dims) -> jax.Array:
    """Tokens [B, S] -> p, [T, B, S]."""
    with jax.default_matmul_precision("highest"):
        params = _float32(params)
        return jnp.stack(exit_probabilities(
            params, exit_states(params, tokens, dims)))


def last_logits(params: Params, tokens, dims) -> jax.Array:
    """Tokens [B, S] -> float32 logits [B, V] of the last pass at the last
    position: what is served while ``early_exit_threshold`` is 1."""
    with jax.default_matmul_precision("highest"):
        params = _float32(params)
        return (exit_states(params, tokens, dims)[-1][:, -1, :]
                @ params["lm_head"])


def loss_and_exits(params: Params, tokens, dims):
    """The loss, the mean exit probability of each pass [T] and the mean
    entropy of the exit distribution."""
    with jax.default_matmul_precision("highest"):
        value, (exit_p, entropy) = _objective(_float32(params), tokens, dims)
        return value, exit_p, entropy


def loss_and_grads(params: Params, tokens, dims):
    """The loss and its gradient with respect to every parameter. One
    sequence at a time: every sequence is as long as the others, so the
    batch's loss and gradient are the means of theirs, and the float32
    intermediates of T x L blocks of one sequence fit on the chip beside
    the parameters where the whole sample's would not."""
    with jax.default_matmul_precision("highest"):
        params = _float32(params)

        def add(total, row):
            (value, _), grads = jax.value_and_grad(_objective, has_aux=True)(
                params, row[None], dims)
            return jax.tree.map(jnp.add, total, (value, grads)), None

        zero = (jnp.zeros((), jnp.float32), jax.tree.map(jnp.zeros_like,
                                                         params))
        total, _ = jax.lax.scan(add, zero, tokens)
        return jax.tree.map(lambda x: x / tokens.shape[0], total)


def loss_and_grad_norm(params: Params, tokens, dims):
    """The loss and the global L2 norm of its gradient over all
    parameters."""
    value, grads = loss_and_grads(params, tokens, dims)
    squares = sum(jnp.sum(g * g) for g in jax.tree.leaves(grads))
    return value, jnp.sqrt(squares)
