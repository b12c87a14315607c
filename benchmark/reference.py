"""The plain reference: the two configurations' forward pass, loss and
gradients in straightforward ``jax.numpy`` and float32.

Written from the published descriptions of ``MistralForCausalLM`` and
``InternLM2ForCausalLM`` (the same decoder: pre-norm residual blocks,
RMSNorm with the published epsilon, rotary embeddings in the rotate-half
form on the first and second half of each head, grouped-query attention
with each key/value head shared by ``n_heads / n_kv_heads`` consecutive
query heads, a causal softmax, a SwiGLU feed-forward, a final RMSNorm and an
untied head without biases). No kernel, no cache, no batching, no
recomputation, and nothing imported from ``ray_tpu``: it takes the
program's parameter tree as data (``embed`` [V, d]; ``blocks`` stacked on a
leading layer axis with ``attn.wq`` [d, h, k], ``attn.wk``/``wv`` [d, g, k],
``attn.wo`` [h, k, d], ``mlp.wi`` gate, ``mlp.wg`` up, ``mlp.wo`` down,
``ln1``, ``ln2``; ``ln_f``; ``lm_head`` [d, V]). InternLM2 publishes q, k
and v packed in one ``wqkv``; with seeded random weights the packing is a
relabelling, so the reference keeps them apart.

On a TPU a float32 product runs in lower precision unless asked otherwise,
so every entry point runs under ``jax.default_matmul_precision("highest")``.
"""

from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

Params = Dict[str, Any]


def _rmsnorm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * weight


def _rotary(x, theta):
    """x: [B, L, heads, k]. Pair i of a head is (x[i], x[i + k/2]), turned
    by position * theta^(-2i/k)."""
    length, k = x.shape[1], x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, k, 2, dtype=jnp.float32) / k)
    angle = jnp.arange(length, dtype=jnp.float32)[:, None] * inv_freq
    cos = jnp.concatenate([jnp.cos(angle), jnp.cos(angle)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(angle), jnp.sin(angle)], -1)[:, None, :]
    x1, x2 = x[..., :k // 2], x[..., k // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def _block(p, x, dims):
    h, g, eps = dims["n_heads"], dims["n_kv_heads"], dims["rms_norm_eps"]
    y = _rmsnorm(x, p["ln1"], eps)
    q = _rotary(jnp.einsum("bld,dhk->blhk", y, p["attn"]["wq"]),
                dims["rope_theta"])
    k = _rotary(jnp.einsum("bld,dgk->blgk", y, p["attn"]["wk"]),
                dims["rope_theta"])
    v = jnp.einsum("bld,dgk->blgk", y, p["attn"]["wv"])
    k = jnp.repeat(k, h // g, axis=2)
    v = jnp.repeat(v, h // g, axis=2)
    scores = jnp.einsum("bqhk,bshk->bhqs", q, k) / math.sqrt(q.shape[-1])
    length = x.shape[1]
    causal = jnp.tril(jnp.ones((length, length), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    attn = jnp.einsum("bhqs,bshk->bqhk", jax.nn.softmax(scores, -1), v)
    x = x + jnp.einsum("bqhk,hkd->bqd", attn, p["attn"]["wo"])
    y = _rmsnorm(x, p["ln2"], eps)
    gate = jax.nn.silu(y @ p["mlp"]["wi"])
    return x + (gate * (y @ p["mlp"]["wg"])) @ p["mlp"]["wo"]


def hidden(params: Params, tokens, dims) -> jax.Array:
    """Tokens [B, L] -> the last block's output after the final norm."""
    x = params["embed"][tokens]
    n_layers = params["blocks"]["ln1"].shape[0]
    for i in range(n_layers):
        x = _block(jax.tree.map(lambda p: p[i], params["blocks"]), x, dims)
    return _rmsnorm(x, params["ln_f"], dims["rms_norm_eps"])


def _float32(params: Params) -> Params:
    return jax.tree.map(lambda p: p.astype(jnp.float32), params)


def last_logits(params: Params, tokens, dims) -> jax.Array:
    """Tokens [B, L] -> float32 logits [B, V] at the last position."""
    with jax.default_matmul_precision("highest"):
        params = _float32(params)
        return hidden(params, tokens, dims)[:, -1, :] @ params["lm_head"]


def loss(params: Params, tokens, dims) -> jax.Array:
    """Mean next-token cross entropy over tokens [B, L + 1]."""
    logits = hidden(params, tokens[:, :-1], dims) @ params["lm_head"]
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)
    return -jnp.mean(picked)


def loss_and_grad_norm(params: Params, tokens, dims):
    """The loss and the global L2 norm of its gradient over all
    parameters."""
    with jax.default_matmul_precision("highest"):
        value, grads = jax.value_and_grad(loss)(_float32(params), tokens,
                                                dims)
        squares = sum(jnp.sum(g * g) for g in jax.tree.leaves(grads))
        return value, jnp.sqrt(squares)
