"""The plain reference of Kanana-2's decoder (``deepseek_v3``;
``kakaocorp/kanana-2-30b-a3b-instruct-2601``): forward, loss and gradients in
``jax.numpy``, float32, ``jax.default_matmul_precision("highest")``, the
attention scores materialised, every held expert applied to every token and
masked by the choice: no kernel, no loop over chunks, no grouped product.
Nothing of the program is imported; the parameter tree is the program's, by
its names.

One layer (``N`` is RMSNorm with a weight, eps ``rms_norm_eps``)::

    h = x + MLA(N1(x));  y = h + FFN_l(N2(h))

* **MLA, no q bottleneck** (``q_lora_rank`` null). ``q = u W_q`` as
  ``n_heads`` heads of ``nope_dim + rope_dim``; ``[c | k_r] = u W_kva``
  (``kv_rank | rope_dim``); ``c = N(c) * sqrt(d_model / kv_rank)`` (the
  repo's scaling, see below); ``[k_n | v] = c W_kvb`` as heads of ``nope_dim
  | v_dim``; rotary positions on the interleaved pairs (2i, 2i + 1) of q's
  last ``rope_dim`` and of ``k_r``, which every head shares, theta
  ``rope_theta``; causal softmax at ``(nope_dim + rope_dim) ** -0.5``;
  ``W_o``.
* **FFN_l** of a published layer below ``first_k_dense``: a SwiGLU ``wo(silu(
  wi u) * wg u)`` at ``d_ff``. Else ``Shared(u) + sum_j w_j Expert_j(u)``:
  ``Shared`` one SwiGLU at ``shared_width`` (``n_shared_experts x
  moe_intermediate_size``); ``s = sigmoid(u W_r)`` over ``n_routed``
  outputs; chosen = the ``top_k`` largest of ``s + b`` (``noaux_tc``, one
  group: ``b`` for the choice alone); ``w = s[chosen]``; ``w = w / (sum(w) +
  NORM_EPS)`` (``norm_topk_prob``); ``w = scale * w``; an expert a SwiGLU at
  ``expert_width``.
* A final ``N``, an untied head, and the mean next-token cross entropy over
  the (sliced) vocabulary.

What the configuration's file states and this follows (``departures``,
``assumed``, ``reduced["train.1"]``):

* **The held share.** Of the routed experts only ``held = (first, count)``
  are applied; what the absent ones would add is left out, and the partial
  sum goes on. The shared experts are whole on every chip.
* **The up-projection's scaling.** ``W_kvb`` reads ``c * sqrt(d_model /
  kv_rank)`` and is drawn at ``1 / sqrt(d_model)``, so that seeded weights
  give scores of order 1 (LongCat's departure, kept).
* **The gradient** flows through ``w`` (the scores and their normalisation)
  and not through the choice; ``b`` gets none. ``moved_bias`` is the update
  that load makes of it, outside the gradient.
"""

from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

NORM_EPS = 1e-6     # added to the chosen weights' sum before the division
DENSE_KIND, MOE_KIND = "latent", "latent_moe"   # the program's stacked trees


def is_moe(i: int, dims) -> bool:
    """Whether layer ``i`` of the cut (published layer ``layer_ids[i]``) has
    the mixture for its FFN."""
    return dims["layer_ids"][i] >= dims["first_k_dense"]


def layer_of(params: Dict[str, Any], i: int, dims) -> Dict[str, Any]:
    """Layer ``i`` of the program's tree: the ``j``-th of its kind's stack."""
    moe = is_moe(i, dims)
    j = sum(is_moe(n, dims) == moe for n in range(i))
    return jax.tree.map(lambda p: p[j],
                        params["blocks"][MOE_KIND if moe else DENSE_KIND])


def rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * w


def rope_interleaved(x, theta):
    """x [S, H, D]: the neighbouring pairs (2i, 2i + 1) rotated by position
    times ``theta ** (-i / (D / 2))``."""
    S, H, D = x.shape
    half = D // 2
    freqs = jnp.exp(-jnp.arange(half, dtype=jnp.float32)
                    * (math.log(theta) / half))
    angles = jnp.arange(S, dtype=jnp.float32)[:, None, None] * freqs
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    pairs = x.reshape(S, H, half, 2)
    x1, x2 = pairs[..., 0], pairs[..., 1]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(S, H, D)


def mla(p, u, dims):
    """Latent attention without a q bottleneck on the normed states u [S,
    d]."""
    S = u.shape[0]
    nope, rank = dims["nope_dim"], dims["kv_rank"]
    theta = dims["rope_theta"]
    q = jnp.einsum("sd,dhk->shk", u, p["wq"])
    kv = u @ p["wkv_a"]
    c = rmsnorm(kv[:, :rank], p["kv_norm"], dims["rms_norm_eps"]) \
        * math.sqrt(dims["d_model"] / rank)
    k_v = jnp.einsum("sr,rhk->shk", c, p["wkv_b"])
    k_r = rope_interleaved(kv[:, None, rank:], theta)        # one shared head
    q = jnp.concatenate([q[..., :nope],
                         rope_interleaved(q[..., nope:], theta)], axis=-1)
    k = jnp.concatenate(
        [k_v[..., :nope],
         jnp.broadcast_to(k_r, (S, k_v.shape[1], k_r.shape[-1]))], axis=-1)
    v = k_v[..., nope:]
    scores = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(q.shape[-1])
    mask = jnp.tril(jnp.ones((S, S), bool))
    prob = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
    o = jnp.einsum("hqk,khd->qhd", prob, v)
    return jnp.einsum("shk,hkd->sd", o, p["wo"])


def ffn(p, x):
    return (jax.nn.silu(x @ p["wi"]) * (x @ p["wg"])) @ p["wo"]


def route(u, router, bias, dims):
    """Each token's chosen experts [S, k] and their weights [S, k]."""
    s = jax.nn.sigmoid(u @ router)
    _, idx = jax.lax.top_k(s + bias, dims["top_k"])
    w = jnp.take_along_axis(s, idx, axis=-1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + NORM_EPS)
    return idx, dims["scale"] * w


def routed(u, layer, dims):
    """The held experts' part of the mixture for tokens u [S, d]: every held
    expert applied to every token and weighted by what the router gave it
    there (0 for most)."""
    first, count = dims["held"]
    idx, w = route(u, layer["router"], layer["router_bias"], dims)
    # [S, count]: the token's weight for held expert e, 0 where not chosen
    mine = jnp.sum(jnp.where(
        idx[:, :, None] == first + jnp.arange(count), w[:, :, None], 0.0),
        axis=1)
    each = jax.vmap(ffn, in_axes=(0, None))(layer["experts"], u)  # [count, S, d]
    return jnp.einsum("sc,csd->sd", mine, each)


def choice_counts(u, layer, dims):
    """How many tokens of u [S, d] chose each routed expert, [n_routed]."""
    idx, _ = route(u, layer["router"], layer["router_bias"], dims)
    return jnp.sum(idx[:, :, None] == jnp.arange(dims["n_routed"]),
                   axis=(0, 1))


def moved_bias(bias, counts, dims):
    """``b_e + bias_rate * sign(mean load - load_e)`` (arXiv:2412.19437,
    section 2.1.2)."""
    counts = counts.astype(jnp.float32)
    return bias + dims["bias_rate"] * jnp.sign(jnp.mean(counts) - counts)


def moe_ffn(u, layer, dims):
    """A mixture layer's FFN: the shared experts, whole, and the held routed
    experts' part."""
    return ffn(layer["shared"], u) + routed(u, layer, dims)


def block(layer, x, i: int, dims):
    """Layer ``i`` on one sequence x [S, d]."""
    eps = dims["rms_norm_eps"]
    x = x + mla(layer["latent"], rmsnorm(x, layer["ln1"], eps), dims)
    u = rmsnorm(x, layer["ln2"], eps)
    return x + (moe_ffn(u, layer, dims) if is_moe(i, dims)
                else ffn(layer["mlp"], u))


def states(params, row, dims):
    """Every position's pre-final-norm state of one sequence."""
    x = params["embed"][row]
    for i in range(dims["n_layers"]):
        x = block(layer_of(params, i, dims), x, i, dims)
    return x


def logits(params, row, dims):
    """One sequence's logits [S, V] over the (sliced) vocabulary."""
    return rmsnorm(states(params, row, dims), params["ln_f"],
                   dims["rms_norm_eps"]) @ params["lm_head"]


def last_logits(params, tokens, dims):
    """tokens [B, S] -> float32 logits [B, V] at the last position."""
    with jax.default_matmul_precision("highest"):
        return jax.vmap(lambda row: logits(params, row, dims)[-1])(tokens)


def loss(params, tokens, dims):
    """Mean next-token cross entropy of ``tokens`` [B, S + 1]."""
    with jax.default_matmul_precision("highest"):
        def sequence(row):
            logp = jax.nn.log_softmax(logits(params, row[:-1], dims), axis=-1)
            return -jnp.mean(jnp.take_along_axis(logp, row[1:, None],
                                                 axis=-1))
        return jnp.mean(jax.lax.map(sequence, tokens))


def loss_and_grads(params, tokens, dims):
    return jax.value_and_grad(loss)(params, tokens, dims)


def loss_and_grad_norm(params, tokens, dims):
    """The loss on the program's tree and the norm of its gradient."""
    value, grads = loss_and_grads(params, tokens, dims)
    norm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    return value, norm
