"""The plain reference of SmallThinker's sparse decoder
(``PowerInfer/SmallThinker-21BA3B-Instruct``; arXiv:2507.20984): the forward
in ``jax.numpy``, float32, ``jax.default_matmul_precision("highest")``, no
kernel, no cache, no ring (a mask), no grouped product. Nothing of the program
is imported.

One layer (``N`` is RMSNorm with a weight, eps ``rms_norm_eps``; ``x`` the
block's input [S, d])::

    logits = x W_r                      # the router reads x itself, first
    chosen = the top_k largest logits;  w = softmax(logits[chosen])
    h = x + Attn(N1(x))
    y = h + sum_j w_j W_down,j( relu(W_gate,j g) * (W_up,j g) ),  g = N2(h)

**The weights are fixed before the attention, the experts read the state
after it.** Attention is grouped-query at ``head_dim`` (which is not ``d /
n_heads``), scale ``head_dim ** -0.5``, no bias, no q/k norm. By
``layer_types[i]``:

* ``window`` (``rope_layout`` 1, ``sliding_window_layout`` 1): q and k
  rotated by halves over all ``head_dim`` lanes at theta ``rope_theta``, no
  scaling; position ``t`` attends the keys ``t - window < j <= t`` (the
  window counts the token itself).
* ``global`` (both 0): nothing is rotated and the mask is causal alone.

Embedding, the blocks, a final ``N``, an untied ``lm_head``.

Departures from the published description, each because of what the
configuration's file states (``departures``, ``assumed``):

* **Seeded weights.**
* **The router the published way**: the ``top_k`` largest *logits*, then a
  softmax over those (``moe_primary_router_apply_softmax``;
  ``norm_topk_prob`` then changes nothing). The program takes a softmax over
  all the logits, the ``top_k`` largest, renormalised over ``sum + 1e-6``:
  the same numbers up to that epsilon.
* **A whole forward a comparison**: no state outlives a call here (that is
  what the served path is compared *with*); every layer's k, as rotated, is
  handed out for the comparison with what the two caches hold.
* **Blocks.** Attention runs ``QUERY_BLOCK`` query positions at a time and
  the experts one at a time (every expert on every token, under the router's
  mostly zero weight for it), so that 12,672 tokens fit.
* **Streamed weights.** ``logits_and_keys_from`` is handed the seed's key
  (``reference_params``) and draws each layer's float32 weights where it uses
  them, by the program's rule (``draw_layer``: a copy of
  ``transformer.init_params`` for these two kinds of layer; a test compares
  them leaf for leaf). One layer is 1.6 GB in float32 and the cut's 8 layers
  with the embedding and the head 15.9 GB.
"""

from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

WINDOW, GLOBAL = "window", "global"     # ``layer_types``' values
QUERY_BLOCK = 512   # query positions whose [heads, block, S] scores are alive


# -- the draw: transformer.init_params for these kinds of layer, copied ------------


def _normal(key, shape, fan_in):
    return jax.random.normal(key, shape, jnp.float32) / math.sqrt(fan_in)


def draw_attention(key, dims):
    d, h, kvh, hd = (dims["d_model"], dims["n_heads"], dims["n_kv_heads"],
                     dims["head_dim"])
    ks = jax.random.split(key, 4)
    return {"wq": _normal(ks[0], (d, h, hd), d),
            "wk": _normal(ks[1], (d, kvh, hd), d),
            "wv": _normal(ks[2], (d, kvh, hd), d),
            "wo": _normal(ks[3], (h, hd, d), h * hd)}


def draw_ffn(key, d, width):
    ks = jax.random.split(key, 3)
    return {"wi": _normal(ks[0], (d, width), d),        # gate
            "wg": _normal(ks[1], (d, width), d),        # up
            "wo": _normal(ks[2], (width, d), width)}    # down


def draw_layer(key, dims) -> Dict[str, Any]:
    """A layer from its key, whatever its kind (the two kinds hold the same
    leaves): one half for the attention, one for the router and the experts,
    an expert from the FFN's third key folded with its published index."""
    d, n = dims["d_model"], dims["n_experts"]
    k_mixer, k_ffn = jax.random.split(key)
    ks = jax.random.split(k_ffn, 3)
    ones = jnp.ones((d,), jnp.float32)
    return {"attn": draw_attention(k_mixer, dims),
            "router": _normal(ks[0], (d, n), d),
            "experts": jax.vmap(lambda i: draw_ffn(
                jax.random.fold_in(ks[2], i), d, dims["expert_width"]))(
                    jnp.arange(n)),
            "ln1": ones, "ln2": ones}


def split_keys(key, dims):
    """``(embedding's key, head's key, [L] layer keys)`` as ``init_params``
    splits them."""
    k_embed, k_head, k_layers = jax.random.split(key, 3)
    return k_embed, k_head, jax.random.split(k_layers, dims["n_layers"])


def embedding(k_embed, dims):
    return jax.random.normal(k_embed, (dims["vocab_size"], dims["d_model"]),
                             jnp.float32) * 0.02


def lm_head(k_head, dims):
    return _normal(k_head, (dims["d_model"], dims["vocab_size"]),
                   dims["d_model"])


def tree_kind(kind: str) -> str:
    """The name of a layer's stacked tree in the program's ``blocks``."""
    return "window_moe" if kind == WINDOW else "global_moe"


def draw_tree(key, dims):
    """The whole float32 tree as ``init_params`` names it (small sizes: the
    tests' comparison with the program's draw)."""
    k_embed, k_head, layer_keys = split_keys(key, dims)
    stacks: Dict[str, list] = {}
    for kind, k in zip(dims["layer_types"], layer_keys):
        stacks.setdefault(tree_kind(kind), []).append(draw_layer(k, dims))
    return {"embed": embedding(k_embed, dims),
            "blocks": {kind: jax.tree.map(lambda *p: jnp.stack(p), *trees)
                       for kind, trees in stacks.items()},
            "ln_f": jnp.ones((dims["d_model"],), jnp.float32),
            "lm_head": lm_head(k_head, dims)}


def from_tree(params, i: int, dims) -> Dict[str, Any]:
    """Layer ``i`` of the program's own parameter tree: the ``j``-th of its
    kind's stack, ``j`` the layers of that kind before it."""
    kind = dims["layer_types"][i]
    j = sum(k == kind for k in dims["layer_types"][:i])
    return jax.tree.map(lambda p: p[j], params["blocks"][tree_kind(kind)])


# -- the forward, one sequence [S, d] ----------------------------------------------


def rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * w


def rope_halves(x, theta):
    """x [S, heads, D] rotated by halves at positions 0 .. S - 1."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None, None] * freqs
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention_and_keys(p, u, kind: str, dims):
    """Grouped-query attention on the normed states u [S, d], a block of
    query positions at a time, and the layer's k [S, kv_heads x head_dim] as
    the scores read it (rotated in a window layer)."""
    S = u.shape[0]
    q = jnp.einsum("sd,dhk->shk", u, p["wq"])
    k = jnp.einsum("sd,dhk->shk", u, p["wk"])
    v = jnp.einsum("sd,dhk->shk", u, p["wv"])
    if kind == WINDOW:
        q, k = (rope_halves(q, dims["rope_theta"]),
                rope_halves(k, dims["rope_theta"]))
    reach = dims["window"] if kind == WINDOW else S     # keys a row sees
    rep = q.shape[1] // k.shape[1]
    k_heads, v_heads = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    block = min(QUERY_BLOCK, S)
    pad = -S % block
    starts = jnp.arange(0, S + pad, block)
    qs = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(
        -1, block, *q.shape[1:])
    cols = jnp.arange(S)[None]

    def rows(at):
        start, qb = at                                  # [block, h, D]
        t = (start + jnp.arange(block))[:, None]
        seen = (cols <= t) & (cols > t - reach)         # [block, S]
        s = jnp.einsum("qhd,khd->hqk", qb, k_heads) * dims["head_dim"] ** -0.5
        prob = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", prob, v_heads)

    o = jax.lax.map(rows, (starts, qs)).reshape(-1, *q.shape[1:])[:S]
    return jnp.einsum("shk,hkd->sd", o, p["wo"]), k.reshape(S, -1)


def route(x, router, dims):
    """Each token's weight for every expert [S, n_experts], zero for those it
    did not choose: the ``top_k`` largest logits, a softmax over them. And
    how narrowly each token chose [S]: the last logit chosen less the first
    one left out, over the spread (standard deviation) of that token's
    logits. A program that rounds the router's input otherwise than this
    float32 stream chooses another expert where that margin is under the
    rounding's reach."""
    logits = x @ router
    top, idx = jax.lax.top_k(logits, dims["top_k"] + 1)
    w = jax.nn.softmax(top[..., :-1], axis=-1)
    margin = (top[..., -2] - top[..., -1]) / jnp.std(logits, axis=-1)
    return jnp.sum(jax.nn.one_hot(idx[..., :-1], dims["n_experts"])
                   * w[..., None], axis=1), margin


def mixture(g, weights, experts):
    """``sum_e weights[:, e] Expert_e(g)``, an expert a ReGLU, one at a
    time."""
    def add(out, at):
        p, w = at
        y = (jax.nn.relu(g @ p["wi"]) * (g @ p["wg"])) @ p["wo"]
        return out + w[:, None] * y, None

    return jax.lax.scan(add, jnp.zeros_like(g), (experts, weights.T))[0]


def block_and_keys(layer, x, kind: str, dims):
    """A layer of ``kind`` on one sequence x [S, d], and ``(its k, its
    router's margins [S])``."""
    eps = dims["rms_norm_eps"]
    weights, margin = route(x, layer["router"], dims)   # before any norm
    out, k = attention_and_keys(layer["attn"], rmsnorm(x, layer["ln1"], eps),
                                kind, dims)
    h = x + out
    return h + mixture(rmsnorm(h, layer["ln2"], eps), weights,
                       layer["experts"]), (k, margin)


def streamed_states(x, layer_keys, dims):
    """The stack on one sequence x [S, d], each layer's weights drawn from
    its key where the layer runs: one scan over the keys, the layer's kind a
    ``lax.cond`` (both kinds compiled once). Returns the states [S, d] and
    ``(every layer's k [L, S, kv_heads x head_dim], its margins [L, S])``."""
    is_window = jnp.array([k == WINDOW for k in dims["layer_types"]])

    def layer(x, at):
        key, window_here = at
        return jax.lax.cond(
            window_here,
            lambda x: block_and_keys(draw_layer(key, dims), x, WINDOW, dims),
            lambda x: block_and_keys(draw_layer(key, dims), x, GLOBAL, dims),
            x)

    return jax.lax.scan(layer, x, (layer_keys, is_window))


def _logits(x, ln_f, head, dims):
    return rmsnorm(x, ln_f, dims["rms_norm_eps"]) @ head


def logits_and_keys_from(key, tokens, first, n: int, dims):
    """One sequence ``tokens`` [S] -> float32 logits [n, V] at the positions
    ``first`` .. ``first + n - 1`` (``first`` may be traced), every layer's
    k [L, S, kv_heads x head_dim] at every position (what the two caches
    hold rows of) and every layer's router's margins [L, S] (``route``),
    every weight drawn from ``key`` where it is used.
    Positions to the right change nothing before them, so a sequence may be
    padded there."""
    with jax.default_matmul_precision("highest"):
        k_embed, k_head, layer_keys = split_keys(key, dims)
        x, (keys, margins) = streamed_states(
            embedding(k_embed, dims)[tokens], layer_keys, dims)
        x, k_head = jax.lax.optimization_barrier(
            (jax.lax.dynamic_slice_in_dim(x, first, n), k_head))
        return _logits(x, jnp.ones((dims["d_model"],)),
                       lm_head(k_head, dims), dims), keys, margins


def logits_from(key, tokens, first, n: int, dims):
    return logits_and_keys_from(key, tokens, first, n, dims)[0]


def last_logits(key, tokens, dims):
    """tokens [B, S] -> float32 logits [B, V] at the last position (the
    adapter's contract; the sequences of a batch run one after another)."""
    S = tokens.shape[1]
    return jax.lax.map(
        lambda row: logits_from(key, row, S - 1, 1, dims)[0], tokens)


def tree_logits_and_keys(params, tokens, dims):
    """Every position's logits [B, S, V] and every layer's k [B, L, S, kv]
    on a whole parameter tree (``init_params``'s, small sizes: what the
    tests compare the program's forward, its prefill and its decode steps
    with)."""
    def one(row):
        x, keys = params["embed"][row], []
        for i, kind in enumerate(dims["layer_types"]):
            x, (k, _) = block_and_keys(from_tree(params, i, dims), x, kind,
                                       dims)
            keys.append(k)
        return (_logits(x, params["ln_f"], params["lm_head"], dims),
                jnp.stack(keys))

    with jax.default_matmul_precision("highest"):
        return jax.vmap(one)(tokens)


def tree_logits(params, tokens, dims):
    return tree_logits_and_keys(params, tokens, dims)[0]


def loss_and_grad_norm(params, tokens, dims):
    """Mean next-token cross entropy of ``tokens`` [B, S + 1] on a whole tree
    and the norm of its gradient (no cell trains this configuration: the
    adapter's contract asks for the name)."""
    with jax.default_matmul_precision("highest"):
        def loss(params):
            logp = jax.nn.log_softmax(
                tree_logits(params, tokens[:, :-1], dims), axis=-1)
            return -jnp.mean(jnp.take_along_axis(
                logp, tokens[:, 1:, None], axis=-1))

        value, grads = jax.value_and_grad(loss)(params)
        norm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
        return value, norm
