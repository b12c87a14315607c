"""The plain reference of Granite 4.0-H's hybrid decoder (``granitemoehybrid``
without experts; ``ibm-granite/granite-4.0-h-micro``): the forward in
``jax.numpy``, float32, ``jax.default_matmul_precision("highest")``, no
kernel, no chunks, no cache, no slots. Nothing of the program is imported.

One layer (``N`` is RMSNorm with a weight, eps ``rms_norm_eps``; ``r`` is
``residual_multiplier``)::

    h = x + r * Mixer(N1(x));  y = h + r * MLP(N2(h))
    MLP(u) = W_out(silu(a) * b),  [a | b] = u W_in  (``wi`` | ``wg``)

The embedding times ``embedding_multiplier``; a final ``N``; logits = ``h E^T
/ logits_scaling`` (the head is the embedding). The mixer by
``layer_types[i]``:

* ``attention``: q, k, v to ``n_heads`` / ``n_kv_heads`` / ``n_kv_heads``
  heads of ``head_dim``, no bias, **no positions**; a full causal softmax of
  ``q k^T * attention_multiplier``; ``W_o``.
* ``mamba`` (Mamba-2): ``[z | xBC | dt] = u W_in`` (widths ``d_inner`` |
  ``d_inner + 2 d_state`` | ``n_heads``); ``xBC = silu(conv(xBC) + b)``,
  depthwise, causal, ``mamba_d_conv`` taps, zeros before the first position;
  ``[x | B | C]`` = ``d_inner`` | ``d_state`` | ``d_state``, ``x`` as heads of
  ``mamba_d_head``; ``dt = softplus(dt + dt_bias)``; ``A = -exp(a_log)`` a
  head; a head's ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T``, ``y_t = S_t
  C_t + D x_t``, **a ``lax.scan`` over the tokens**; ``g = RMSNorm(y *
  silu(z)) * w`` over all ``d_inner`` channels; ``g W_out``.

Departures from the published code, each because of what the configuration's
file states (``departures``): seeded weights; greedy answers of a fixed
length; a whole forward a comparison (no state outlives a call here: that is
what the served path is compared *with*; the recurrence's state after one
position is handed out, for the comparison with what a decode loop holds).

**Streamed weights.** ``logits_and_state_from`` (``logits_from`` is its
first result) is handed the seed's key
(``reference_params``) and draws each layer's float32 weights where it uses
them, by the program's rule (``draw_layer``: a copy of
``transformer.init_params`` for these two kinds of layer; a test compares them
leaf for leaf). The layers are one ``lax.scan`` over their keys whose body
holds both kinds' layers under a ``lax.cond``: one Mamba and one attention
layer are compiled whatever the depth, and one layer's weights (0.3 GB) are
alive at a time of the tree's 12.8 GB.
"""

from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

MAMBA, ATTENTION = "mamba", "attention"      # ``layer_types``' values


# -- the draw: transformer.init_params for these kinds of layer, copied ------------


def _normal(key, shape, fan_in):
    return jax.random.normal(key, shape, jnp.float32) / math.sqrt(fan_in)


def _on_grid(v):
    """Rounded to bfloat16's grid (the serving tree's cast leaves it so)."""
    return v.astype(jnp.bfloat16).astype(jnp.float32)


def draw_attention(key, dims):
    d, h, kvh, hd = (dims["d_model"], dims["n_heads"], dims["n_kv_heads"],
                     dims["head_dim"])
    ks = jax.random.split(key, 4)
    return {"wq": _normal(ks[0], (d, h, hd), d),
            "wk": _normal(ks[1], (d, kvh, hd), d),
            "wv": _normal(ks[2], (d, kvh, hd), d),
            "wo": _normal(ks[3], (h, hd, d), h * hd)}


def draw_mamba(key, dims):
    d, heads, state, taps = (dims["d_model"], dims["mamba_heads"],
                             dims["d_state"], dims["conv_width"])
    inner = heads * dims["mamba_head_dim"]
    conv_dim = inner + 2 * state
    ks = jax.random.split(key, 6)
    step = jnp.exp(jax.random.uniform(ks[4], (heads,), jnp.float32,
                                      math.log(1e-3), math.log(1e-1)))
    return {"w_in": _normal(ks[0], (d, 2 * inner + 2 * state + heads), d),
            "conv": _normal(ks[1], (conv_dim, taps), taps),
            "conv_bias": _on_grid(0.02 * jax.random.normal(
                ks[2], (conv_dim,), jnp.float32)),
            "a_log": _on_grid(jnp.log(jax.random.uniform(
                ks[3], (heads,), jnp.float32, 1.0, 16.0))),
            "dt_bias": _on_grid(step + jnp.log(-jnp.expm1(-step))),
            "d_skip": jnp.ones((heads,), jnp.float32),
            "norm": jnp.ones((inner,), jnp.float32),
            "w_out": _normal(ks[5], (inner, d), inner)}


def draw_ffn(key, d, width):
    ks = jax.random.split(key, 3)
    return {"wi": _normal(ks[0], (d, width), d),
            "wg": _normal(ks[1], (d, width), d),
            "wo": _normal(ks[2], (width, d), width)}


def draw_layer(key, kind: str, dims) -> Dict[str, Any]:
    """A layer of ``kind`` from its key: one half for the mixer, one for the
    FFN."""
    d = dims["d_model"]
    k_mixer, k_ffn = jax.random.split(key)
    ones = jnp.ones((d,), jnp.float32)
    mixer = (draw_mamba(k_mixer, dims) if kind == MAMBA
             else draw_attention(k_mixer, dims))
    return {"mixer": mixer, "mlp": draw_ffn(k_ffn, d, dims["d_ff"]),
            "ln1": ones, "ln2": ones}


def split_keys(key, dims):
    """``(embedding's key, [L] layer keys)`` as ``init_params`` splits them
    (the head's key is drawn and unused: the head is tied)."""
    k_embed, _, k_layers = jax.random.split(key, 3)
    return k_embed, jax.random.split(k_layers, dims["n_layers"])


def embedding(k_embed, dims):
    return jax.random.normal(k_embed, (dims["vocab_size"], dims["d_model"]),
                             jnp.float32) * 0.02


def tree_kind(kind: str) -> str:
    """The name of a layer's stacked tree (and of its mixer's sub-tree) in
    the program's ``blocks``."""
    return "mamba" if kind == MAMBA else "attn"


def draw_tree(key, dims):
    """The whole float32 tree as ``init_params`` names it (small sizes: the
    tests' comparison with the program's draw)."""
    k_embed, layer_keys = split_keys(key, dims)
    stacks: Dict[str, list] = {}
    for kind, k in zip(dims["layer_types"], layer_keys):
        layer = draw_layer(k, kind, dims)
        name = tree_kind(kind)
        stacks.setdefault(name, []).append(
            {name: layer["mixer"], "mlp": layer["mlp"], "ln1": layer["ln1"],
             "ln2": layer["ln2"]})
    return {"embed": embedding(k_embed, dims),
            "blocks": {kind: jax.tree.map(lambda *p: jnp.stack(p), *trees)
                       for kind, trees in stacks.items()},
            "ln_f": jnp.ones((dims["d_model"],), jnp.float32)}


def from_tree(params, i: int, dims) -> Dict[str, Any]:
    """Layer ``i`` of the program's own parameter tree: the ``j``-th of its
    kind's stack, ``j`` the layers of that kind before it."""
    kind = dims["layer_types"][i]
    name = tree_kind(kind)
    j = sum(k == kind for k in dims["layer_types"][:i])
    tree = jax.tree.map(lambda p: p[j], params["blocks"][name])
    return {"mixer": tree[name], "mlp": tree["mlp"], "ln1": tree["ln1"],
            "ln2": tree["ln2"]}


# -- the forward, one sequence [S, d] ----------------------------------------------


def rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * w


def attention(p, u, dims):
    """NoPE grouped-query attention on the normed states u [S, d]: a full
    masked softmax at ``attention_multiplier``."""
    S = u.shape[0]
    q = jnp.einsum("sd,dhk->shk", u, p["wq"])
    k = jnp.einsum("sd,dhk->shk", u, p["wk"])
    v = jnp.einsum("sd,dhk->shk", u, p["wv"])
    rep = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k) * dims["attn_scale"]
    mask = jnp.tril(jnp.ones((S, S), bool))
    prob = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
    return jnp.einsum("shk,hkd->sd", jnp.einsum("hqk,khd->qhd", prob, v),
                      p["wo"])


def back(g, n):
    """Row t of the result is row t - n of g; zeros before the first."""
    return jnp.concatenate([jnp.zeros_like(g[:n]), g[:g.shape[0] - n]])


def mamba_and_state(p, u, dims, last, skip: bool = True):
    """The Mamba-2 mixer on the normed states u [S, d], the recurrence token
    by token, and the state [heads, head_dim, d_state] once position ``last``
    (may be traced) is taken in. ``skip=False`` leaves ``D x`` out (a control
    of the tests)."""
    heads, hd, state, taps = (dims["mamba_heads"], dims["mamba_head_dim"],
                              dims["d_state"], dims["conv_width"])
    inner = heads * hd
    zxd = u @ p["w_in"]
    z, xbc, dt = (zxd[:, :inner], zxd[:, inner:2 * inner + 2 * state],
                  zxd[:, 2 * inner + 2 * state:])
    conv = sum(p["conv"][:, j] * back(xbc, taps - 1 - j)
               for j in range(taps)) + p["conv_bias"]
    xbc = jax.nn.silu(conv)
    x = xbc[:, :inner].reshape(-1, heads, hd)
    b, c = xbc[:, inner:inner + state], xbc[:, inner + state:]
    dt = jax.nn.softplus(dt + p["dt_bias"])                     # [S, H]
    a = -jnp.exp(p["a_log"])

    def token(carry, at):
        s, kept = carry
        x_t, b_t, c_t, dt_t, here = at
        s = jnp.exp(dt_t * a)[:, None, None] * s \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[None, None, :]
        return ((s, jnp.where(here, s, kept)),
                jnp.sum(s * c_t[None, None, :], axis=-1))

    zeros = jnp.zeros((heads, hd, state), jnp.float32)
    (_, kept), y = jax.lax.scan(
        token, (zeros, zeros), (x, b, c, dt, jnp.arange(u.shape[0]) == last))
    if skip:
        y = y + p["d_skip"][:, None] * x
    g = rmsnorm(y.reshape(-1, inner) * jax.nn.silu(z), p["norm"],
                dims["rms_norm_eps"])
    return g @ p["w_out"], kept


def mamba(p, u, dims, skip: bool = True):
    return mamba_and_state(p, u, dims, u.shape[0] - 1, skip)[0]


def ffn(p, u):
    return (jax.nn.silu(u @ p["wi"]) * (u @ p["wg"])) @ p["wo"]


def block_and_state(layer, x, kind: str, dims, last):
    """A layer of ``kind`` on one sequence x [S, d], and what a Mamba layer's
    recurrence holds after position ``last`` (zeros for an attention
    layer)."""
    eps, r = dims["rms_norm_eps"], dims["residual_scale"]
    u = rmsnorm(x, layer["ln1"], eps)
    if kind == MAMBA:
        out, kept = mamba_and_state(layer["mixer"], u, dims, last)
    else:
        out = attention(layer["mixer"], u, dims)
        kept = jnp.zeros((dims["mamba_heads"], dims["mamba_head_dim"],
                          dims["d_state"]), jnp.float32)
    x = x + r * out
    return x + r * ffn(layer["mlp"], rmsnorm(x, layer["ln2"], eps)), kept


def block(layer, x, kind: str, dims):
    return block_and_state(layer, x, kind, dims, x.shape[0] - 1)[0]


def streamed_states(x, layer_keys, dims, last):
    """The stack on one sequence x [S, d], each layer's weights drawn from
    its key where the layer runs: one scan over the keys, the layer's kind a
    ``lax.cond`` (both kinds compiled once). Returns the states [S, d] and
    the Mamba layers' recurrent state after position ``last``, stacked in
    the layers' order [n_mamba, heads, head_dim, d_state]."""
    kinds = dims["layer_types"]
    is_mamba = jnp.array([k == MAMBA for k in kinds])

    def layer(x, at):
        key, mamba_here = at
        return jax.lax.cond(
            mamba_here,
            lambda x: block_and_state(draw_layer(key, MAMBA, dims), x, MAMBA,
                                      dims, last),
            lambda x: block_and_state(draw_layer(key, ATTENTION, dims), x,
                                      ATTENTION, dims, last), x)

    x, kept = jax.lax.scan(layer, x, (layer_keys, is_mamba))
    return x, kept[jnp.array([i for i, k in enumerate(kinds) if k == MAMBA])]


def _logits(x, embed, ln_f, dims):
    """States [n, d] -> logits [n, V]: final norm, the tied head, over
    ``logits_scaling``."""
    return (rmsnorm(x, ln_f, dims["rms_norm_eps"]) @ embed.T) \
        * dims["logit_scale"]


def logits_and_state_from(key, tokens, first, n: int, dims):
    """One sequence ``tokens`` [S] -> float32 logits [n, V] at the positions
    ``first`` .. ``first + n - 1`` (``first`` may be traced) and the Mamba
    layers' recurrent state [n_mamba, heads, head_dim, d_state] once the last
    of those positions is taken in (what a decode loop holds when it has
    chosen its ``n``-th token), every weight drawn from ``key`` where it is
    used. Positions to the right change nothing before them, so a sequence
    may be padded there."""
    with jax.default_matmul_precision("highest"):
        k_embed, layer_keys = split_keys(key, dims)
        x = embedding(k_embed, dims)[tokens] * dims["embed_scale"]
        x, kept = streamed_states(x, layer_keys, dims, first + n - 1)
        x, k = jax.lax.optimization_barrier(
            (jax.lax.dynamic_slice_in_dim(x, first, n), k_embed))
        return _logits(x, embedding(k, dims),
                       jnp.ones((dims["d_model"],)), dims), kept


def logits_from(key, tokens, first, n: int, dims):
    return logits_and_state_from(key, tokens, first, n, dims)[0]


def last_logits(key, tokens, dims):
    """tokens [B, S] -> float32 logits [B, V] at the last position (the
    adapter's contract; the sequences of a batch run one after another)."""
    S = tokens.shape[1]
    return jax.lax.map(
        lambda row: logits_from(key, row, S - 1, 1, dims)[0], tokens)


def _tree_states(params, row, dims):
    x = params["embed"][row] * dims["embed_scale"]
    for i, kind in enumerate(dims["layer_types"]):
        x = block(from_tree(params, i, dims), x, kind, dims)
    return x


def tree_logits(params, tokens, dims):
    """Every position's logits [B, S, V] on a whole parameter tree
    (``init_params``'s, small sizes: what the tests compare the program's
    forward, its prefill and its decode steps with)."""
    with jax.default_matmul_precision("highest"):
        return jax.vmap(lambda row: _logits(
            _tree_states(params, row, dims), params["embed"], params["ln_f"],
            dims))(tokens)


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
