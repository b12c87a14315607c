"""The plain reference of LongCat-Flash's language model (the decoder of
``meituan-longcat/LongCat-Flash-Omni``; ``modeling_longcat_flash``): the
forward in ``jax.numpy``, float32, ``jax.default_matmul_precision("highest")``,
no kernel, no sort, no grouped product. Nothing of the program is imported.

One layer (``N`` is RMSNorm with a weight, eps ``rms_norm_eps``)::

    h = x + MLA_0(N(x));  u = N(h);  s = MoE(u);  h = h + FFN_0(u)
    h = h + MLA_1(N(h));  y = h + FFN_1(N(h)) + s

FFN: SwiGLU ``wo(silu(wi u) * wg u)``. Router: ``z = u W_r`` over ``n_routed
+ n_zero`` outputs, ``p = softmax(z)``, the ``top_k`` largest (the published
correction bias is a buffer of zeros, used for the choice alone), weights
``scale * p_e``, not renormalised; an index e < n_routed adds ``w_e
Expert_e(u)`` (SwiGLU at ``expert_width``), an index e >= n_routed adds ``w_e
u`` (``zero_expert_type: identity``). MLA: ``c_q = N(u W_qa)``, ``q = c_q
W_qb * sqrt(d / q_rank)`` as heads of ``nope_dim + rope_dim``; ``[c_kv | k_r]
= u W_kva``, ``c_kv = N(c_kv) * sqrt(d / kv_rank)``, ``[k_n | v] = c_kv
W_kvb`` as heads of ``nope_dim | v_dim``; rotary positions (interleaved
pairs) on q's last ``rope_dim`` and on ``k_r``, which every head shares;
causal softmax attention at ``(nope_dim + rope_dim) ** -0.5``; ``W_o``.

Departures from the published code, each because of the cut the
configuration's file states (``reduced["serve.1"]``, ``stands_for``):

* **The held share.** Of the ``n_routed`` experts only ``held = (first,
  count)`` are applied: every held expert to *every* token, weighted by the
  router's (mostly zero) weight for it. What the absent experts would add is
  left out, and the partial sum goes on; the zero-compute part is whole.
* **The vocabulary slice.** Embedding and head have ``vocab_size`` rows of
  the slice; ids and logits are over the slice.
* **Blocks.** Attention runs ``HEAD_GROUP`` heads at a time and the experts
  one at a time, so that 8,192 tokens fit beside the weights.
* **Streamed weights.** ``last_logits`` is handed the seed's key
  (``reference_params``) and draws each layer's float32 weights where it uses
  them, by the program's rule (``draw_layer``: a copy of
  ``transformer.init_params`` for this kind of layer; a test compares them
  leaf for leaf). The whole float32 tree is 20.7 GB and fits no chip.
"""

from __future__ import annotations

import math
from typing import Any, Callable, Dict, NamedTuple

import jax
import jax.numpy as jnp

HEAD_GROUP = 4     # heads whose [S, S] scores are alive at once


class Layer(NamedTuple):
    """One layer's weights, the large ones behind a call that makes them
    where they are used: ``attn(i)`` and ``mlp(i)`` for the i-th of the two
    sub-blocks, ``expert(e)`` for the e-th *held* expert (e may be traced)."""
    attn: Callable[[int], Dict[str, Any]]
    mlp: Callable[[int], Dict[str, Any]]
    expert: Callable[[Any], Dict[str, Any]]
    router: Any
    ln_attn: Any
    ln_mlp: Any


def from_tree(tree: Dict[str, Any]) -> Layer:
    """A layer of the program's own parameter tree (one layer's leaves)."""
    def part(name):
        return lambda i: jax.tree.map(lambda p: p[i], tree[name])
    return Layer(part("attn"), part("mlp"), part("experts"), tree["router"],
                 tree["ln_attn"], tree["ln_mlp"])


# -- the draw: transformer.init_params for this kind of layer, copied ------------


def _normal(key, shape, fan_in):
    return jax.random.normal(key, shape, jnp.float32) / math.sqrt(fan_in)


def draw_attention(key, dims):
    d, h = dims["d_model"], dims["n_heads"]
    qk = dims["nope_dim"] + dims["rope_dim"]
    ks = jax.random.split(key, 5)
    return {
        "wq_a": _normal(ks[0], (d, dims["q_rank"]), d),
        "q_norm": jnp.ones((dims["q_rank"],), jnp.float32),
        # the up-projections at 1 / sqrt(d): the forward's sqrt(d / rank)
        # then leaves q, k and v at unit variance
        "wq_b": _normal(ks[1], (dims["q_rank"], h, qk), d),
        "wkv_a": _normal(ks[2], (d, dims["kv_rank"] + dims["rope_dim"]), d),
        "kv_norm": jnp.ones((dims["kv_rank"],), jnp.float32),
        "wkv_b": _normal(ks[3], (dims["kv_rank"], h,
                                 dims["nope_dim"] + dims["v_dim"]), d),
        "wo": _normal(ks[4], (h, dims["v_dim"], d), h * dims["v_dim"]),
    }


def draw_ffn(key, d, width):
    ks = jax.random.split(key, 3)
    return {"wi": _normal(ks[0], (d, width), d),
            "wg": _normal(ks[1], (d, width), d),
            "wo": _normal(ks[2], (width, d), width)}


def draw_layer(key, dims) -> Layer:
    """A layer from its key: the key split in four (attention, FFNs, router,
    experts), the first two split once a sub-block, an expert's key the
    fourth folded with its *published* index."""
    d = dims["d_model"]
    ks = jax.random.split(key, 4)
    attn_keys, mlp_keys = jax.random.split(ks[0], 2), jax.random.split(ks[1], 2)
    first = dims["held"][0]
    return Layer(
        attn=lambda i: draw_attention(attn_keys[i], dims),
        mlp=lambda i: draw_ffn(mlp_keys[i], d, dims["d_ff"]),
        expert=lambda e: draw_ffn(jax.random.fold_in(ks[3], first + e), d,
                                  dims["expert_width"]),
        router=_normal(ks[2], (d, dims["n_routed"] + dims["n_zero"]), d),
        ln_attn=jnp.ones((2, d), jnp.float32),
        ln_mlp=jnp.ones((2, d), jnp.float32))


def split_keys(key, dims):
    """``(embedding's key, head's key, [L] layer keys)`` as ``init_params``
    splits them."""
    k_embed, k_head, k_layers = jax.random.split(key, 3)
    return k_embed, k_head, jax.random.split(k_layers, dims["n_layers"])


def draw_tree(key, dims):
    """The whole float32 tree as ``init_params`` names it (small sizes: the
    tests' comparison with the program's draw)."""
    k_embed, k_head, layer_keys = split_keys(key, dims)

    def stacked(make, n):
        parts = [make(i) for i in range(n)]
        return jax.tree.map(lambda *p: jnp.stack(p), *parts)

    def one(k):
        layer = draw_layer(k, dims)
        return {"attn": stacked(layer.attn, 2), "mlp": stacked(layer.mlp, 2),
                "experts": stacked(layer.expert, dims["held"][1]),
                "router": layer.router, "ln_attn": layer.ln_attn,
                "ln_mlp": layer.ln_mlp}

    layers = [one(k) for k in layer_keys]
    return {"embed": embedding(k_embed, dims),
            "blocks": {"shortcut": jax.tree.map(lambda *p: jnp.stack(p),
                                                *layers)},
            "ln_f": jnp.ones((dims["d_model"],), jnp.float32),
            "lm_head": head_weight(k_head, dims)}


def embedding(k_embed, dims):
    return jax.random.normal(k_embed, (dims["vocab_size"], dims["d_model"]),
                             jnp.float32) * 0.02


def head_weight(k_head, dims):
    return _normal(k_head, (dims["d_model"], dims["vocab_size"]),
                   dims["d_model"])


# -- the forward, one sequence [S, d] ----------------------------------------------


def rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * w


def rope_interleaved(x, theta):
    """x [S, H, D]: the neighbouring pairs (2i, 2i + 1) rotated by position
    times ``theta ** (-i / (D / 2))``."""
    S, _, D = x.shape
    half = D // 2
    freqs = jnp.exp(-jnp.arange(half, dtype=jnp.float32)
                    * (math.log(theta) / half))
    angles = jnp.arange(S, dtype=jnp.float32)[:, None, None] * freqs
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(x.shape)


def causal_attention(q, k, v):
    """q, k [S, H, D], v [S, H, Dv] -> [S, H, Dv]: softmax(q k^T / sqrt(D))
    v under the causal mask, ``HEAD_GROUP`` heads at a time."""
    S, H, D = q.shape
    group = math.gcd(H, HEAD_GROUP)
    mask = jnp.tril(jnp.ones((S, S), bool))

    def heads(qkv):
        q, k, v = qkv                                   # [g, S, .]
        s = jnp.einsum("hqd,hkd->hqk", q, k) / math.sqrt(D)
        p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,hkd->hqd", p, v)

    def grouped(x):
        return x.transpose(1, 0, 2).reshape(H // group, group, S, -1)

    o = jax.lax.map(heads, (grouped(q), grouped(k), grouped(v)))
    return o.reshape(H, S, -1).transpose(1, 0, 2)


def latent_attention(p, h, dims):
    """MLA of the normed states h [S, d]."""
    d, nope, r = dims["d_model"], dims["nope_dim"], dims["kv_rank"]
    eps, theta = dims["rms_norm_eps"], dims["rope_theta"]
    c_q = rmsnorm(h @ p["wq_a"], p["q_norm"], eps)
    q = jnp.einsum("sr,rhk->shk", c_q, p["wq_b"]) \
        * math.sqrt(d / dims["q_rank"])
    kv = h @ p["wkv_a"]
    c_kv = rmsnorm(kv[:, :r], p["kv_norm"], eps) * math.sqrt(d / r)
    k_v = jnp.einsum("sr,rhk->shk", c_kv, p["wkv_b"])
    k_r = rope_interleaved(kv[:, None, r:], theta)          # one shared head
    q = jnp.concatenate([q[..., :nope],
                         rope_interleaved(q[..., nope:], theta)], axis=-1)
    k = jnp.concatenate([k_v[..., :nope],
                         jnp.broadcast_to(k_r, (*k_v.shape[:2],
                                                dims["rope_dim"]))], axis=-1)
    o = causal_attention(q, k, k_v[..., nope:])
    return jnp.einsum("shk,hkd->sd", o, p["wo"])


def ffn(p, x):
    return (jax.nn.silu(x @ p["wi"]) * (x @ p["wg"])) @ p["wo"]


def route(u, router, dims):
    """Each token's chosen experts [S, k] and their weights [S, k]."""
    p = jax.nn.softmax(u @ router, axis=-1)
    p, idx = jax.lax.top_k(p, dims["top_k"])
    return idx, dims["scale"] * p


def experts_part(u, layer: Layer, dims):
    """The held experts' part and the zero-compute experts' part of the
    mixture for tokens u [S, d]: every held expert applied to every token
    and weighted by what the router gave it there (0 for most)."""
    first, count = dims["held"]
    idx, w = route(u, layer.router, dims)
    zero = jnp.sum(jnp.where(idx >= dims["n_routed"], w, 0.0), axis=-1)

    def add(e, out):
        mine = jnp.sum(jnp.where(idx == first + e, w, 0.0), axis=-1)
        return out + mine[:, None] * ffn(layer.expert(e), u)

    return jax.lax.fori_loop(0, count, add, zero[:, None] * u)


def block(layer: Layer, x, dims):
    """One layer on one sequence x [S, d]."""
    eps = dims["rms_norm_eps"]
    h = x + latent_attention(layer.attn(0), rmsnorm(x, layer.ln_attn[0], eps),
                             dims)
    u = rmsnorm(h, layer.ln_mlp[0], eps)
    s = experts_part(u, layer, dims)
    h = h + ffn(layer.mlp(0), u)
    h = h + latent_attention(layer.attn(1), rmsnorm(h, layer.ln_attn[1], eps),
                             dims)
    return h + ffn(layer.mlp(1), rmsnorm(h, layer.ln_mlp[1], eps)) + s


def last_logits(key, tokens, dims):
    """tokens [B, S] -> float32 logits [B, V] at the last position, every
    weight drawn from ``key`` where it is used. The layers are a
    ``fori_loop``, so the compiled program holds one layer's weights
    whatever the depth, and the sequences of a batch run one after another."""
    with jax.default_matmul_precision("highest"):
        k_embed, k_head, layer_keys = split_keys(key, dims)

        def sequence(row):
            x = embedding(k_embed, dims)[row]
            x = jax.lax.fori_loop(
                0, dims["n_layers"],
                lambda i, x: block(draw_layer(layer_keys[i], dims), x, dims),
                x)
            x = rmsnorm(x[-1], jnp.ones((dims["d_model"],)),
                        dims["rms_norm_eps"])
            return x @ head_weight(k_head, dims)

        return jax.lax.map(sequence, tokens)


def _tree_states(params, row, dims):
    """Every position's pre-final-norm state of one sequence, on a whole
    parameter tree."""
    x = params["embed"][row]
    for i in range(dims["n_layers"]):
        x = block(from_tree(jax.tree.map(lambda p: p[i],
                                         params["blocks"]["shortcut"])), x,
                  dims)
    return x


def tree_last_logits(params, tokens, dims):
    """The same forward on a whole parameter tree (``init_params``'s, small
    sizes): what the tests plant their faults in."""
    with jax.default_matmul_precision("highest"):
        def sequence(row):
            x = _tree_states(params, row, dims)[-1]
            return rmsnorm(x, params["ln_f"],
                           dims["rms_norm_eps"]) @ params["lm_head"]

        return jax.vmap(sequence)(tokens)


def loss_and_grad_norm(params, tokens, dims):
    """Mean next-token cross entropy of ``tokens`` [B, S + 1] on a whole
    tree and the norm of its gradient (no cell trains this configuration:
    the adapter's contract asks for the name, and the CPU tests use it on
    the reference alone)."""
    with jax.default_matmul_precision("highest"):
        def loss(params):
            def sequence(row):
                x = _tree_states(params, row[:-1], dims)
                logp = jax.nn.log_softmax(
                    rmsnorm(x, params["ln_f"], dims["rms_norm_eps"])
                    @ params["lm_head"], axis=-1)
                return -jnp.mean(jnp.take_along_axis(
                    logp, row[1:, None], axis=-1))
            return jnp.mean(jax.vmap(sequence)(tokens))

        value, grads = jax.value_and_grad(loss)(params)
        norm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
        return value, norm
