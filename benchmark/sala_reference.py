"""The plain reference of MiniCPM-SALA's decoder (``minicpm_sala``): a stack
of two kinds of layer, block-sparse softmax attention without positions
(``minicpm4``, InfLLM-V2) and Lightning linear attention with rotary
positions (``lightning-attn``), in straightforward ``jax.numpy`` and float32.
No kernel, no chunked recurrence, no cache, nothing imported from
``ray_tpu``: the linear layer is the masked product ``(Q K^T * D) V``, the
sparse layer a whole row of scores with the selection applied as a mask.
Loops are ``lax.scan`` over the layers (a switch on the layer's kind) and
``lax.map`` over groups of heads, blocks of queries and blocks of rows, each
taking its layer's weights out of the stacked parameters where it uses them,
so that at 32,768 tokens the intermediates fit on the chip beside 11 GB of
float32 weights and no second copy of a weight is made.

The equations (one sequence, tokens ``[S]``; ``N(x; g) = x / sqrt(mean(x^2)
+ rms_norm_eps) * g``; ``L`` = 32 the *published* depth, also where fewer
layers are run; ``d`` = 128; ``c = scale_depth / sqrt(L)``)::

    x = scale_emb * E[tokens]
    every layer l (its published index):
        y = N(x; g1);  x = x + c * Mixer_l(y)
        y = N(x; g2);  x = x + c * (silu(y Wgate) * (y Wup)) Wdown
    logits = (N(x; g_f) / (hidden_size / dim_model_base)) W_head

    lightning-attn, head h of 32:
        q, k = rotary(N(y Wq; gq)), rotary(N(y Wk; gk));  v = y Wv      # norms a head, weight of d
        lam = exp(-2^(-8 (h + 1) / 32) * (1 - l / (L - 1) + 1e-5))
        o_t = sum_{u <= t} lam^(t - u) (q_t . k_u) v_u / sqrt(d)         # = q_t S_t / sqrt(d), no normaliser
        out = (N(concat_h o; g_o) * sigmoid(y Wg)) Wo

    minicpm4, query head h of 32, K/V head h // 16, no positions:
        q, k = N(y Wq; gq), N(y Wk; gk);  v = y Wv
        S <= dense_len:  o_t = softmax_{u <= t}(q_t . k_u / sqrt(d)) v
        S >  dense_len:  Kc_j  = mean(k[16 j : 16 j + 32])
                         P[t,j] = sum over the 16 heads of the K/V head of softmax_j(q_t . Kc_j / sqrt(d)), over 16 j + 31 <= t
                         score[t,b] = max_{j = 4b-1 .. 4b+3} P[t,j]       # block b = tokens [64 b, 64 b + 64)
                         kept[t] = the 64 best blocks among those with 64 b <= t, block 0 and the blocks holding
                                   (t - 2048, t] first; ties at the 64th place are all kept
                         o_t = softmax over the u <= t of kept blocks (q_t . k_u / sqrt(d)) v
        out = (concat_h o * sigmoid(y Wg)) Wo

What the published ``config.json`` does not say (the decay, the constants
32 / 16 / 64 / 64 / 1 / 2048 / 8192, the order of norm, rotation and gate)
is listed under ``assumed`` in ``benchmark/configs/minicpm-sala.json``.

It takes the program's parameter tree as data: ``embed`` [V, D];
``blocks["sparse"]`` and ``blocks["linear"]``, each stacked over that kind's
layers in their order, with ``attn.wq`` [D, h, d], ``attn.wk`` / ``wv`` [D,
kv, d], ``attn.wg`` [D, h, d] (the gate), ``attn.wo`` [h, d, D],
``attn.q_norm`` / ``k_norm`` [d], for a linear layer ``attn.o_norm`` [h d],
``mlp.wi`` gate, ``mlp.wg`` up, ``mlp.wo`` down, ``ln1``, ``ln2``; ``ln_f``;
``lm_head`` [D, V].

On a TPU a float32 product runs in lower precision unless asked otherwise,
so every entry point runs under ``jax.default_matmul_precision("highest")``.
"""

from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

Params = Dict[str, Any]
TREE_KEY = {"minicpm4": "sparse", "lightning-attn": "linear"}
QUERY_BLOCK = 64       # queries whose whole score rows are alive at once
HEAD_GROUP = 8         # heads whose q, k, v of every position are alive at once
MLP_ROWS = 2048        # rows of a projection or of the MLP computed at once


def _rmsnorm(x, weight, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                        + eps) * weight


def _rotary(x, theta):
    """x: [S, heads, d]. Pair i of a head is (x[i], x[i + d/2]), turned by
    position * theta^(-2i/d)."""
    length, d = x.shape[0], x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = jnp.arange(length, dtype=jnp.float32)[:, None] * inv_freq
    cos = jnp.concatenate([jnp.cos(angle), jnp.cos(angle)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(angle), jnp.sin(angle)], -1)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def _block(length: int, block: int) -> int:
    """The largest block of at most ``block`` rows that divides
    ``length``."""
    return max(b for b in range(1, min(block, length) + 1)
               if length % b == 0)


def _over(fn, firsts, i):
    """``fn(first, i)`` for each ``first`` in turn, the results stacked.
    The layer's index ``i`` travels with the loop's own variable, so that a
    weight is taken out of its stack inside the loop that uses it: taken
    outside, a copy of the layer's weights would live beside the stack."""
    return jax.lax.map(lambda a: fn(a[0], a[1]),
                       (firsts, jnp.full(firsts.shape, i)))


def _over_blocks(fn, length: int, block: int, i):
    """``fn(first row, i)`` for each block of ``block`` rows in turn; the
    results joined along the first axis."""
    out = _over(fn, jnp.arange(0, length, block), i)
    return out.reshape((length,) + out.shape[2:])


def _add_over_blocks(x, fn, block: int, i, c: float):
    """``x[rows] + c * fn(x[rows], first row, i)`` for each block of
    ``block`` rows of x in turn, written over x's rows: a second whole x
    would not fit beside the weights."""
    def rows(x, a):
        start, i = a
        mine = _rows(x, start, block)
        mine = mine + c * fn(mine, start, i)
        return jax.lax.dynamic_update_slice_in_dim(x, mine, start, 0), None

    firsts = jnp.arange(0, x.shape[0], block)
    return jax.lax.scan(rows, x, (firsts, jnp.full(firsts.shape, i)))[0]


def _rows(a, first, block: int, axis: int = 0):
    return jax.lax.dynamic_slice_in_dim(a, first, block, axis)


def _layer(stacked, i, first=0, size=None, axis=1):
    """Layer ``i`` of a kind's stacked parameters [n, ...]; with ``size``,
    only ``[first, first + size)`` along ``axis`` of the layer's array (one
    slice: a float32 product at the highest precision splits its operands,
    so whatever is sliced out is copied)."""
    if size is None:
        return jax.lax.dynamic_index_in_dim(stacked, i, 0, keepdims=False)
    starts = [i] + [0] * (stacked.ndim - 1)
    sizes = [1] + list(stacked.shape[1:])
    starts[axis + 1], sizes[axis + 1] = first, size
    return jax.lax.dynamic_slice(stacked, starts, sizes)[0]


def _columns(w):
    """Stacked [n, D, heads, d] as matrices [n, D, heads * d]."""
    return w.reshape(w.shape[:2] + (-1,))


def _joined(w):
    """Stacked [n, heads, d, D] as matrices [n, heads * d, D]."""
    return w.reshape(w.shape[0], -1, w.shape[-1])


def _project(x, g, w, first, size, i, eps):
    """Heads [first, first + size) of N(x; g_i) w_i, for x [S, D] and
    stacked g [n, D], w [n, D, heads, d], a block of rows at a time: [S,
    size, d]. The product is taken over all the heads' columns and the
    wanted ones cut out of it: cut out of the weight they would not lie
    together in memory, and the compiler would lay the whole stack out
    anew beside itself."""
    block, d = _block(x.shape[0], MLP_ROWS), w.shape[-1]

    def rows(start, i):
        y = _rmsnorm(_rows(x, start, block), _layer(g, i), eps)
        mine = _rows(y @ _layer(_columns(w), i), first * d, size * d, axis=1)
        return mine.reshape(block, size, d)

    return _over_blocks(rows, x.shape[0], block, i)


def _leave(p, i, x, o, scale, eps, normed: bool):
    """x + scale * ((N(o; g_o) if ``normed`` else o) * sigmoid(N(x; g1) Wg))
    Wo, a block of rows at a time, for the heads' outputs o [groups, S,
    heads a group, d] in the order of the heads."""
    a, block = p["attn"], _block(x.shape[0], MLP_ROWS)

    def rows(x_rows, start, i):
        y = _rmsnorm(x_rows, _layer(p["ln1"], i), eps)
        gate = jax.nn.sigmoid(y @ _layer(_columns(a["wg"]), i))
        mine = jnp.moveaxis(_rows(o, start, block, axis=1), 0, 1)
        mine = mine.reshape(gate.shape)
        if normed:
            mine = _rmsnorm(mine, _layer(a["o_norm"], i), eps)
        return (mine * gate) @ _layer(_joined(a["wo"]), i)

    return _add_over_blocks(x, rows, block, i, scale)


def decay_rates(layer, dims):
    """Each head's decay rate a token in the layer of published index
    ``layer``: ``2^(-8 (h + 1) / heads) * (1 - layer / (L - 1) + 1e-5)``."""
    heads = dims["n_heads"]
    slopes = 2.0 ** (-8.0 * np.arange(1, heads + 1) / heads)
    return jnp.asarray(slopes, jnp.float32) * (
        1.0 - layer / (dims["published_layers"] - 1) + 1e-5)


def decayed_attention(q, k, v, rate):
    """q, k, v [S, heads, d], each head's decay rate a token [heads] ->
    ``o_t = sum_{u <= t} exp(-rate (t - u)) (q_t . k_u) v_u / sqrt(d)``, [S,
    heads, d]: the masked product, a block of queries against every
    position at a time."""
    length, d = q.shape[0], q.shape[-1]
    queries = _block(length, QUERY_BLOCK)

    def rows(start, _):
        t = start + jnp.arange(queries)[:, None]
        back = t - jnp.arange(length)[None, :]                # t - u
        decay = jnp.where(
            back >= 0, jnp.exp(-rate[:, None, None]
                               * jnp.maximum(back, 0)), 0.0)
        scores = jnp.einsum("qhk,uhk->hqu", _rows(q, start, queries),
                            k) * decay
        return jnp.einsum("hqu,uhk->qhk", scores, v) / math.sqrt(d)

    return _over_blocks(rows, length, queries, 0)             # [S, heads, d]


def _lightning(p, i, x, layer, scale, dims):
    """x [S, D] -> x + scale * the mixer's output, [S, D], from layer ``i`` of the
    linear layers' stacked parameters ``p``; ``layer`` is its published
    index."""
    a, eps, heads = p["attn"], dims["rms_norm_eps"], dims["n_heads"]
    rates = decay_rates(layer, dims)
    size = _block(heads, HEAD_GROUP)

    def group(first, i):
        """Heads [first, first + size): q, k, v of every position, then
        blocks of queries against all of them."""
        q = _rotary(_rmsnorm(_project(x, p["ln1"], a["wq"], first, size, i,
                                      eps), _layer(a["q_norm"], i), eps),
                    dims["rope_theta"])
        k = _rotary(_rmsnorm(_project(x, p["ln1"], a["wk"], first, size, i,
                                      eps), _layer(a["k_norm"], i), eps),
                    dims["rope_theta"])
        v = _project(x, p["ln1"], a["wv"], first, size, i, eps)
        return decayed_attention(q, k, v, _rows(rates, first, size))

    o = _over(group, jnp.arange(0, heads, size), i)   # [groups, S, size, d]
    return _leave(p, i, x, o, scale, eps, normed=True)


def _kept_blocks(q_rows, first, pooled, length, dims):
    """q_rows [Q, G, d] of one K/V head from position ``first``, pooled
    keys [n, d] -> which blocks each query keeps, bool [Q, blocks]."""
    c, d = dims["sparse_config"], q_rows.shape[-1]
    size, stride = c["block_size"], c["kernel_stride"]
    n, blocks = pooled.shape[0], length // c["block_size"]
    t = first + jnp.arange(q_rows.shape[0])[:, None]
    s = jnp.einsum("qgk,nk->gqn", q_rows, pooled) / math.sqrt(d)
    whole = jnp.arange(n)[None, :] * stride + c["kernel_size"] - 1 <= t
    s = jnp.where(whole, s, -jnp.inf)
    top = jnp.max(s, axis=-1, keepdims=True)
    e = jnp.where(whole, jnp.exp(s - jnp.where(jnp.isfinite(top), top, 0.0)),
                  0.0)
    total = jnp.sum(e, axis=-1, keepdims=True)
    probs = jnp.sum(e / jnp.where(total > 0, total, 1.0), axis=0)  # [Q, n]
    # the pooled windows [stride j, stride j + kernel_size) that overlap
    # block b = [size b, size b + size)
    j = np.arange(n)[None, :] * stride
    b = np.arange(blocks)[:, None] * size
    overlap = (j < b + size) & (j + c["kernel_size"] > b)     # [blocks, n]
    width = int(overlap.sum(1).max())
    index = np.stack([np.pad(np.nonzero(row)[0], (0, width - row.sum()),
                             constant_values=n) for row in overlap])
    padded = jnp.concatenate([probs, jnp.zeros((probs.shape[0], 1))], -1)
    score = jnp.max(padded[:, index], axis=-1)                # [Q, blocks]
    begins = jnp.arange(blocks)[None, :] * size
    visible = begins <= t
    always = visible & ((begins < c["init_blocks"] * size)
                        | (begins + size - 1 > t - c["window_size"]))
    value = jnp.where(always, jnp.inf, jnp.where(visible, score, -jnp.inf))
    if blocks <= c["topk"]:
        return visible
    last = jax.lax.top_k(value, c["topk"])[0][:, -1:]
    return (value >= last) & visible


def pooled_keys(k, c):
    """k [S, d] -> the means of its windows of ``kernel_size`` tokens, one
    every ``kernel_stride``: [n, d]."""
    windows = (k.shape[0] - c["kernel_size"]) // c["kernel_stride"] + 1
    at = (np.arange(windows)[:, None] * c["kernel_stride"]
          + np.arange(c["kernel_size"])[None, :])
    return jnp.mean(k[at], axis=1)


def selected_attention(q, k, v, dims, pooled=None):
    """The query heads of one K/V head, q [S, G, d], over its k and v [S,
    d] -> [S, G, d]: causal softmax attention, over the blocks each query
    keeps where ``pooled`` (the K/V head's pooled keys) is given, a block of
    queries against every position at a time."""
    length, d = q.shape[0], q.shape[-1]
    queries = _block(length, QUERY_BLOCK)

    def rows(start, _):
        mine = _rows(q, start, queries)                       # [Q, G, d]
        t = start + jnp.arange(queries)[:, None]
        keep = jnp.arange(length)[None, :] <= t               # [Q, S]
        if pooled is not None:
            kept = _kept_blocks(mine, start, pooled, length, dims)
            keep = keep & jnp.repeat(
                kept, dims["sparse_config"]["block_size"], axis=1)
        s = jnp.einsum("qgk,uk->gqu", mine, k) / math.sqrt(d)
        s = jnp.where(keep[None], s, -jnp.inf)
        return jnp.einsum("gqu,uk->qgk", jax.nn.softmax(s, axis=-1), v)

    return _over_blocks(rows, length, queries, 0)             # [S, G, d]


def _minicpm4(p, i, x, scale, dims):
    """x [S, D] -> x + scale * the mixer's output, [S, D], from layer ``i`` of the
    sparse layers' stacked parameters ``p``."""
    a, eps, c = p["attn"], dims["rms_norm_eps"], dims["sparse_config"]
    kv = dims["n_kv_heads"]
    group = dims["n_heads"] // kv

    def head(kv_head, i):
        """The ``group`` query heads of one K/V head."""
        q = _rmsnorm(_project(x, p["ln1"], a["wq"], kv_head * group, group,
                              i, eps), _layer(a["q_norm"], i), eps)
        k = _rmsnorm(_project(x, p["ln1"], a["wk"], kv_head, 1, i, eps),
                     _layer(a["k_norm"], i), eps)[:, 0]       # [S, d]
        v = _project(x, p["ln1"], a["wv"], kv_head, 1, i, eps)[:, 0]
        return selected_attention(
            q, k, v, dims,
            pooled_keys(k, c) if x.shape[0] > c["dense_len"] else None)

    o = _over(head, jnp.arange(kv), i)                        # [kv, S, G, d]
    return _leave(p, i, x, o, scale, eps, normed=False)


def _mlp(p, i, x, scale, dims):
    """x [S, D] -> x + scale * SwiGLU(N(x; g2)), [S, D]."""
    block = _block(x.shape[0], MLP_ROWS)

    def rows(x_rows, start, i):
        y = _rmsnorm(x_rows, _layer(p["ln2"], i), dims["rms_norm_eps"])
        return (jax.nn.silu(y @ _layer(p["mlp"]["wi"], i))
                * (y @ _layer(p["mlp"]["wg"], i))) @ _layer(p["mlp"]["wo"], i)

    return _add_over_blocks(x, rows, block, i, scale)


def final_states(params: Params, tokens, dims) -> jax.Array:
    """tokens [S] -> the stack's output before the final norm, [S, D]. The
    loop over layers is a ``lax.scan`` that switches on the layer's kind and
    indexes the kind's stacked parameters in place."""
    c = dims["scale_depth"] / math.sqrt(dims["published_layers"])
    blocks = params["blocks"]
    kinds = [TREE_KEY[m] for m in dims["mixer_types"]]
    within = [kinds[:n].count(kind) for n, kind in enumerate(kinds)]

    def linear(x, i, layer):
        x = _lightning(blocks["linear"], i, x, layer, c, dims)
        return _mlp(blocks["linear"], i, x, c, dims)

    def sparse(x, i, layer):
        x = _minicpm4(blocks["sparse"], i, x, c, dims)
        return _mlp(blocks["sparse"], i, x, c, dims)

    def one(x, layer):
        is_linear, i, published = layer
        if len(set(kinds)) == 1:
            return (linear if kinds[0] == "linear" else sparse)(
                x, i, published), None
        return jax.lax.cond(is_linear, linear, sparse, x, i, published), None

    x = dims["scale_emb"] * params["embed"][tokens]
    x, _ = jax.lax.scan(one, x, (
        jnp.array([kind == "linear" for kind in kinds]),
        jnp.array(within), jnp.array(dims["layer_ids"], jnp.float32)))
    return x


def _logits(params: Params, x, dims):
    width = dims["d_model"] / dims["dim_model_base"]
    return (_rmsnorm(x, params["ln_f"], dims["rms_norm_eps"]) / width
            ) @ params["lm_head"]


def _float32(params: Params) -> Params:
    return jax.tree.map(lambda p: p.astype(jnp.float32), params)


def last_logits(params: Params, tokens, dims) -> jax.Array:
    """Tokens [B, S] -> float32 logits [B, V] at the last position, a
    sequence at a time."""
    with jax.default_matmul_precision("highest"):
        params = _float32(params)
        return jax.lax.map(
            lambda row: _logits(params, final_states(params, row, dims)[-1],
                                dims), tokens)


def _loss(params: Params, tokens, dims):
    def one(row):
        logp = jax.nn.log_softmax(
            _logits(params, final_states(params, row[:-1], dims), dims), -1)
        return -jnp.mean(jnp.take_along_axis(logp, row[1:, None], axis=-1))

    return jnp.mean(jax.lax.map(one, tokens))


def loss_and_grad_norm(params: Params, tokens, dims):
    """The mean next-token cross entropy of tokens [B, S + 1] and the global
    L2 norm of its gradient over all parameters: the same forward under
    ``jax.grad`` (no cell trains this configuration; small sizes only)."""
    with jax.default_matmul_precision("highest"):
        value, grads = jax.value_and_grad(_loss)(_float32(params), tokens,
                                                 dims)
        squares = sum(jnp.sum(g * g) for g in jax.tree.leaves(grads))
        return value, jnp.sqrt(squares)
