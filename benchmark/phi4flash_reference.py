"""The plain reference of Phi-4-mini-flash-reasoning's decoder (``phi4flash``,
SambaY: ``microsoft/Phi-4-mini-flash-reasoning``): the forward in
``jax.numpy``, float32, ``jax.default_matmul_precision("highest")``, a
sequential scan, two explicit softmaxes, no kernel, no chunks, no cache, no
slots. Nothing of the program is imported.

Every block (``LN`` a LayerNorm with a weight and a bias, eps
``layer_norm_eps``)::

    h = x + Mixer_l(LN1(x));  y = h + W_down(silu(g) * u),  [g | u] = LN2(h) W

``Mixer_l`` by the layer's index ``l`` (``layer_types``: from ``mb_per_layer``
= 2 and ``L / 2``):

* ``mamba`` (``l`` even, ``l <= L / 2``; Mamba-1, arXiv:2312.00752): ``[x | z]
  = u W_in``; ``x = silu(conv(x) + b)``, depthwise, causal, ``d_conv`` taps;
  ``[r | B | C] = x W_x`` at ``dt_rank | d_state | d_state``; ``dt =
  softplus(r W_dt + b_dt)`` a channel; ``A = -exp(A_log)`` [d_inner,
  d_state]; ``S_t = exp(dt_t A) S_{t-1} + (dt_t x_t) B_t^T``, ``y_t = S_t C_t
  + D x_t``, **a ``lax.scan`` over the tokens**; out ``= (y * silu(z))
  W_out``. The last such layer's ``y`` (with ``D x``, before the gate) is the
  memory ``m``.
* ``swa`` (``l`` odd, ``l < L / 2``): differential attention (arXiv:2410.05258)
  in which position ``t`` sees ``t - window < j <= t``; ``full`` (``l = L / 2
  + 1``): the same over everything, **and its K and V are kept**; ``cross``
  (``l`` odd beyond): its own ``W_q``, ``W_out``, lambdas and sub-norm over the
  ``full`` layer's K and V (YOCO, arXiv:2405.05254). No positions anywhere.
  With q in ``H`` heads and k, v in ``G``: ``(q1, q2)_p = (q_2p, q_2p+1)``,
  ``(k1, k2)_g = (k_2g, k_2g+1)``, ``v_g = [v_2g | v_2g+1]``, pair ``p`` reads
  group ``p // (H / G)``; ``A_i = softmax(q_i k_i^T / sqrt(D) + mask)``;
  ``lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init(l)``,
  ``lambda_init(l) = 0.8 - 0.6 exp(-0.3 l)``; ``o_p = RMSNorm_2D((A_1 - lambda
  A_2) v_g) * (1 - lambda_init(l))``; out ``= [o_0 | .. ] W_out + b``. The q,
  k, v projection has a bias too.
* ``gmu`` (``l`` even beyond ``L / 2``; SambaY, arXiv:2507.06607): out ``= (m *
  silu(u W_in)) W_out``, ``m`` at the same position.

Then a final ``LN`` and the tied head.

**Streamed weights.** ``logits_and_state_from`` is handed the seed's key and
draws each layer's float32 weights where it uses them, by the program's rule
(``draw_layer``: a copy of ``transformer.init_params`` for these kinds of
layer; a test compares them leaf for leaf). The layers are one ``lax.scan``
over their keys whose body holds the kinds' mixers under a ``lax.switch`` and
the FFN after it: one layer's weights (0.5 GB) are alive at a time of the
tree's 15.4 GB. The memory and the kept K and V ride the scan's carry.

``FAULTS`` names what a test may leave out of the mathematics, to see that a
comparison notices.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

MAMBA, SWA, FULL, CROSS, GMU = TYPES = ("mamba", "swa", "full", "cross",
                                        "gmu")
FAULTS = ("no_subln", "no_init_scale", "no_skip", "cross_own_kv")


def layer_types(n_layers: int, mb_per_layer: int = 2) -> Tuple[str, ...]:
    """Each layer's mixer, as ``phi4flash`` lays them out: every
    ``mb_per_layer``-th layer a scan up to the middle and a gated memory unit
    beyond it; between them attention, through the window up to the middle,
    the one full layer right after it, cross layers beyond."""
    half = n_layers // 2
    return tuple(
        (MAMBA if l <= half else GMU) if l % mb_per_layer == 0 else
        SWA if l < half + 1 else FULL if l == half + 1 else CROSS
        for l in range(n_layers))


def lambda_init(l):
    return 0.8 - 0.6 * jnp.exp(-0.3 * jnp.asarray(l, jnp.float32))


# -- the draw: transformer.init_params for these kinds of layer, copied ------------


def _normal(key, shape, fan_in):
    return jax.random.normal(key, shape, jnp.float32) / math.sqrt(fan_in)


def _on_grid(v):
    """Rounded to bfloat16's grid (the serving tree's cast leaves it so)."""
    return v.astype(jnp.bfloat16).astype(jnp.float32)


def _small(key, shape, scale=0.02):
    return _on_grid(scale * jax.random.normal(key, shape, jnp.float32))


def draw_mamba(key, dims):
    d, inner, state, rank, taps = (dims["d_model"], dims["d_inner"],
                                   dims["d_state"], dims["dt_rank"],
                                   dims["conv_width"])
    ks = jax.random.split(key, 8)
    step = jnp.exp(jax.random.uniform(ks[5], (inner,), jnp.float32,
                                      math.log(1e-3), math.log(1e-1)))
    return {"w_in": _normal(ks[0], (d, 2 * inner), d),
            "conv": _normal(ks[1], (inner, taps), taps),
            "conv_bias": _small(ks[2], (inner,)),
            "w_x": _normal(ks[3], (inner, rank + 2 * state), inner),
            "w_dt": _normal(ks[4], (rank, inner), rank),
            "dt_bias": _on_grid(step + jnp.log(-jnp.expm1(-step))),
            "a_log": _on_grid(jnp.log(jax.random.uniform(
                ks[6], (inner, state), jnp.float32, 1.0, 16.0))),
            "d_skip": jnp.ones((inner,), jnp.float32),
            "w_out": _normal(ks[7], (inner, d), inner)}


def draw_attention(key, dims, q_only: bool):
    d, h, kvh, hd = (dims["d_model"], dims["n_heads"], dims["n_kv_heads"],
                     dims["head_dim"])
    ks = jax.random.split(key, 8)
    width = h * hd if q_only else (h + 2 * kvh) * hd
    name = "q" if q_only else "qkv"
    return {"w" + name: _normal(ks[0], (d, width), d),
            "b" + name: _small(ks[1], (width,)),
            "wo": _normal(ks[2], (h * hd, d), h * hd),
            "bo": _small(ks[3], (d,)),
            "lambda_q1": _small(ks[4], (hd,), 0.1),
            "lambda_k1": _small(ks[5], (hd,), 0.1),
            "lambda_q2": _small(ks[6], (hd,), 0.1),
            "lambda_k2": _small(ks[7], (hd,), 0.1),
            "subln": jnp.ones((2 * hd,), jnp.float32)}


def draw_gmu(key, dims):
    d, inner = dims["d_model"], dims["d_inner"]
    ks = jax.random.split(key)
    return {"w_in": _normal(ks[0], (d, inner), d),
            "w_out": _normal(ks[1], (inner, d), inner)}


def draw_ffn(key, d, width):
    ks = jax.random.split(key, 3)
    return {"wi": _normal(ks[0], (d, width), d),
            "wg": _normal(ks[1], (d, width), d),
            "wo": _normal(ks[2], (width, d), width)}


def draw_feed(key, dims) -> Dict[str, Any]:
    """A layer's FFN and the bias of the LayerNorm before it."""
    d = dims["d_model"]
    _, k_ffn = jax.random.split(key)
    _, k_b2 = jax.random.split(jax.random.fold_in(key, 2))
    return {"mlp": draw_ffn(k_ffn, d, dims["d_ff"]),
            "ln2": jnp.ones((d,), jnp.float32), "ln2_b": _small(k_b2, (d,))}


def draw_layer(key, kind: str, dims, feed: bool = True) -> Dict[str, Any]:
    """A layer of ``kind`` from its key: one half for the mixer, one for the
    FFN, the key folded with 2 for the two LayerNorms' biases (without
    ``feed`` the mixer and its norm alone)."""
    d = dims["d_model"]
    k_mixer, _ = jax.random.split(key)
    k_b1, _ = jax.random.split(jax.random.fold_in(key, 2))
    mixer = (draw_mamba(k_mixer, dims) if kind == MAMBA else
             draw_gmu(k_mixer, dims) if kind == GMU else
             draw_attention(k_mixer, dims, kind == CROSS))
    return {"mixer": mixer, "ln1": jnp.ones((d,), jnp.float32),
            "ln1_b": _small(k_b1, (d,)),
            **(draw_feed(key, dims) if feed else {})}


def split_keys(key, dims):
    """``(embedding's key, [L] layer keys)`` as ``init_params`` splits them
    (the head's key is drawn and unused: the head is tied)."""
    k_embed, _, k_layers = jax.random.split(key, 3)
    return k_embed, jax.random.split(k_layers, dims["n_layers"])


def embedding(k_embed, dims):
    return jax.random.normal(k_embed, (dims["vocab_size"], dims["d_model"]),
                             jnp.float32) * 0.02


def final_norm(key, dims):
    return (jnp.ones((dims["d_model"],), jnp.float32),
            _small(jax.random.fold_in(key, 4), (dims["d_model"],)))


# the program's names: a kind's stacked tree, and the mixer's sub-tree in it
TREE = {MAMBA: ("mamba1", "mamba1"), SWA: ("diff_window", "diff"),
        FULL: ("diff_global", "diff"), CROSS: ("diff_cross", "diff"),
        GMU: ("gmu", "gmu")}


def draw_tree(key, dims):
    """The whole float32 tree as ``init_params`` names it (small sizes: the
    tests' comparison with the program's draw)."""
    k_embed, layer_keys = split_keys(key, dims)
    stacks: Dict[str, list] = {}
    for kind, k in zip(dims["layer_types"], layer_keys):
        layer = draw_layer(k, kind, dims)
        stack, mixer = TREE[kind]
        stacks.setdefault(stack, []).append(
            {mixer: layer.pop("mixer"), **layer})
    ln_f, ln_f_b = final_norm(key, dims)
    return {"embed": embedding(k_embed, dims),
            "blocks": {kind: jax.tree.map(lambda *p: jnp.stack(p), *trees)
                       for kind, trees in stacks.items()},
            "ln_f": ln_f, "ln_f_b": ln_f_b}


def from_tree(params, i: int, dims) -> Dict[str, Any]:
    """Layer ``i`` of the program's own parameter tree: the ``j``-th of its
    kind's stack, ``j`` the layers of that kind before it."""
    kind = dims["layer_types"][i]
    stack, mixer = TREE[kind]
    j = sum(k == kind for k in dims["layer_types"][:i])
    tree = dict(jax.tree.map(lambda p: p[j], params["blocks"][stack]))
    return {"mixer": tree.pop(mixer), **tree}


# -- the forward, one sequence [S, d] ----------------------------------------------


def layernorm(x, w, b, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * w + b


def back(g, n):
    """Row t of the result is row t - n of g; zeros before the first."""
    return jnp.concatenate([jnp.zeros_like(g[:n]), g[:g.shape[0] - n]])


def selective_scan(x, dt, a, b, c, last):
    """The recurrence token by token: ``x``, ``dt`` [S, C]; ``a`` [C, N];
    ``b``, ``c`` [S, N]. Returns ``y`` [S, C] (without ``D x``) and the state
    [C, N] once position ``last`` (may be traced) is taken in."""
    def token(carry, at):
        s, kept = carry
        x_t, b_t, c_t, dt_t, here = at
        s = jnp.exp(dt_t[:, None] * a) * s \
            + (dt_t * x_t)[:, None] * b_t[None, :]
        return (s, jnp.where(here, s, kept)), s @ c_t

    zeros = jnp.zeros(a.shape, jnp.float32)
    (_, kept), y = jax.lax.scan(
        token, (zeros, zeros), (x, b, c, dt, jnp.arange(x.shape[0]) == last))
    return y, kept


def mamba(p, u, dims, last, faults=()):
    """The Mamba-1 mixer on the normed states u [S, d]: the output, the
    memory ``y`` [S, d_inner] and the state [d_inner, d_state] after
    position ``last``."""
    inner, state, rank, taps = (dims["d_inner"], dims["d_state"],
                                dims["dt_rank"], dims["conv_width"])
    xz = u @ p["w_in"]
    x, z = xz[:, :inner], xz[:, inner:]
    x = jax.nn.silu(sum(p["conv"][:, j] * back(x, taps - 1 - j)
                        for j in range(taps)) + p["conv_bias"])
    rbc = x @ p["w_x"]
    r, b, c = rbc[:, :rank], rbc[:, rank:rank + state], rbc[:, rank + state:]
    dt = jax.nn.softplus(r @ p["w_dt"] + p["dt_bias"])          # [S, C]
    y, kept = selective_scan(x, dt, -jnp.exp(p["a_log"]), b, c, last)
    if "no_skip" not in faults:
        y = y + p["d_skip"] * x
    return (y * jax.nn.silu(z)) @ p["w_out"], y, kept


def qkv(p, u, dims):
    """q [S, H, D] and, of a layer with its own, k and v [S, G, D]."""
    h, g, hd = dims["n_heads"], dims["n_kv_heads"], dims["head_dim"]
    if "wq" in p:
        return (u @ p["wq"] + p["bq"]).reshape(-1, h, hd), None, None
    out = u @ p["wqkv"] + p["bqkv"]
    return (out[:, :h * hd].reshape(-1, h, hd),
            out[:, h * hd:(h + g) * hd].reshape(-1, g, hd),
            out[:, (h + g) * hd:].reshape(-1, g, hd))


def differential(p, q, k, v, dims, l, window, faults=()):
    """Differential attention of the queries q [S, H, D] over k, v [S, G, D]
    in layer ``l``: two softmax maps a pair of heads, each normalised by
    itself, subtracted, times the group's values; the sub-norm; ``W_out``."""
    S, hd = q.shape[0], dims["head_dim"]
    pairs, groups = dims["n_heads"] // 2, dims["n_kv_heads"] // 2
    q1, q2 = q[:, 0::2], q[:, 1::2]                             # [S, P, D]
    each = pairs // groups
    k1, k2 = (jnp.repeat(a, each, axis=1) for a in (k[:, 0::2], k[:, 1::2]))
    vg = jnp.repeat(jnp.concatenate([v[:, 0::2], v[:, 1::2]], axis=-1),
                    each, axis=1)                               # [S, P, 2D]
    t, j = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
    mask = j <= t
    if window is not None:
        mask = mask & (j > t - window)

    def softmax_map(qi, ki):
        s = jnp.einsum("qpd,kpd->pqk", qi, ki) / math.sqrt(hd)
        return jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)

    init = lambda_init(l)
    lam = (jnp.exp(jnp.sum(p["lambda_q1"] * p["lambda_k1"]))
           - jnp.exp(jnp.sum(p["lambda_q2"] * p["lambda_k2"])) + init)
    o = jnp.einsum("pqk,kpe->qpe", softmax_map(q1, k1)
                   - lam * softmax_map(q2, k2), vg)             # [S, P, 2D]
    if "no_subln" not in faults:
        o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                              + dims["eps"]) * p["subln"]
    if "no_init_scale" not in faults:
        o = o * (1.0 - init)
    return o.reshape(S, -1) @ p["wo"] + p["bo"]


def gmu(p, u, memory):
    return (memory * jax.nn.silu(u @ p["w_in"])) @ p["w_out"]


def ffn(p, u):
    return (jax.nn.silu(u @ p["wi"]) * (u @ p["wg"])) @ p["wo"]


def mix(layer, x, kind: str, dims, l, carried, last, window=None, faults=()):
    """``x + Mixer(LN1(x))`` of a layer of ``kind`` at index ``l`` on one
    sequence x [S, d]. ``carried`` is ``(memory [S, d_inner], k, v [S, G,
    D])`` as the layers before left them; returns the states, what this layer
    leaves of the three (a scan its output, an attention with K and V of its
    own those) and what a scan layer's recurrence holds after position
    ``last`` (zeros for any other kind). ``window`` (may be traced): the keys
    a position sees in an attention layer with K and V of its own."""
    memory, k, v = carried
    u = layernorm(x, layer["ln1"], layer["ln1_b"], dims["eps"])
    kept = jnp.zeros((dims["d_inner"], dims["d_state"]), jnp.float32)
    p = layer["mixer"]
    if kind == MAMBA:
        out, memory, kept = mamba(p, u, dims, last, faults)
    elif kind == GMU:
        out = gmu(p, u, memory)
    else:
        q, own_k, own_v = qkv(p, u, dims)
        if kind == CROSS and "cross_own_kv" in faults:
            # K and V made from this layer's own input, by the full layer's
            # projection (what a stack without the shared cache would do)
            _, k, v = qkv(faults["cross_own_kv"], u, dims)
        elif own_k is not None:
            k, v = own_k, own_v
        out = differential(p, q, k, v, dims, l, window, faults)
        if kind == CROSS:
            k, v = carried[1], carried[2]
    return x + out, (memory, k, v), kept


def feed(layer, x, dims):
    """``x + FFN(LN2(x))``."""
    return x + ffn(layer["mlp"], layernorm(x, layer["ln2"], layer["ln2_b"],
                                           dims["eps"]))


def block(layer, x, kind: str, dims, l, carried, last, faults=()):
    """A whole layer of ``kind`` at index ``l`` (``mix`` then ``feed``); a
    window layer's K and V end with it, the full layer's stay."""
    x, left, kept = mix(layer, x, kind, dims, l, carried, last,
                        dims["window"] if kind == SWA else None, faults)
    if kind == SWA:
        left = (left[0], carried[1], carried[2])
    return feed(layer, x, dims), left, kept


def _empty_carried(S, dims):
    kv = jnp.zeros((S, dims["n_kv_heads"], dims["head_dim"]), jnp.float32)
    return jnp.zeros((S, dims["d_inner"]), jnp.float32), kv, kv


def streamed_states(x, layer_keys, dims, last):
    """The stack on one sequence x [S, d], each layer's weights drawn from
    its key where the layer runs: one scan over the keys, the layer's mixer a
    ``lax.switch`` over the kinds (each compiled once; the two kinds of
    attention with K and V of their own are one branch, the window a traced
    number that is the whole length for the full layer) and the FFN, which
    every kind has, after it (compiled once: a float32 product at this
    precision costs the TPU compiler seconds). Returns the states [S, d] and
    the scan layers' recurrent state after position ``last``, stacked in the
    layers' order [n_mamba, d_inner, d_state]."""
    kinds = dims["layer_types"]
    S = x.shape[0]
    ids = jnp.asarray(dims.get("layer_ids", range(len(kinds))), jnp.int32)
    branches = (MAMBA, SWA, CROSS, GMU)             # FULL runs as SWA
    which = jnp.array([branches.index(SWA if k == FULL else k)
                       for k in kinds])
    window = jnp.array([dims["window"] if k == SWA else S for k in kinds])
    keeps = jnp.array([k == FULL for k in kinds])

    def mixed(kind):
        def run(key, x, carried, l, window, keeps):
            layer = draw_layer(key, kind, dims, feed=False)
            x, left, kept = mix(layer, x, kind, dims, l, carried, last,
                                window)
            if kind == SWA:     # only the full layer's K and V stay
                left = (left[0], *(jnp.where(keeps, new, old) for new, old
                                   in zip(left[1:], carried[1:])))
            return x, left, kept
        return run

    def layer(carry, at):
        key, kind_at, l, window, keeps = at
        x, carried = carry
        x, carried, kept = jax.lax.switch(
            kind_at, [mixed(kind) for kind in branches], key, x, carried, l,
            window, keeps)
        return (feed(draw_feed(key, dims), x, dims), carried), kept

    (x, _), kept = jax.lax.scan(
        layer, (x, _empty_carried(S, dims)),
        (layer_keys, which, ids, window, keeps))
    return x, kept[jnp.array([i for i, k in enumerate(kinds) if k == MAMBA])]


def _logits(x, embed, ln_f, ln_f_b, dims):
    """States [n, d] -> logits [n, V]: final LayerNorm, the tied head."""
    return layernorm(x, ln_f, ln_f_b, dims["eps"]) @ embed.T


def logits_and_state_from(key, tokens, first, n: int, dims):
    """One sequence ``tokens`` [S] -> float32 logits [n, V] at the positions
    ``first`` .. ``first + n - 1`` (``first`` may be traced) and the scan
    layers' recurrent state [n_mamba, d_inner, d_state] once the last of
    those positions is taken in (what a decode loop holds when it has chosen
    its ``n``-th token), every weight drawn from ``key`` where it is used.
    Positions to the right change nothing before them, so a sequence may be
    padded there."""
    with jax.default_matmul_precision("highest"):
        k_embed, layer_keys = split_keys(key, dims)
        x = embedding(k_embed, dims)[tokens]
        x, kept = streamed_states(x, layer_keys, dims, first + n - 1)
        x, k = jax.lax.optimization_barrier(
            (jax.lax.dynamic_slice_in_dim(x, first, n), k_embed))
        return _logits(x, embedding(k, dims), *final_norm(key, dims),
                       dims), kept


def logits_from(key, tokens, first, n: int, dims):
    return logits_and_state_from(key, tokens, first, n, dims)[0]


def last_logits(key, tokens, dims):
    """tokens [B, S] -> float32 logits [B, V] at the last position (the
    adapter's contract; the sequences of a batch run one after another)."""
    S = tokens.shape[1]
    return jax.lax.map(
        lambda row: logits_from(key, row, S - 1, 1, dims)[0], tokens)


def tree_forward(params, row, dims, last=None, faults=()):
    """One sequence ``row`` [S] on a whole parameter tree: every position's
    logits [S, V], the scan layers' state after position ``last`` (the last
    one, if None) [n_mamba, d_inner, d_state], and each attention layer's K
    and V with its own (``{layer index: (k, v)}``, [S, G, D]). ``faults``
    names what is left out (``FAULTS``)."""
    with jax.default_matmul_precision("highest"):
        kinds = dims["layer_types"]
        ids = dims.get("layer_ids", range(len(kinds)))
        last = row.shape[0] - 1 if last is None else last
        faults = {f: True for f in faults}
        if "cross_own_kv" in faults:
            faults["cross_own_kv"] = from_tree(
                params, kinds.index(FULL), dims)["mixer"]
        x = params["embed"][row]
        carried = _empty_carried(row.shape[0], dims)
        states, rows = [], {}
        for i, (kind, l) in enumerate(zip(kinds, ids)):
            layer = from_tree(params, i, dims)
            if kind in (SWA, FULL):
                u = layernorm(x, layer["ln1"], layer["ln1_b"], dims["eps"])
                rows[i] = qkv(layer["mixer"], u, dims)[1:]
            x, carried, kept = block(layer, x, kind, dims, l, carried, last,
                                     faults)
            if kind == MAMBA:
                states.append(kept)
        return (_logits(x, params["embed"], params["ln_f"], params["ln_f_b"],
                        dims), jnp.stack(states), rows)


def tree_logits(params, tokens, dims, faults=()):
    """Every position's logits [B, S, V] on a whole parameter tree
    (``init_params``'s, small sizes)."""
    return jnp.stack([tree_forward(params, row, dims, faults=faults)[0]
                      for row in tokens])


def loss_and_grad_norm(params, tokens, dims):
    """Mean next-token cross entropy of ``tokens`` [B, S + 1] on a whole tree
    and the norm of its gradient (no cell trains this configuration: the
    adapter's contract asks for the name)."""
    def loss(params):
        logp = jax.nn.log_softmax(
            tree_logits(params, tokens[:, :-1], dims), axis=-1)
        return -jnp.mean(jnp.take_along_axis(
            logp, tokens[:, 1:, None], axis=-1))

    value, grads = jax.value_and_grad(loss)(params)
    norm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    return value, norm
