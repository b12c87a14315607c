"""The adapter of MiniCPM-SALA's decoder (``minicpm_sala``): a stack of two
kinds of layer in the published order (``mixer_types``), block-sparse
softmax attention without positions (``minicpm4``) and Lightning linear
attention with rotary positions (``lightning-attn``), per-head RMSNorm of q
and k, gated (and, in the linear layer, normed) outputs, SwiGLU, muP
scalings. Its program configuration is
``ray_tpu.models.transformer.TransformerConfig`` with ``layer_kinds`` and
the scalings set, and its reference is ``benchmark/sala_reference.py``."""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict

from benchmark import harness, sala_reference
from benchmark.adapters import dense_decoder
from benchmark.manifest import ManifestError
from benchmark.sala_reference import TREE_KEY, loss_and_grad_norm  # noqa: F401

# What the program's two kinds of layer compute, as the published config
# spells it; any other value is a layer the program does not have.
_LAYERS_AS_BUILT = {
    "attention_bias": False, "attn_use_rope": False, "hidden_act": "silu",
    "lightning_scale": "1/sqrt(d)", "lightning_use_rope": True,
    "qk_norm": True, "tie_word_embeddings": False, "use_output_gate": True,
    "use_output_norm": True, "attn_use_output_gate": True,
}

# Argued for 8 layers in bfloat16 (eps 2^-8 = 3.9e-3) against float32, at
# prompts of 4,096 to 32,768 tokens. The logits here are small: the head
# reads N_f(x) / 16 (hidden_size / dim_model_base), so with seeded weights
# a logit is about N(0, 1/16) and the largest of 73,448 is near 0.27, where
# the other adapters' logits are about unit normal with a largest near 4.5:
# every limit below is a sixteenth of what it would be there.
#
# The stream. x starts as 12 * E (RMS 0.24) and every sub-layer adds 0.2475
# times its output. A linear layer's output is normed to unit RMS before
# its gate, so it adds about 0.12 and the six of them carry the stream; each
# is a sum over a horizon of up to 1 / (1 - lam) = 430 tokens of bfloat16
# products, accumulated in float32. A sparse layer averages about 4,096
# values of v with near-equal weights (q and k are normed, so a score is
# about N(0, 1)): its output has RMS near 1 / 40 and adds 0.003, a
# hundredth of the stream.
#
# The selection is discrete. A block's score is a sum over 16 heads of a
# maximum of softmax probabilities, and the candidates near the 64th place
# lie closer together (about 1e-3 relative) than bfloat16 resolves them
# (about 5e-3), so the program and the reference keep a few different blocks
# in most rows. A swapped block replaces 64 of about 4,096 averaged values:
# the row's attention output moves by about sqrt(2 * 64 / 4096) = 18% of
# itself, which is 5e-4 of the stream, a tenth of what one bfloat16 rounding
# of the stream is. So the limit is set by the rounding of eight layers and
# not by the swaps, and it cannot see the sparse operation at all: its
# output left out moves a logit by 6e-4 to 1e-3. Made larger (weights drawn
# so that a sparse mixer adds what a linear one adds) it would be seen no
# better, because the two sides' streams differ by 3% where the last layer
# selects, about three of its 64 blocks are swapped, and three swapped
# blocks are a third of what a selection without its window is: the limit
# that passed the one would pass the other. A wrong *rule* of selection
# (no window, no first block, 32 blocks for 64) is told by the CPU tests,
# where both sides are float32 and agree to 1e-5. What the chip compiles of
# the two operations is told by ``OPERATIONS`` below, on the same q, k and
# v on both sides, where nothing is swapped.
#
# The limit is set from two readings on the v5e at the cell's own sizes
# (PERF.md section 6, PR 37; a run's number is the worst of its three
# prompts, of 4,096, 12,288 and 32,768 tokens, as serve_job._compare takes
# it): the program's largest gap over its seeds, and the gap of the same
# program with its weights rounded to float8_e4m3fn (eps 2^-4), which has to
# fall outside.
TOLERANCES = {
    # The program over 35 seeds: 0.0003 to 0.0045 (median 0.0018; a single
    # prompt's gap is about half-normal with sigma 0.0013, no larger at
    # 32,768 tokens than at 4,096). With 8-bit weights over 12 seeds, four
    # of them through the harness itself: 0.0082 to 0.0403 (median 0.02).
    # The two lie a factor of eight apart and each spreads by a factor of
    # three, so they nearly touch: 0.007 is 1.6 times the program's largest
    # reading (over five of its sigmas) and under every reading of the
    # control, the nearest by a sixth. That is a factor of two between the
    # nearest readings where three is asked: the number is held, not argued
    # (PERF.md section 7; one logit a prompt is what serve_job._compare
    # has). A layer left out, a norm, the gate or a scaling moves every
    # logit by about its own size, 0.06.
    "logit_atol": 0.007,
    # No cell trains this configuration (the new kinds have no backward
    # pass): the dense decoder's limits stand for the CPU comparison of the
    # reference with itself under jax.grad.
    "loss_rtol": 5e-3,
    "grad_norm_rtol": 3e-2,
}

# The two operations, as this device compiled them, against the reference's
# functions of the same bfloat16 q, k and v, at the compared prompt's length
# rounded up to the bucket it was served in. With the same operands the two
# sides' block scores differ by float32 summation order (4e-7 of a score at
# most, where neighbouring candidates lie 5e-4 apart), so a query keeps the
# same blocks on both sides, and a row then differs by the rounding of the
# softmax's weights and of the output to bfloat16. On the v5e at 16,384 and
# 32,768 tokens (PERF.md section 6, PR 37): the largest sparse row 0.24% of
# its norm, no linear row over 1%, no row off on either side over six keys.
# One swapped block is 12-18% of a row. A selection with a window of 64
# tokens for 2,048 puts 75% and 87% of the rows off, 32 blocks for 64 87% and
# 93%, the first block not forced 54% and 73%, an output zeroed or shifted by
# a row all of them. ``row_rtol`` lies between the rounding and one swap;
# ``rows_off_max`` between no row and the half of the rows that the mildest
# of those faults reaches. (Both sides must read the *same* numbers: a cast
# to bfloat16 and back inside one program is kept in excess precision by the
# TPU compiler, and the reference then swaps a block in 5-7% of the rows;
# hence ``rounded`` below.)
OPERATIONS = {"row_rtol": 0.02, "rows_off_max": 0.05}
# serve_job._compare has one number, the logit's error: a failed check of the
# operations adds this to every logit, so the error reads 1 + the share of
# rows that were off, where a logit is 0.3 at most.
OPERATIONS_OFF = 1.0


def _rows_off(got, want, axes):
    import jax.numpy as jnp
    gap = jnp.sqrt(jnp.sum((got - want) ** 2, axis=axes))
    size = jnp.sqrt(jnp.sum(want ** 2, axis=axes))
    return jnp.mean(gap > OPERATIONS["row_rtol"] * size)


def operations_rows_off(key, length: int, dims: Dict[str, Any]):
    """The share of rows in which the program's operation leaves the
    reference's function of the same seeded bfloat16 q, k, v of ``length``
    tokens (unit RMS, as the heads' norms leave them): ``(sparse, linear)``.
    A sparse row is a query's 16 heads of one K/V head (they share a
    selection), a linear row one head's output at one position. The pooled
    keys reach the reference rounded to bfloat16, as the program holds them.
    The linear operation runs with the decay of the first linear layer kept,
    each side by its own formula. Sparse: 0 up to ``dense_len`` tokens, where
    the program takes the accepted flash kernel."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.ops import linear_attention, sparse_attention
    from ray_tpu.ops.linear_attention import decay_rates

    cfg = program_config(dims, length, {})
    c = dims["sparse_config"]
    heads, kv, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    group = heads // kv
    kq, kk, kv_key = jax.random.split(key, 3)

    def rounded(x):
        """x as bfloat16 rounds it, still float32. A cast there and back
        will not do: inside one program the TPU compiler keeps the excess
        precision, the reference would read what the operation never saw,
        and 3-7% of the rows would swap a block."""
        return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)

    def drawn(k, n):
        return rounded(jax.random.normal(k, (length, n, d))).astype(
            jnp.bfloat16)

    def part(x, first, size):
        """Heads [first, first + size) of x [S, n, d], widened once cut out:
        a float32 copy of all the heads would be the check's largest array."""
        return jax.lax.dynamic_slice_in_dim(x, first, size, axis=1).astype(
            jnp.float32)

    q = drawn(kq, heads)
    selecting = length > c["dense_len"]
    first = next(i for i, m in zip(dims["layer_ids"], dims["mixer_types"])
                 if TREE_KEY[m] == "linear")
    size = math.gcd(heads, sala_reference.HEAD_GROUP)
    # the operations as the served program calls them, outside the
    # reference's matmul precision
    if selecting:
        k, v = drawn(kk, kv), drawn(kv_key, kv)
        got = sparse_attention(q[None], k[None], v[None], cfg.sparse)[0]
    lk, lv = drawn(kk, heads), drawn(kv_key, heads)
    lgot = linear_attention(q[None], lk[None], lv[None], decay_rates(
        heads, first, dims["published_layers"]))[0]
    with jax.default_matmul_precision("highest"):
        sparse = jnp.float32(0.0)
        if selecting:
            def head(n):
                keys, values = part(k, n, 1)[:, 0], part(v, n, 1)[:, 0]
                want = sala_reference.selected_attention(
                    part(q, n * group, group), keys, values, dims,
                    rounded(sala_reference.pooled_keys(keys, c)))
                return _rows_off(part(got, n * group, group), want, (1, 2))

            sparse = jnp.mean(jax.lax.map(head, jnp.arange(kv)))
        rates = sala_reference.decay_rates(first, dims)

        def heads_from(n):
            want = sala_reference.decayed_attention(
                part(q, n, size), part(lk, n, size), part(lv, n, size),
                jax.lax.dynamic_slice_in_dim(rates, n, size))
            return _rows_off(part(lgot, n, size), want, (2,))

        linear = jnp.mean(jax.lax.map(heads_from,
                                      jnp.arange(0, heads, size)))
        return sparse, linear


def last_logits(params, tokens, dims: Dict[str, Any]):
    """The reference's logits at the last position, tokens [B, S] ->
    float32 [B, V]; after them (the reference's temporaries are freed) the
    check of the two operations at S rounded up to a whole number of
    ``dense_len`` (the cell's buckets; and to whole K/V tiles of 8 blocks),
    seeded from the first prompt: where either is off in more rows than
    ``OPERATIONS`` allows, every logit is off by ``OPERATIONS_OFF`` and
    more."""
    import jax
    import jax.numpy as jnp

    logits = sala_reference.last_logits(params, tokens, dims)
    c = dims["sparse_config"]
    whole = math.lcm(c["dense_len"], 8 * c["block_size"])
    length = -(-tokens.shape[1] // whole) * whole
    key = jax.random.fold_in(jax.random.PRNGKey(37), tokens[0, 0])
    key, logits = jax.lax.optimization_barrier((key, logits))
    sparse, linear = operations_rows_off(key, length, dims)
    jax.debug.callback(
        lambda s, l: harness.say(
            f"operations against the reference at {length} tokens: rows "
            f"off sparse {float(s):.5f}, linear {float(l):.5f} (at most "
            f"{OPERATIONS['rows_off_max']})"), sparse, linear)
    worst = jnp.maximum(sparse, linear)
    return logits + jnp.where(worst > OPERATIONS["rows_off_max"],
                              OPERATIONS_OFF + worst, 0.0)


def _program_has_the_layers() -> bool:
    from ray_tpu.models.transformer import TransformerConfig
    return "layer_kinds" in {f.name for f in dataclasses.fields(
        TransformerConfig)}


def dims(config: Dict[str, Any], job: str, chips: int) -> Dict[str, Any]:
    """The dense decoder's sizes (the published keys with the cut that
    ``reduced`` lists for this (job, chips)), the kept layers' published
    indices and kinds (``first_layer`` of the cut onwards), the scalings,
    and the selection's constants (``assumed``: the published config has no
    such keys). A ``ManifestError`` on a program from before the stack of
    several kinds of block: the harness asks for the sizes before it takes
    a chip, so such a program is refused at once."""
    if not _program_has_the_layers():
        raise ManifestError(
            f"configuration {config.get('name')!r}: this program's "
            "TransformerConfig has no layer_kinds (a stack of one kind of "
            "block): it cannot run this configuration")
    for key, built in _LAYERS_AS_BUILT.items():
        if config.get(key) != built:
            raise ManifestError(
                f"configuration {config.get('name')!r}: {key} is "
                f"{config.get(key)!r}, the program's layers are built for "
                f"{built!r}")
    sizes = dense_decoder.dims(config, job, chips)
    heads = (sizes["n_heads"], sizes["n_heads"], sizes["head_dim"])
    if (config["lightning_nh"], config["lightning_nkv"],
            config["lightning_head_dim"]) != heads:
        raise ManifestError(
            f"configuration {config.get('name')!r}: the linear layers' "
            f"heads {config['lightning_nh']} / {config['lightning_nkv']} x "
            f"{config['lightning_head_dim']} are not the attention's "
            f"{heads}")
    first = int(config["reduced"][f"{job}.{chips}"].get("first_layer", 0))
    kept = config["mixer_types"][first:first + sizes["n_layers"]]
    unknown = sorted(set(kept) - set(TREE_KEY))
    if unknown or len(kept) != sizes["n_layers"]:
        raise ManifestError(
            f"configuration {config.get('name')!r}: layers {first} to "
            f"{first + sizes['n_layers'] - 1} of mixer_types are {kept}")
    return {
        **sizes,
        "mixer_types": list(kept),
        "layer_ids": list(range(first, first + sizes["n_layers"])),
        "published_layers": len(config["mixer_types"]),
        "scale_emb": float(config["scale_emb"]),
        "scale_depth": float(config["scale_depth"]),
        "dim_model_base": int(config["dim_model_base"]),
        "sparse_config": dict(config["assumed"]["sparse_config"]),
    }


def program_config(dims: Dict[str, Any], seq_len: int, opts: Dict[str, Any]):
    """``TransformerConfig`` for a cell's sizes and its ``model`` options:
    the dense decoder's with each layer's kind and published index, the
    scalings, the published epsilon and the selection's constants."""
    from ray_tpu.ops.sparse_attention import SparseConfig
    return dataclasses.replace(
        dense_decoder.program_config(dims, seq_len, opts),
        layer_kinds=tuple(TREE_KEY[m] for m in dims["mixer_types"]),
        layer_ids=tuple(dims["layer_ids"]),
        decay_depth=dims["published_layers"],
        embed_scale=dims["scale_emb"],
        residual_scale=dims["scale_depth"] / math.sqrt(
            dims["published_layers"]),
        logit_scale=dims["dim_model_base"] / dims["d_model"],
        norm_eps=dims["rms_norm_eps"],
        sparse=SparseConfig(**dims["sparse_config"]))
