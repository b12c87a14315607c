"""The adapter of Granite 4.0-H's hybrid decoder (``granitemoehybrid`` with no
experts; ``ibm-granite/granite-4.0-h-micro``): Mamba-2 mixers among
grouped-query attention without positions, a dense SwiGLU FFN in every layer,
the four muP multipliers and a tied head. Its program configuration is
``ray_tpu.models.transformer.TransformerConfig`` with ``layer_kinds`` of
``mamba`` and ``attn``, ``mamba``, ``rope=False``, ``attn_scale`` and
``tie_embeddings`` set, and its reference is
``benchmark/granite_reference.py``, streamed: ``reference_params`` hands on
the seed's key and the reference draws a layer's float32 weights where it uses
them (the whole float32 tree is 12.8 GB). The configuration is served by the
``generate`` job (``benchmark/generate_job.py``), which compares a whole
answer's logits (``granite_reference.logits_from``)."""

from __future__ import annotations

from typing import Any, Dict

from benchmark.granite_reference import (ATTENTION, MAMBA,  # noqa: F401
                                         last_logits, logits_and_state_from,
                                         logits_from, loss_and_grad_norm)
from benchmark.manifest import ManifestError

# What the program's layers compute, as the published config spells it; any
# other value is a layer the program (or the reference) does not have.
_LAYER_AS_BUILT = {
    "model_type": "granitemoehybrid", "position_embedding_type": "nope",
    "attention_bias": False, "mamba_conv_bias": True,
    "mamba_proj_bias": False, "mamba_n_groups": 1, "num_local_experts": 0,
    "hidden_act": "silu", "normalization_function": "rmsnorm",
    "tie_word_embeddings": True,
}

# Argued for 40 layers (36 Mamba-2 mixers, 4 attention blocks, a dense FFN in
# each) in bfloat16 (eps 2^-8 = 3.9e-3) against float32, at prompts of 64 to
# 1,024 tokens and 384 generated positions each (the traffic's longest
# answer), decoded among 61 other occupied slots, over the whole vocabulary.
# The head is tied to an embedding drawn at 0.02 and read over
# logits_scaling = 8: a logit is about N(0, 0.02 sqrt(2048) / 8 = 0.11) and
# the largest of 100,352 is near 0.5. No choice is discrete but the answer's
# own tokens, which the reference is handed: the comparison reads how far
# bfloat16 rounding moves a logit through 40 layers at residual_multiplier
# 0.22 (a sub-layer's rounding enters the stream at a fifth of its size), and
# how far it moves what the recurrence holds after 447 to 1,407 tokens.
TOLERANCES = {
    # Read on the v5e at the cell's own sizes (PERF.md section 6, PR 52; a
    # position's number is generate_job.compare's two terms, a run's the
    # worst of its positions). The program: a run's worst 0.0099 to 0.0183
    # over 16 seeds at 32 generated positions an answer (median 0.0145) and
    # 0.0124 to 0.0243 over 16 seeds at 384. The same program with every
    # matrix rounded to float8_e4m3fn's precision (eps 2^-4, the nearest
    # precision below bfloat16; lax.reduce_precision, which the compiler
    # cannot fold away): 0.51. With ``D x`` left out of the Mamba mixer: 0.57
    # to 0.71. 0.05 is twice the program's largest reading and the two
    # controls fail it by a factor of ten. **The recurrent state kept at
    # bfloat16's precision reads 0.0156 to 0.0469 here, under the limit: the
    # logits do not decide that control, ``state_rtol`` does.**
    "logit_atol": 0.05,
    # generate_job.state_error: a sequence's served recurrent state [36, 64,
    # 64, 128] after its last step against the reference's after the same
    # tokens, the norm of the difference over the norm. The program: a run's
    # worst 0.032 to 0.064 over 16 seeds (its rounding is the step size's:
    # ``dt`` is softplus of a bfloat16 number near -5, so an absolute 2^-9 x
    # 5 there is 1% of ``dt``; 0.7% in the first layer, 6% in the last). The
    # state rounded to bfloat16's precision after the prefill and after every
    # step (lax.reduce_precision: a pair of converts the TPU compiler folds
    # away, and the control then reads what the program reads): 0.220 to
    # 0.279 over 6 answers of 2 seeds, five to sixteen times the program's
    # in every layer. ``D x`` left out: 1.07 to 1.35. 0.12 is 1.9 times the
    # program's largest reading and half the nearest control's smallest.
    "state_rtol": 0.12,
    # No cell trains this configuration (the scan has no backward pass): the
    # dense decoder's limits stand for the CPU comparison of the reference
    # with itself under jax.grad.
    "loss_rtol": 5e-3,
    "grad_norm_rtol": 3e-2,
}


def _program_kinds():
    """The program's names of the two layer shapes, ``None`` on a program
    from before the Mamba mixer and the decode loop."""
    from ray_tpu.models import transformer
    names = ("MAMBA", "ATTN", "prefill", "decode_step")
    if not all(hasattr(transformer, n) for n in names):
        return None
    return transformer.MAMBA, transformer.ATTN


def dims(config: Dict[str, Any], job: str, chips: int) -> Dict[str, Any]:
    """The sizes a cell runs: the published keys with the cut that
    ``reduced`` lists for this (job, chips): the depth alone, as the
    published indices of the layers kept (``published_layers``; nothing is
    cut for ``generate.1``). A ``ManifestError`` on a program from before the
    Mamba mixer: the harness asks for the sizes before it takes a chip, so
    such a program is refused at once."""
    name = config.get("name")
    if _program_kinds() is None:
        raise ManifestError(
            f"configuration {name!r}: this program's transformer has no "
            "Mamba-2 mixer and no decode loop (layer kinds 'mamba', 'attn'; "
            "prefill, decode_step): it cannot run this configuration")
    for key, built in _LAYER_AS_BUILT.items():
        if config.get(key) != built:
            raise ManifestError(
                f"configuration {name!r}: {key} is {config.get(key)!r}, the "
                f"program's layer is built for {built!r}")
    key = f"{job}.{chips}"
    cuts = config.get("reduced", {})
    if key not in cuts:
        raise ManifestError(
            f"configuration {name!r} has no 'reduced' entry for {key!r} (it "
            f"has {sorted(cuts)}): say what is cut, or that nothing is, "
            "before running it there")
    cut = cuts[key]
    types = config["layer_types"]
    kept = [int(i) for i in cut.get("published_layers",
                                    range(config["num_hidden_layers"]))]
    depth = int(cut.get("num_hidden_layers", config["num_hidden_layers"]))
    if (len(kept) != depth or kept != sorted(set(kept))
            or not all(0 <= i < len(types) for i in kept)):
        raise ManifestError(
            f"configuration {name!r}: published_layers {kept} are not "
            f"{depth} rising indices into the {len(types)} published layers")
    heads = int(config["num_attention_heads"])
    d_inner = int(config["mamba_expand"]) * int(config["hidden_size"])
    if d_inner != int(config["mamba_n_heads"]) * int(config["mamba_d_head"]):
        raise ManifestError(
            f"configuration {name!r}: mamba_expand x hidden_size is not "
            "mamba_n_heads x mamba_d_head")
    return {
        "vocab_size": int(config["vocab_size"]),
        "d_model": int(config["hidden_size"]),
        "n_layers": depth,
        "n_heads": heads,
        "n_kv_heads": int(config["num_key_value_heads"]),
        "head_dim": int(config["hidden_size"]) // heads,
        "d_ff": int(config["intermediate_size"]),
        "rms_norm_eps": float(config["rms_norm_eps"]),
        "layer_types": [types[i] for i in kept],
        "layer_ids": kept,
        "mamba_heads": int(config["mamba_n_heads"]),
        "mamba_head_dim": int(config["mamba_d_head"]),
        "d_state": int(config["mamba_d_state"]),
        "conv_width": int(config["mamba_d_conv"]),
        "chunk": int(config["mamba_chunk_size"]),
        "attn_scale": float(config["attention_multiplier"]),
        "embed_scale": float(config["embedding_multiplier"]),
        "residual_scale": float(config["residual_multiplier"]),
        "logit_scale": 1.0 / float(config["logits_scaling"]),
    }


def program_config(dims: Dict[str, Any], seq_len: int, opts: Dict[str, Any]):
    """``TransformerConfig`` for a cell's sizes and its ``model`` options."""
    import jax.numpy as jnp
    from ray_tpu.models.transformer import MambaConfig, TransformerConfig
    mamba, attn = _program_kinds()
    return TransformerConfig(
        vocab_size=dims["vocab_size"], d_model=dims["d_model"],
        n_layers=dims["n_layers"], n_heads=dims["n_heads"],
        n_kv_heads=dims["n_kv_heads"], d_ff=dims["d_ff"],
        max_seq_len=seq_len,
        dtype=jnp.dtype(opts.get("dtype", "bfloat16")), remat=False,
        use_flash=bool(opts.get("use_flash", True)),
        norm_eps=dims["rms_norm_eps"],
        layer_kinds=tuple(mamba if t == MAMBA else attn
                          for t in dims["layer_types"]),
        layer_ids=tuple(dims["layer_ids"]),
        embed_scale=dims["embed_scale"],
        residual_scale=dims["residual_scale"],
        logit_scale=dims["logit_scale"], tie_embeddings=True, rope=False,
        attn_scale=dims["attn_scale"],
        mamba=MambaConfig(n_heads=dims["mamba_heads"],
                          head_dim=dims["mamba_head_dim"],
                          d_state=dims["d_state"],
                          conv_width=dims["conv_width"],
                          chunk=dims["chunk"]))


def reference_params(key, dims: Dict[str, Any], seq_len: int):
    """The key: the reference draws every weight from it where it is
    used."""
    return key
