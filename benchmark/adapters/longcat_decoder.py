"""The adapter of LongCat-Flash's decoder (the language model of
``meituan-longcat/LongCat-Flash-Omni``): a stack of shortcut-connected
layers, each two latent-attention (MLA) blocks, two dense SwiGLU FFNs and a
routed mixture with zero-compute experts, of which this device holds a
share. Its program configuration is
``ray_tpu.models.transformer.TransformerConfig`` with ``layer_kinds`` all
``shortcut``, ``latent`` and ``experts`` set, and its reference is
``benchmark/longcat_reference.py``, streamed: ``reference_params`` hands on
the seed's key and ``last_logits`` draws a layer's float32 weights where it
uses them (the whole float32 tree is 20.7 GB)."""

from __future__ import annotations

import dataclasses
from typing import Any, Dict

from benchmark.longcat_reference import (last_logits,  # noqa: F401
                                         loss_and_grad_norm)
from benchmark.manifest import ManifestError

# What the program's layer computes, as the published config spells it; any
# other value is a layer the program does not have.
_LAYER_AS_BUILT = {
    "attention_bias": False, "attention_method": "MLA",
    "mla_scale_q_lora": True, "mla_scale_kv_lora": True,
    "zero_expert_type": "identity",
}

# Argued for 4 layers (8 attention blocks, 8 FFNs, 4 mixtures) in bfloat16
# (eps 2^-8 = 3.9e-3) against float32, at prompts of 1,024 to 8,192 tokens
# over a 16,384-row slice of the vocabulary. Logits of seeded weights are
# about unit normal and the largest of 16,384 is near 4. The stream is not
# normed between sub-layers: 16 additions carry a rounding error of about
# eps * sqrt(16) = 1.6e-2 of the stream, which the final norm hands to the
# logits: about 0.06 at the largest.
#
# That holds only while attention is smooth. With MLA's up-projections drawn
# at 1 / sqrt(rank) the published scalings (2 on q, sqrt(12) on the latent)
# make the scores' spread 5.7, attention all but picks one key, and a
# rounding grows 2.5 times a layer: on the chip one layer read 0.17 and four
# layers 2.49 (61% of the logits' size), bfloat16 and float32 at the TPU's
# default precision alike, with the kernel (0.17% of a row) and the expert
# layer (0.17%) exact. Drawn at 1 / sqrt(hidden_size), the fan-in those
# scalings restore, q, k and v have unit variance, the scores are of order 1
# as the dense decoder's are, and four layers read 0.03 to 0.19
# (``transformer.init_params``; PERF.md section 6, PR 41).
#
# The choice of experts is discrete. The program's router reads u rounded to
# bfloat16 and bfloat16 weights and accumulates exactly in float32; the
# reference reads both in float32. The twelfth and thirteenth largest of 768
# probabilities lie about 2% apart on average, and the two sides' logits
# differ by about 5e-3 of a unit-normal logit: in about a tenth of the tokens
# of a layer the twelfth choice differs. What a flipped choice moves: a
# zero-compute index swapped for another changes nothing but the weight (6 p,
# with p near 0.01, by 2% of itself); a held expert swapped in or out adds or
# removes 6 p Expert_e(u), about 0.06 of one expert's output: 6/768 of an
# expert's output a choice on average. So a flip moves the stream by well
# under the rounding's 1.6e-2 in the layer where it happens, and the readings
# show no second population: every seed's worst logit lies in one cluster
# below 0.1 (TOLERANCES below).
TOLERANCES = {
    # Read on the v5e at the cell's own sizes (PERF.md section 6, PR 41; a
    # run's number is the worst of its three prompts, of 1,024, 4,096 and
    # 8,192 tokens, as serve_job._compare takes it). The program over its
    # 16 seeds: 0.012 to 0.062 (median 0.020). The same program with every matrix rounded to
    # float8_e4m3fn's precision (eps 2^-4), four seeds through the harness
    # itself: 4.24, 4.53, 4.74, 8.07 (the rounding is 16 times coarser and
    # the stack amplifies it: the logits are then unrelated to the
    # reference's). 0.3 is five times the program's largest reading and a
    # fourteenth of the control's smallest. A layer left out, a norm, a
    # scaling or the factor 6 moves every logit by about its own size.
    "logit_atol": 0.3,
    # No cell trains this configuration (the layer has no backward pass in
    # the program): the dense decoder's limits stand for the CPU comparison
    # of the reference with itself under jax.grad.
    "loss_rtol": 5e-3,
    "grad_norm_rtol": 3e-2,
}


def _program_has_the_layer() -> bool:
    from ray_tpu.models import transformer
    return hasattr(transformer, "SHORTCUT")


def dims(config: Dict[str, Any], job: str, chips: int) -> Dict[str, Any]:
    """The sizes a cell runs: the published keys with the cut that
    ``reduced`` lists for this (job, chips): the depth, the routed experts
    held here (``n_routed_experts`` becomes the count held, from published
    index ``first_expert``; the router keeps the published width) and the
    vocabulary's slice. A ``ManifestError`` on a program from before the
    shortcut layer: the harness asks for the sizes before it takes a chip,
    so such a program is refused at once."""
    name = config.get("name")
    if not _program_has_the_layer():
        raise ManifestError(
            f"configuration {name!r}: this program's transformer has no "
            "'shortcut' layer kind (latent attention, two FFNs and a routed "
            "mixture a layer): it cannot run this configuration")
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
    held = (int(cut.get("first_expert", 0)),
            int(cut.get("n_routed_experts", config["n_routed_experts"])))
    if held[0] < 0 or held[1] < 1 or sum(held) > config["n_routed_experts"]:
        raise ManifestError(
            f"configuration {name!r}: experts {held[0]} to "
            f"{sum(held) - 1} are not among the published "
            f"{config['n_routed_experts']}")
    return {
        "vocab_size": int(cut.get("vocab_size", config["vocab_size"])),
        "d_model": int(config["hidden_size"]),
        "n_layers": int(cut.get("num_layers", config["num_layers"])),
        "n_heads": int(config["num_attention_heads"]),
        "d_ff": int(config["ffn_hidden_size"]),
        "rope_theta": float(config["rope_theta"]),
        "rms_norm_eps": float(config["rms_norm_eps"]),
        "q_rank": int(config["q_lora_rank"]),
        "kv_rank": int(config["kv_lora_rank"]),
        "nope_dim": int(config["qk_nope_head_dim"]),
        "rope_dim": int(config["qk_rope_head_dim"]),
        "v_dim": int(config["v_head_dim"]),
        "n_routed": int(config["n_routed_experts"]),
        "n_zero": int(config["zero_expert_num"]),
        "top_k": int(config["moe_topk"]),
        "scale": float(config["routed_scaling_factor"]),
        "expert_width": int(config["expert_ffn_hidden_size"]),
        "held": list(held),
    }


def program_config(dims: Dict[str, Any], seq_len: int, opts: Dict[str, Any]):
    """``TransformerConfig`` for a cell's sizes and its ``model`` options: a
    stack of ``shortcut`` layers with the latent attention's and the
    mixture's sizes and this device's held experts."""
    import jax.numpy as jnp
    from ray_tpu.models.transformer import (SHORTCUT, LatentConfig,
                                            TransformerConfig)
    from ray_tpu.parallel.expert import ExpertConfig
    return TransformerConfig(
        vocab_size=dims["vocab_size"], d_model=dims["d_model"],
        n_layers=dims["n_layers"], n_heads=dims["n_heads"],
        d_ff=dims["d_ff"], max_seq_len=seq_len,
        dtype=jnp.dtype(opts.get("dtype", "bfloat16")),
        remat=bool(opts.get("remat", True)),
        use_flash=bool(opts.get("use_flash", True)),
        rope_theta=dims["rope_theta"], norm_eps=dims["rms_norm_eps"],
        layer_kinds=(SHORTCUT,) * dims["n_layers"],
        latent=LatentConfig(
            q_rank=dims["q_rank"], kv_rank=dims["kv_rank"],
            nope_dim=dims["nope_dim"], rope_dim=dims["rope_dim"],
            v_dim=dims["v_dim"]),
        experts=ExpertConfig(
            n_routed=dims["n_routed"], n_zero=dims["n_zero"],
            top_k=dims["top_k"], scale=dims["scale"],
            width=dims["expert_width"], held=tuple(dims["held"])))


def reference_params(key, dims: Dict[str, Any], seq_len: int):
    """The key: ``last_logits`` draws every weight from it where it is
    used."""
    return key
