"""The adapter of Kanana-2's decoder (``deepseek_v3``;
``kakaocorp/kanana-2-30b-a3b-instruct-2601``): latent attention (MLA) with
no q bottleneck at 192-wide q and k heads beside 128-wide v heads, a dense
SwiGLU FFN in the leading layer and then two shared experts beside a
sigmoid-routed mixture of 128 small experts, top-6, the choice on score +
bias, of which this chip holds a share. Its program configuration is
``ray_tpu.models.transformer.TransformerConfig`` with ``layer_kinds`` of
``latent`` and ``latent_moe``, ``latent`` (``q_rank`` None) and ``experts``
(``shared_width``) set, and its reference is
``benchmark/kanana2_reference.py`` on the program's own float32 tree (2.75 GB
at the cell's cut)."""

from __future__ import annotations

from typing import Any, Dict

from benchmark.adapters import dense_decoder
from benchmark.kanana2_reference import (last_logits,  # noqa: F401
                                         loss_and_grad_norm)
from benchmark.manifest import ManifestError

# What the program's layer computes, as the published config spells it; any
# other value is a layer the program (or the reference) does not have.
_LAYER_AS_BUILT = {
    "model_type": "deepseek_v3", "q_lora_rank": None, "attention_bias": False,
    "rope_interleave": True, "rope_scaling": None, "scoring_func": "sigmoid",
    "topk_method": "noaux_tc", "n_group": 1, "topk_group": 1,
    "norm_topk_prob": True, "moe_layer_freq": 1, "hidden_act": "silu",
    "tie_word_embeddings": False,
}

# Argued for 5 layers (5 MLA blocks, a dense FFN, 4 mixtures of 16 held
# experts beside the shared ones) in bfloat16 (eps 2^-8 = 3.9e-3) against
# float32, on 4 sequences of 512 tokens over a 16,032-row slice of the
# vocabulary, the loss a mean of 2,048 cross entropies near ln(16,032) = 9.68
# + 0.5. The stream is not normed between sub-layers: 10 additions carry a
# rounding error of about eps * sqrt(10) = 1.2e-2 of the stream, most of which
# cancels in the mean; a gradient norm does not average it away. The choice
# of experts is discrete (the sixth and seventh largest of 128 values of s +
# b lie close), but a flipped sixth choice moves a token by one expert at a
# weight of 2.448 / 6 = 0.4 on 0.75 held pairs a token: under the rounding.
#
# Each limit is set from two readings on the v5e at the cell's own sizes
# (PERF.md section 6, PR 48; `.scratch/probe_tol.py` and the harness's own
# runs): the program's largest gap over its seeds, and the gap of the same
# program with every matrix rounded to float8_e4m3fn's precision (eps 2^-4,
# ``lax.reduce_precision``), which has to fall outside one of the two.
TOLERANCES = {
    # No cell serves this configuration: the dense decoder's limit stands
    # for the CPU comparison of ``last_logits`` with the program's forward
    # (float32 on both sides there: 1e-3 of it), not measured on the chip.
    "logit_atol": 0.3,
    # Read on the chip, 21 seeds (the probe's six and the harness's fifteen
    # runs, PR 48): 7e-7 to 2.6e-4, relative; with 8-bit weights, six seeds,
    # 7.7e-4 to 6.6e-3. The limit is 2.7 times the program's largest and under
    # the control's smallest. What is left out of the mathematics moves it
    # less than the precision does: the shared experts 1.1e-4 to 2.1e-3, the
    # factor 2.448 3e-5 to 9.7e-4, one held expert of 16 2.5e-5 to 4.4e-4: it
    # is the gradient norm that refuses the first two, and neither limit
    # sees one held expert (below).
    "loss_rtol": 7e-4,
    # Read on the chip, 21 seeds: 1e-7 to 2.1e-3 (to 1.1e-3 over the nine of
    # the final tree, whose backward makes the weight gradients once a layer
    # call); with 8-bit weights, six seeds, 2.6e-3 to 5.2e-2, which overlaps
    # at one seed (2.6e-3), where it is the loss's limit that refuses the
    # control (3.9e-3). About three times the program's largest. The
    # reference with the shared experts left out reads 0.30 to 0.32 on every
    # seed, with the factor 2.448 left out 2.2e-2 to 2.8e-2: both refused, by
    # a factor of 43 and of 3. With one held expert of 16 left out it reads
    # 9e-5 to 4.2e-3, inside the program's own rounding: a sixteenth of 0.75
    # pairs a token is under what one loss and one norm can see at these
    # sizes. The CPU tests hold every leaf's gradient to 1e-5 of the leaf,
    # and the toy cell (4 held of 16, top-3) does refuse a missing expert by
    # these limits.
    "grad_norm_rtol": 7e-3,
}


def _program_kinds():
    """The program's names of the two layer shapes, ``None`` on a program
    from before the latent mixer of ``PARTS``."""
    from ray_tpu.models import transformer
    names = ("LATENT", "LATENT_MOE")
    if not all(hasattr(transformer, n) for n in names):
        return None
    return tuple(getattr(transformer, n) for n in names)


def dims(config: Dict[str, Any], job: str, chips: int) -> Dict[str, Any]:
    """The sizes a cell runs: the published keys with the cut that
    ``reduced`` lists for this (job, chips): the depth as the published
    indices of the layers kept, the routed experts held here and the
    vocabulary's slice; every width, the router's 128 outputs and its 6
    experts a token are as published. A ``ManifestError`` on a program from
    before the latent mixer: the harness asks for the sizes before it takes
    a chip, so such a program is refused at once."""
    name = config.get("name")
    if _program_kinds() is None:
        raise ManifestError(
            f"configuration {name!r}: this program's transformer has no "
            "latent mixer among its mixer-and-FFN kinds ('latent', "
            "'latent_moe'): it cannot run this configuration")
    for key, built in _LAYER_AS_BUILT.items():
        if config.get(key, "missing") != built:
            raise ManifestError(
                f"configuration {name!r}: {key} is {config.get(key)!r}, the "
                f"program's layer is built for {built!r}")
    from ray_tpu.parallel import expert
    rate = config.get("assumed", {}).get("bias_update_rate")
    if rate != expert.BIAS_RATE:
        raise ManifestError(
            f"configuration {name!r}: assumed.bias_update_rate is {rate!r}, "
            f"the program's step moves a bias by {expert.BIAS_RATE}")
    key = f"{job}.{chips}"
    cuts = config.get("reduced", {})
    if key not in cuts:
        raise ManifestError(
            f"configuration {name!r} has no 'reduced' entry for {key!r} (it "
            f"has {sorted(cuts)}): say what is cut, or that nothing is, "
            "before running it there")
    cut = cuts[key]
    published = int(config["num_hidden_layers"])
    kept = [int(i) for i in cut.get("published_layers", range(published))]
    depth = int(cut.get("num_hidden_layers", published))
    if (len(kept) != depth or kept != sorted(set(kept))
            or not all(0 <= i < published for i in kept)):
        raise ManifestError(
            f"configuration {name!r}: published_layers {kept} are not "
            f"{depth} rising indices into the {published} published layers")
    routed = int(config["n_routed_experts"])
    first = int(cut.get("first_expert", 0))
    held = int(cut.get("n_routed_experts", routed))
    if not 0 <= first <= first + held <= routed:
        raise ManifestError(
            f"configuration {name!r}: experts {first} to {first + held} are "
            f"not among the {routed} published")
    return {
        "vocab_size": int(cut.get("vocab_size", config["vocab_size"])),
        "d_model": int(config["hidden_size"]),
        "n_layers": depth,
        "layer_ids": kept,
        "n_heads": int(config["num_attention_heads"]),
        "nope_dim": int(config["qk_nope_head_dim"]),
        "rope_dim": int(config["qk_rope_head_dim"]),
        "v_dim": int(config["v_head_dim"]),
        "kv_rank": int(config["kv_lora_rank"]),
        "d_ff": int(config["intermediate_size"]),
        "rope_theta": float(config["rope_theta"]),
        "rms_norm_eps": float(config["rms_norm_eps"]),
        "first_k_dense": int(config["first_k_dense_replace"]),
        "n_routed": routed,
        "top_k": int(config["num_experts_per_tok"]),
        "scale": float(config["routed_scaling_factor"]),
        "expert_width": int(config["moe_intermediate_size"]),
        "shared_width": int(config["n_shared_experts"])
        * int(config["moe_intermediate_size"]),
        "held": [first, held],
        "bias_rate": float(config["assumed"]["bias_update_rate"]),
        "warmup_steps": int(config["assumed"].get("lr_warmup_steps", 0)),
    }


def program_config(dims: Dict[str, Any], seq_len: int, opts: Dict[str, Any]):
    """``TransformerConfig`` for a cell's sizes and its ``model`` options:
    each layer's kind from its published index (a dense FFN below
    ``first_k_dense``, else the mixture), MLA without a q bottleneck, the
    router (sigmoid scores, a bias for the choice that load moves, weights
    normalised over the chosen), the shared experts, and the learning rate's
    warm-up (``assumed``: the step builder's default optimizer reads it)."""
    import jax.numpy as jnp
    from ray_tpu.models.transformer import LatentConfig, TransformerConfig
    from ray_tpu.parallel.expert import ExpertConfig
    latent, latent_moe = _program_kinds()
    return TransformerConfig(
        vocab_size=dims["vocab_size"], d_model=dims["d_model"],
        n_layers=dims["n_layers"], n_heads=dims["n_heads"],
        d_ff=dims["d_ff"], max_seq_len=seq_len,
        dtype=jnp.dtype(opts.get("dtype", "bfloat16")),
        remat=bool(opts.get("remat", True)),
        use_flash=bool(opts.get("use_flash", True)),
        rope_theta=dims["rope_theta"], norm_eps=dims["rms_norm_eps"],
        layer_kinds=tuple(latent if i < dims["first_k_dense"] else latent_moe
                          for i in dims["layer_ids"]),
        layer_ids=tuple(dims["layer_ids"]),
        latent=LatentConfig(q_rank=None, kv_rank=dims["kv_rank"],
                            nope_dim=dims["nope_dim"],
                            rope_dim=dims["rope_dim"], v_dim=dims["v_dim"]),
        experts=ExpertConfig(
            n_routed=dims["n_routed"], n_zero=0, top_k=dims["top_k"],
            scale=dims["scale"], width=dims["expert_width"],
            held=tuple(dims["held"]), score="sigmoid", choice_bias=True,
            normalize=True, shared_width=dims["shared_width"]),
        warmup_steps=dims["warmup_steps"])


def reference_params(key, dims: Dict[str, Any], seq_len: int):
    """What ``last_logits`` takes as ``params``: the whole float32 tree."""
    return dense_decoder.float32_init_params(program_config, key, dims,
                                             seq_len)
