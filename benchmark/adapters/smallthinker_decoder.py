"""The adapter of SmallThinker's sparse decoder
(``PowerInfer/SmallThinker-21BA3B-Instruct``): grouped-query attention at a
``head_dim`` of its own, through a window with rotary positions in three
layers of four and over everything without positions in the fourth, a router
that reads the block's input ahead of the attention, and ReGLU experts with
no shared one and no dense layer. Its program configuration is
``ray_tpu.models.transformer.TransformerConfig`` with ``layer_kinds`` of
``window_moe`` and ``global_moe``, ``head_width``, ``window`` and ``experts``
(``activation="relu"``) set, and its reference is
``benchmark/smallthinker_reference.py``, streamed: ``reference_params`` hands
on the seed's key and the reference draws a layer's float32 weights where it
uses them (the cut's float32 tree is 15.9 GB). The configuration is served by
the ``generate_kv`` job (``benchmark/generate_kv_job.py``), which compares a
whole answer's logits and the K rows the two caches hold
(``smallthinker_reference.logits_and_keys_from``)."""

from __future__ import annotations

from typing import Any, Dict

from benchmark.manifest import ManifestError
from benchmark.smallthinker_reference import (GLOBAL, WINDOW,  # noqa: F401
                                              last_logits,
                                              logits_and_keys_from,
                                              logits_from, loss_and_grad_norm)

# What the program's layers compute, as the published config spells it; any
# other value is a layer the program (or the reference) does not have.
_LAYER_AS_BUILT = {
    "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
    "rope_scaling": None, "tie_word_embeddings": False,
}

# Argued for 8 layers (2 global, 6 window; 64 ReGLU experts, 6 a token, in
# each) in bfloat16 (eps 2^-8 = 3.9e-3) against float32, at prompts of 512,
# 3,968 and 12,288 tokens and 384 generated positions each, decoded among 45
# other occupied slots, over the whole vocabulary. The head is untied and
# drawn at 1/sqrt(2560): a logit is about N(0, 1) and the largest of 151,936
# is near 4.5.
#
# Two things are discrete: the answer's own tokens, which the reference is
# handed, and **the router's choice**, which the reference makes again in
# float32 from its own float32 stream. The sixth and seventh largest of a
# token's 64 logits lie about a tenth of the logits' spread apart, and the
# program's router reads a stream rounded to bfloat16 at every sub-layer: on
# the v5e a few tokens in a hundred choose another sixth expert than the
# reference in the first layer, a quarter by the fourth, a third by the
# eighth. A flipped choice swaps a whole expert at a weight of about a sixth
# and moves that token's state by a tenth to a half of itself. So the worst
# position of a *sound* run reads 0.8 to 1.9 of logits and a layer's whole
# norm ratio 0.17 to 0.20, which is what a window layer that sees everything
# reads too. **The witness** (generate_kv_job.cache_error's ``witness``; 10
# seeds, 30 sequences, PERF.md section 6, PR 55): the reference hands out its
# own router's margins, and of the rows whose token chose by a tenth of its
# logits' spread or more at every layer below (*clear* rows) that a prefill
# wrote, **none of 43,955 is off by 0.1 (the largest reads 0.080)**, while 1
# to 35 in a hundred of a layer's other rows are. So the logits' number and
# the number over all of a prompt's rows are **lower quartiles**, the clear
# rows of a prompt are held **at their largest**, and the clear rows that the
# decode steps wrote at their **upper quartile**: a greedy answer of seeded
# weights repeats itself, so a token that chose narrowly comes back, its rows
# flip together and what they add to their clear neighbours through the
# attention is no longer small (one answer in thirty: 0.25 at the ninetieth
# percentile of its clear rows, 0.033 at the upper quartile). The steps' rows
# are also asked *where they lie* (ring_placed_min), which no drift changes.
# What the numbers then cannot see: one wrong choice, a fault on fewer than a
# quarter of the clear rows the steps wrote or on rows that are not clear
# alone, a generated position's logits at their worst (no position is clear
# through all eight layers); the CPU tests hold those, float32 on both sides,
# to 2e-4 at every position.
TOLERANCES = {
    # Read on the v5e at the cell's own sizes (PERF.md section 6, PR 55; a
    # run's number is the worst of its three answers' lower quartiles). The
    # program over 33 seeds 0.0100 to 0.0305 and three runs at 0.0495, 0.0502
    # and 0.0847 (an answer whose tokens flipped together). The controls, each
    # planted through the harness's own entry, two seeds
    # (lax.reduce_precision for the rounding, which the compiler cannot fold
    # away): every matrix at float8_e4m3fn's precision (eps 2^-4, the nearest
    # precision below bfloat16) 2.53 and 2.95; the router read after the
    # attention 1.15 and 1.45; a window layer that sees everything 0.353 and
    # 1.06 (its 12,288-token answer); the ring written one row off reads the
    # sound program's, 0.011 and 0.026 (one key of 4,096: ring_placed_min
    # decides that control). 0.25 is three times the program's largest reading
    # and under a quarter of the two controls it is named for (float8, the
    # router).
    "logit_atol": 0.25,
    # generate_kv_job.cache_error's ``worst``, the prefill's rows. The
    # program over 40 seeds 0.0187 to 0.0407 (0.0032 in layer 0, before any
    # choice, rising to the eighth layer's). Controls, a run's number (its
    # worst layer): float8 1.26 (0.42 in layer 0); the router after the
    # attention 1.21 (0.97 and more from layer 1 on); a global layer with
    # positions 1.18 (0.9 in layer 0); **a window layer that sees everything
    # 0.59 and 0.60** (0.19 in layer 2, the first that reads a window
    # layer's output: every row past position 4,096 moves; named for this
    # number); the ring one row off reads the sound program's, 0.028 (the
    # prefill places its rows itself). 0.12 is three times the program's
    # largest reading and a fifth of the nearest control's.
    "cache_rtol": 0.12,
    # generate_kv_job.cache_error's ``placed``: of the rows the decode steps
    # wrote into a window layer's ring, the share nearer to the reference's k
    # at their own position than one before or after, the smallest layer of
    # the three answers. A drifted row is still its own position's; a ring
    # written one row off holds the position before, and a ring that is not
    # one keeps overwriting its last row. The program over 41 seeds 0.919 to
    # 0.997 and one run at 0.890 (a repeated token's neighbours differ by one
    # step of rotation, and a few rows that drifted far lie nearer the next);
    # the ring one row off 0.000; a window layer that sees everything 0.243
    # and 0.214 (0.514 in its 3,968-token answer, whose steps cross the
    # window). 0.6 leaves the program 0.29 and the nearest control 0.36 (0.8,
    # which left the first 28 seeds 0.13, until the thirty-ninth read
    # 0.890).
    "ring_placed_min": 0.6,
    # A row is clear where every router below it chose that token by this
    # much of its logits' spread (smallthinker_reference.route): at 0.1 a
    # third of layer 1's rows are clear, a seventh of layer 2's, one in a
    # hundred of layer 4's and a handful of layer 7's; layer 0's all are. At
    # 0.05 clear rows read 0.2 to 0.4 in every sound run (bfloat16 does reach
    # a margin of a twentieth by the third layer).
    "clear_margin": 0.1,
    # generate_kv_job.cache_error's ``clear``: the largest error of layer 0's
    # rows and of the clear rows a prefill wrote. The program over 30 seeds
    # (90 sequences) 0.023 to 0.080 and one run at 0.128 (layer 0's rows
    # 0.004); it is the largest of tens of thousands of rows, so its tail is
    # long. The controls' readings are in PERF.md section 6 (PR 55: the ring
    # one row off 1.50, a window layer that sees everything 1.45). 0.4 is
    # three times the largest reading and under a third of either control
    # (0.2, two and a half times the first ten seeds' largest, until the
    # eighteenth read 0.128).
    "clear_rtol": 0.4,
    # ``clear_steps``: the upper quartile of the clear rows the decode steps
    # wrote (layers 1 and up together; 57 to 687 rows an answer). The program
    # over 17 seeds (51 answers) 0.010 to 0.022, 0.033 and 0.044 (two answers
    # whose tokens flipped together: 0.25 at the first's ninetieth
    # percentile); the ring one row off 1.41, a window layer that sees
    # everything 1.44. 0.1 is over twice the largest reading.
    "clear_steps_rtol": 0.1,
    # No cell trains this configuration (the window has no backward pass in
    # the kernel): the dense decoder's limits stand for the CPU comparison of
    # the reference with itself under jax.grad.
    "loss_rtol": 5e-3,
    "grad_norm_rtol": 3e-2,
}


def _program_kinds():
    """The program's names of the two layer shapes, ``None`` on a program
    from before the window, the early router and the mixture in the decode
    loop."""
    from ray_tpu.models import transformer
    names = ("WINDOW_MOE", "GLOBAL_MOE", "prefill", "decode_step")
    if not all(hasattr(transformer, n) for n in names):
        return None
    return transformer.WINDOW_MOE, transformer.GLOBAL_MOE


def dims(config: Dict[str, Any], job: str, chips: int) -> Dict[str, Any]:
    """The sizes a cell runs: the published keys with the cut that
    ``reduced`` lists for this (job, chips): the depth alone, as the
    published indices of the layers kept (``published_layers``). A
    ``ManifestError`` on a program from before the two kinds of layer: the
    harness asks for the sizes before it takes a chip, so such a program is
    refused at once."""
    name = config.get("name")
    if _program_kinds() is None:
        raise ManifestError(
            f"configuration {name!r}: this program's transformer has no "
            "window attention beside global attention, no early router and "
            "no mixture in its decode loop (layer kinds 'window_moe', "
            "'global_moe'): it cannot run this configuration")
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
    rope, window = config["rope_layout"], config["sliding_window_layout"]
    published = int(config["num_hidden_layers"])
    if len(rope) != published or rope != window:
        raise ManifestError(
            f"configuration {name!r}: rope_layout and sliding_window_layout "
            f"are not one list of {published}: the program has a layer that "
            "rotates and looks through the window, and one that does neither")
    kept = [int(i) for i in cut.get("published_layers", range(published))]
    depth = int(cut.get("num_hidden_layers", published))
    if (len(kept) != depth or kept != sorted(set(kept))
            or not all(0 <= i < published for i in kept)):
        raise ManifestError(
            f"configuration {name!r}: published_layers {kept} are not "
            f"{depth} rising indices into the {published} published layers")
    return {
        "vocab_size": int(config["vocab_size"]),
        "d_model": int(config["hidden_size"]),
        "n_layers": depth,
        "n_heads": int(config["num_attention_heads"]),
        "n_kv_heads": int(config["num_key_value_heads"]),
        "head_dim": int(config["head_dim"]),
        "expert_width": int(config["moe_ffn_hidden_size"]),
        "n_experts": int(config["moe_num_primary_experts"]),
        "top_k": int(config["moe_num_active_primary_experts"]),
        "window": int(config["sliding_window_size"]),
        "rope_theta": float(config["rope_theta"]),
        "rms_norm_eps": float(config["rms_norm_eps"]),
        "layer_types": [WINDOW if window[i] else GLOBAL for i in kept],
        "layer_ids": kept,
    }


def program_config(dims: Dict[str, Any], seq_len: int, opts: Dict[str, Any]):
    """``TransformerConfig`` for a cell's sizes and its ``model`` options."""
    import jax.numpy as jnp
    from ray_tpu.models.transformer import TransformerConfig
    from ray_tpu.parallel.expert import ExpertConfig
    window_moe, global_moe = _program_kinds()
    n = dims["n_experts"]
    return TransformerConfig(
        vocab_size=dims["vocab_size"], d_model=dims["d_model"],
        n_layers=dims["n_layers"], n_heads=dims["n_heads"],
        n_kv_heads=dims["n_kv_heads"], head_width=dims["head_dim"],
        max_seq_len=seq_len,
        dtype=jnp.dtype(opts.get("dtype", "bfloat16")), remat=False,
        use_flash=bool(opts.get("use_flash", True)),
        rope_theta=dims["rope_theta"], norm_eps=dims["rms_norm_eps"],
        layer_kinds=tuple(window_moe if t == WINDOW else global_moe
                          for t in dims["layer_types"]),
        layer_ids=tuple(dims["layer_ids"]), window=dims["window"],
        # a softmax over the top_k largest logits is the softmax over all of
        # them, the top_k largest, renormalised
        experts=ExpertConfig(
            n_routed=n, n_zero=0, top_k=dims["top_k"], scale=1.0,
            width=dims["expert_width"], held=(0, n), score="softmax",
            normalize=True, activation="relu"))


def reference_params(key, dims: Dict[str, Any], seq_len: int):
    """The key: the reference draws every weight from it where it is
    used."""
    return key
