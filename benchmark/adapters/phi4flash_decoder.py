"""The adapter of Phi-4-mini-flash-reasoning's decoder (``phi4flash``, SambaY;
``microsoft/Phi-4-mini-flash-reasoning``): Mamba-1 scans and differential
attention through a window, one full differential attention whose K and V
seven cross layers read, gated memory units over the last scan's output, a
dense SwiGLU FFN in every layer, LayerNorms with a bias and a tied head. Its
program configuration is ``ray_tpu.models.transformer.TransformerConfig`` with
``layer_kinds`` of ``transformer.SAMBAY``, ``mamba1``, ``layer_norm`` and
``window`` set, and its reference is ``benchmark/phi4flash_reference.py``,
streamed: ``reference_params`` hands on the seed's key and the reference draws
a layer's float32 weights where it uses them (the whole float32 tree is 15.4
GB). The configuration is served by the ``generate`` job
(``benchmark/generate_job.py``, as it is), which compares a whole answer's
logits and the scan layers' recurrent state."""

from __future__ import annotations

from typing import Any, Dict

from benchmark import phi4flash_reference as reference
from benchmark.manifest import ManifestError
from benchmark.phi4flash_reference import (last_logits,  # noqa: F401
                                           logits_from, loss_and_grad_norm)

# What the program's layers compute, as the published config spells it; any
# other value is a layer the program (or the reference) does not have.
_LAYER_AS_BUILT = {
    "model_type": "phi4flash", "mb_per_layer": 2, "hidden_act": "silu",
    "mlp_bias": False, "lm_head_bias": False, "tie_word_embeddings": True,
}

# Argued for 32 layers in bfloat16 (eps 2^-8 = 3.9e-3) against float32, at
# prompts of three buckets and 2,048 generated positions each (the traffic's
# longest answer), decoded among 93 other occupied slots, over the whole
# vocabulary. The head is tied to an embedding drawn at 0.02 and read as it
# is: a logit is about N(0, 0.02 sqrt(2560) = 1.0) and the largest of 200,064
# near 4.5. No choice is discrete but the answer's own tokens, which the
# reference is handed. The subtraction of two softmax maps amplifies a map's
# rounding by 1 / (1 - lambda) and the sub-norm divides by what is left, so
# the logits' limit is argued from the spread over seeds on the chip, not
# from one run.
TOLERANCES = {
    # Read on the v5e at the cell's own sizes (PERF.md section 6, PR 61; a
    # position's number is generate_job.compare's two terms, a run's the
    # worst of its 3 x 2,048 positions). The program: a run's worst 0.196 to
    # 0.326 over twenty-four runs on twenty-four seeds (median 0.24; a
    # logit's spread is 1.0: the relative error of Granite's 0.012-0.024 of
    # 0.11). The same program with every matrix rounded to float8_e4m3fn's
    # precision (eps 2^-4, the nearest precision below bfloat16;
    # lax.reduce_precision, which the compiler cannot fold away): 7.97 and
    # 8.43 in two runs, every one of the 6,144 positions off the reference's
    # best. 0.8 is 2.5 times the program's largest reading and a tenth of the
    # control's. **The recurrent state kept at bfloat16's precision reads
    # 0.207 and 0.239, under the limit: the logits do not decide that
    # control.**
    "logit_atol": 0.8,
    # generate_job.state_error over the nine scan layers' states [9, 16,
    # 5120] after an answer's last step, against the reference's after the
    # same tokens (the reference's [d_inner, d_state] laid as the program
    # keeps it): one norm over the nine layers. The program: a run's worst
    # 0.027 to 0.041 over twenty-four runs (the state inherits the rounding
    # of the bfloat16 activations and weights that feed x, B, C and dt
    # through up to 17 layers). Float8 weights: 1.14 and 1.16. 0.07 is 1.7
    # times the program's largest reading and a sixteenth of the control's.
    # **The state rounded to bfloat16's precision after the prefill and after
    # every step reads 0.045 to 0.052 (three runs), 1.3 times the program's
    # and under this limit: this cell's comparison does not decide that
    # control either.** The two errors add in quadrature, and a limit between
    # 0.041 and 0.045 would leave neither side room. The job takes the served
    # state from the replica and folds the nine layers into one norm, so a
    # limit a layer (layer 0's state has no bfloat16 layer before it) needs a
    # job module of this cell's own: PERF.md section 7. The CPU test decides
    # it at float32 (test_phi4flash_engine.py); the float8 control fails both
    # limits here.
    "state_rtol": 0.07,
    # No cell trains this configuration (the window has no backward pass):
    # the dense decoder's limits stand for the CPU comparison of the
    # reference with itself under jax.grad.
    "loss_rtol": 5e-3,
    "grad_norm_rtol": 3e-2,
}


def _program_kinds():
    """The program's names of the five layer shapes by the reference's,
    ``None`` on a program from before SambaY's kinds."""
    from ray_tpu.models import transformer
    names = ("MAMBA1", "DIFF_WINDOW", "DIFF_GLOBAL", "DIFF_CROSS", "GMU")
    if not all(hasattr(transformer, n) for n in names):
        return None
    return {kind: getattr(transformer, n)
            for kind, n in zip(reference.TYPES, names)}


def dims(config: Dict[str, Any], job: str, chips: int) -> Dict[str, Any]:
    """The sizes a cell runs: the published keys and the ``phi4flash``
    defaults the row leaves out (``assumed.mamba_defaults``), with the cut
    that ``reduced`` lists for this (job, chips): the depth alone
    (``num_hidden_layers``; nothing is cut for ``generate.1``). A
    ``ManifestError`` on a program from before SambaY's kinds: the harness
    asks for the sizes before it takes a chip, so such a program is refused at
    once."""
    name = config.get("name")
    if _program_kinds() is None:
        raise ManifestError(
            f"configuration {name!r}: this program's transformer has no "
            "Mamba-1 scan, differential attention, shared K/V cache or gated "
            "memory unit (layer kinds 'mamba1', 'diff_window', 'diff_global', "
            "'diff_cross', 'gmu'): it cannot run this configuration")
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
    depth = int(cuts[key].get("num_hidden_layers",
                              config["num_hidden_layers"]))
    sizes = config.get("assumed", {}).get("mamba_sizes", {})
    missing = [k for k in ("d_state", "d_conv", "expand", "dt_rank")
               if k not in sizes]
    if missing or depth % 2:
        raise ManifestError(
            f"configuration {name!r}: assumed.mamba_sizes lacks {missing}, "
            f"or the depth {depth} is odd")
    d, heads = int(config["hidden_size"]), int(config["num_attention_heads"])
    return {
        "vocab_size": int(config["vocab_size"]),
        "d_model": d,
        "n_layers": depth,
        "n_heads": heads,
        "n_kv_heads": int(config["num_key_value_heads"]),
        "head_dim": d // heads,
        "d_ff": int(config["intermediate_size"]),
        "eps": float(config["layer_norm_eps"]),
        "window": int(config["sliding_window"]),
        "d_inner": int(sizes["expand"]) * d,
        "d_state": int(sizes["d_state"]),
        "dt_rank": int(sizes["dt_rank"]),
        "conv_width": int(sizes["d_conv"]),
        "layer_types": list(reference.layer_types(
            depth, int(config["mb_per_layer"]))),
        "layer_ids": list(range(depth)),
    }


def program_config(dims: Dict[str, Any], seq_len: int, opts: Dict[str, Any]):
    """``TransformerConfig`` for a cell's sizes and its ``model`` options."""
    import jax.numpy as jnp
    from ray_tpu.models.transformer import Mamba1Config, TransformerConfig
    kinds = _program_kinds()
    return TransformerConfig(
        vocab_size=dims["vocab_size"], d_model=dims["d_model"],
        n_layers=dims["n_layers"], n_heads=dims["n_heads"],
        n_kv_heads=dims["n_kv_heads"], d_ff=dims["d_ff"],
        max_seq_len=seq_len,
        dtype=jnp.dtype(opts.get("dtype", "bfloat16")), remat=False,
        use_flash=bool(opts.get("use_flash", True)), norm_eps=dims["eps"],
        layer_kinds=tuple(kinds[t] for t in dims["layer_types"]),
        layer_ids=tuple(dims["layer_ids"]), tie_embeddings=True, rope=False,
        head_width=dims["head_dim"], window=dims["window"], layer_norm=True,
        mamba1=Mamba1Config(d_inner=dims["d_inner"], d_state=dims["d_state"],
                            dt_rank=dims["dt_rank"],
                            conv_width=dims["conv_width"]))


def reference_params(key, dims: Dict[str, Any], seq_len: int):
    """The key: the reference draws every weight from it where it is
    used."""
    return key


def logits_and_state_from(key, tokens, first, n: int, dims):
    """The reference's, its scan layers' states [n_mamba, d_inner, d_state]
    laid as the program keeps them, [n_mamba, d_state, d_inner]."""
    logits, state = reference.logits_and_state_from(key, tokens, first, n,
                                                    dims)
    return logits, state.swapaxes(1, 2)
