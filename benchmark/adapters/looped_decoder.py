"""The adapter of the looped decoder (``OuroForCausalLM``, LoopLM): the
dense decoder's blocks with norms on both sides of every sub-layer, the
whole stack applied ``total_ut_steps`` times with the same weights, the
final norm between passes, an exit gate and its exit-weighted loss. Its
program configuration is ``ray_tpu.models.transformer.TransformerConfig``
with ``n_passes``, ``post_norm``, ``norm_eps`` and ``exit_beta`` set, and
its reference is ``benchmark/looped_reference.py``."""

from __future__ import annotations

import dataclasses
from typing import Any, Dict

from benchmark.adapters import dense_decoder
from benchmark.looped_reference import (last_logits,  # noqa: F401
                                        loss_and_grad_norm)

# Argued for T x L = 28 block applications (4 passes over 7 layers) in
# bfloat16 (eps 2^-8 = 3.9e-3) against float32. Two things differ from the
# dense decoder's argument. Every sub-layer's output is normed before it is
# added, and the stream is normed again after every pass, so a rounding
# error does not ride a growing residual: each block adds an error of about
# eps relative to a unit-RMS term, and 28 of them add to about eps *
# sqrt(28) = 2e-2 of the stream. And the loss is a mean over 2,048 positions
# of four cross entropies weighted by exit probabilities that sum to 1, less
# 0.05 of an entropy: the stream's error mostly cancels in it, much more
# than in the dense decoder's single cross entropy of an un-normed stream.
# Each limit is set from two readings on the v5e at the cell's own sizes
# (PERF.md section 6, PR 30): the program's largest gap over its seeds, and
# the gap of the same program with its weights rounded to float8_e4m3fn
# (eps 2^-4), which has to fall outside.
TOLERANCES = {
    # The served logits are the fourth pass's: about unit-normal over 49,152
    # rows, off by about 2e-2 * 4.4 = 0.09, and by more where two near-equal
    # logits swap. Read on the chip, 2 prompts of 1,024 tokens over three
    # seeds: 0.106 to 0.126; with 8-bit weights 1.55 to 1.76. A missing pass
    # or a missing inter-pass norm moves every logit by order 1.
    "logit_atol": 0.3,
    # Read on the chip over 20 seeds: at most 3.8e-5 (relative; root mean
    # square 1.6e-5); with 8-bit weights 1.1e-4 to 2.1e-4 over three seeds.
    # The precision hardly moves this loss, so the limit is about three
    # times the first reading. What is left out of the mathematics moves it
    # far more: the entropy term alone is 0.05 * 1.05 = 0.05 of a loss near
    # 11.25 (5e-3 relative), an exit dropped from the sum a quarter of it.
    "loss_rtol": 1e-4,
    # A gradient norm does not average rounding away, and every block
    # weight's gradient is a sum over four uses. Read on the chip over 20
    # seeds: at most 0.31% (root mean square 0.12%; the dense decoder reads
    # 0.7%, mostly its epsilon); with 8-bit weights 0.13% to 1.25%, which
    # overlaps, so it is the loss's limit that refuses that control. About
    # three times the first reading.
    "grad_norm_rtol": 1e-2,
}


def dims(config: Dict[str, Any], job: str, chips: int) -> Dict[str, Any]:
    """The dense decoder's sizes (the published keys with the cut that
    ``reduced`` lists for this (job, chips)) and the loop's: how many times
    the stack is applied, the exit threshold while serving, and the weight
    of the exit distribution's entropy in the loss (``assumed``: the
    published config has no such key)."""
    return {
        **dense_decoder.dims(config, job, chips),
        "total_ut_steps": int(config["total_ut_steps"]),
        "early_exit_threshold": float(config["early_exit_threshold"]),
        "exit_beta": float(config["assumed"]["exit_beta"]),
    }


def program_config(dims: Dict[str, Any], seq_len: int, opts: Dict[str, Any]):
    """``TransformerConfig`` for a cell's sizes and its ``model`` options:
    the dense decoder's with the loop, the second pair of norms, the
    published epsilon and the gate."""
    if dims["early_exit_threshold"] < 1.0:
        raise ValueError(
            f"early_exit_threshold {dims['early_exit_threshold']}: the "
            "program has no adaptive exit (it always runs every pass)")
    return dataclasses.replace(
        dense_decoder.program_config(dims, seq_len, opts),
        n_passes=dims["total_ut_steps"], post_norm=True,
        norm_eps=dims["rms_norm_eps"], exit_beta=dims["exit_beta"])
