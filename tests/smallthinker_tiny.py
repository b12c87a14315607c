"""SmallThinker's layer at toy widths, for the CPU tests: a ``head_dim`` that
is not ``d_model / n_heads``, a window of 8, 4 experts a token of 8, two
periods of one global and three window layers."""

TINY_DIMS = {
    "vocab_size": 96, "d_model": 48, "n_layers": 8, "n_heads": 4,
    "n_kv_heads": 2, "head_dim": 16, "expert_width": 24, "n_experts": 8,
    "top_k": 4, "window": 8, "rope_theta": 1.5e6, "rms_norm_eps": 1e-6,
    "layer_types": ["global", "window", "window", "window"] * 2,
    "layer_ids": list(range(8)),
}
