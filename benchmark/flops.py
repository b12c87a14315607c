"""Operations and bytes the algorithm needs, from shapes alone.

Kept with the benchmark so that every PR converts a rate to a utilization
the same way. Nothing here counts recomputation (``remat``): model FLOP/s
utilization is ``train_tokens_per_s * train_flops_per_token / (chips *
peak)``. ``dims`` is a configuration as ``manifest.model_dims`` gives it.
"""

from __future__ import annotations

from typing import Dict

MATMUL = 2          # one multiply and one add for each weight and token
TRAIN_PASSES = 3    # forward, and a backward of twice its cost


def layer_matmul_params(dims: Dict[str, int]) -> int:
    """Weights of one block that a token is multiplied by: q, k, v and o
    projections and the three SwiGLU matrices."""
    d, h, kvh, hd, f = (dims["d_model"], dims["n_heads"], dims["n_kv_heads"],
                        dims["head_dim"], dims["d_ff"])
    return d * h * hd + 2 * d * kvh * hd + h * hd * d + 3 * d * f


def matmul_params(dims: Dict[str, int]) -> int:
    """Every weight a token is multiplied by: the blocks and the untied
    head. The embedding is a lookup, not a multiplication."""
    return (dims["n_layers"] * layer_matmul_params(dims)
            + dims["d_model"] * dims["vocab_size"])


def total_params(dims: Dict[str, int]) -> int:
    """All parameters held: blocks with their two norms, embedding, final
    norm, head."""
    d = dims["d_model"]
    return (dims["n_layers"] * (layer_matmul_params(dims) + 2 * d)
            + 2 * d * dims["vocab_size"] + d)


def attention_fwd_flops(batch: int, seq: int, n_heads: int,
                        head_dim: int) -> float:
    """Causal attention forward over ``batch`` sequences of ``seq``: two
    matrix products (QK^T and PV) of 2*seq*seq*head_dim operations a head
    each, of which the causal mask needs half."""
    return 2 * causal_matmul_flops(batch, seq, n_heads, head_dim)


def causal_matmul_flops(batch: int, seq: int, n_heads: int,
                        head_dim: int) -> float:
    """One seq x seq x head_dim product for every head, lower triangle
    only."""
    return MATMUL * batch * n_heads * seq * seq * head_dim / 2


def forward_flops_per_token(dims: Dict[str, int], seq: int) -> float:
    """One forward pass, for each token of a sequence of ``seq`` tokens."""
    attn = dims["n_layers"] * attention_fwd_flops(
        1, seq, dims["n_heads"], dims["head_dim"]) / seq
    return MATMUL * matmul_params(dims) + attn


def train_flops_per_token(dims: Dict[str, int], seq: int) -> float:
    """Forward and backward, no recomputation counted."""
    return TRAIN_PASSES * forward_flops_per_token(dims, seq)


def mfu_pct(tokens_per_s: float, flops_per_token: float, chips: int,
            peak_flops_per_s: float) -> float:
    return 100.0 * tokens_per_s * flops_per_token / (chips * peak_flops_per_s)


# -- the flash kernel's three calls ----------------------------------------
# Matrix products each call cannot do without, given what it is handed:
# forward QK^T, PV; dq rebuilds P (QK^T) and dP (dO V^T) and makes dQ;
# dk/dv rebuilds the same two and makes dV and dK.
FLASH_MATMULS = {"fwd": 2, "dq": 3, "dkv": 4}


def flash_call_flops(kind: str, batch: int, seq: int, n_heads: int,
                     head_dim: int) -> float:
    return FLASH_MATMULS[kind] * causal_matmul_flops(batch, seq, n_heads,
                                                     head_dim)


def flash_call_bytes(kind: str, batch: int, seq: int, n_heads: int,
                     head_dim: int, itemsize: int = 2) -> float:
    """Bytes each call must move once: its [batch, seq, heads, head_dim]
    operands and results (the kernel is handed K and V already repeated to
    ``n_heads``), and the float32 row statistics."""
    tensor = batch * seq * n_heads * head_dim * itemsize
    rows = batch * seq * n_heads * 4
    tensors = {"fwd": 4,      # q k v -> o
               "dq": 6,       # q k v o do -> dq
               "dkv": 7}[kind]  # q k v o do -> dk dv
    return tensors * tensor + 2 * rows


def flash_min_seconds(kind: str, batch: int, seq: int, n_heads: int,
                      head_dim: int, peak_flops_per_s: float,
                      peak_bytes_per_s: float) -> Dict[str, float]:
    """The least time the chip could take for one call, and which bound
    applies."""
    by_flops = flash_call_flops(kind, batch, seq, n_heads,
                                head_dim) / peak_flops_per_s
    by_bytes = flash_call_bytes(kind, batch, seq, n_heads,
                                head_dim) / peak_bytes_per_s
    return {"seconds": max(by_flops, by_bytes),
            "bound": "flops" if by_flops >= by_bytes else "bytes"}
