"""A tiny Granite-shaped stack (Mamba-2 mixers round one attention layer) for
the tests of the decode loop: the sizes ``granite_decoder.dims`` would give,
the program's configuration of them and a seeded float32 tree, made once a
process."""

import functools

import jax

from benchmark.adapters import granite_decoder
from ray_tpu.models import transformer

DIMS = dict(
    vocab_size=64, d_model=32, n_layers=4, n_heads=4, n_kv_heads=2,
    head_dim=8, d_ff=48, rms_norm_eps=1e-5,
    layer_types=["mamba", "mamba", "attention", "mamba"],
    layer_ids=[0, 1, 2, 3], mamba_heads=4, mamba_head_dim=16, d_state=8,
    conv_width=4, chunk=8, attn_scale=0.1, embed_scale=12.0,
    residual_scale=0.22, logit_scale=0.125)
SEED = 3


def config(use_flash: bool = False):
    return granite_decoder.program_config(
        DIMS, 64, {"dtype": "float32", "use_flash": use_flash})


@functools.lru_cache(maxsize=None)
def params():
    return jax.jit(lambda k: transformer.init_params(k, config()))(
        jax.random.PRNGKey(SEED))
