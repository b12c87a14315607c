"""A tiny Phi-4-mini-flash-shaped stack for the tests of SambaY's kinds: 8
layers, so that the stack still has every kind (0-3 the self-decoder: scan,
window, scan, window; 4 the scan whose output is kept; 5 the full attention
whose K and V are kept; 6 a gated memory unit; 7 a cross layer), a window of
8: the sizes ``phi4flash_decoder.dims`` would give, the program's
configuration of them and a seeded float32 tree, made once a process."""

import functools

import jax

from benchmark import phi4flash_reference
from benchmark.adapters import phi4flash_decoder
from ray_tpu.models import transformer

DIMS = dict(
    vocab_size=64, d_model=32, n_layers=8, n_heads=8, n_kv_heads=4,
    head_dim=4, d_ff=48, eps=1e-5, window=8, d_inner=64, d_state=4,
    dt_rank=2, conv_width=4,
    layer_types=list(phi4flash_reference.layer_types(8)),
    layer_ids=list(range(8)))
SEED = 3


def config(use_flash: bool = False, dims=DIMS):
    return phi4flash_decoder.program_config(
        dims, 64, {"dtype": "float32", "use_flash": use_flash})


@functools.lru_cache(maxsize=None)
def params():
    return jax.jit(lambda k: transformer.init_params(k, config()))(
        jax.random.PRNGKey(SEED))
