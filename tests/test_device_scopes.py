"""The scopes the model enters on the device (``jax.named_scope``,
``metric_names.DEVICE_SCOPES``): at tiny sizes on the CPU, every
architecture's compiled program carries its layers' names in its
operations' ``op_name``, forward and backward, and the names under
``ray_tpu/`` are the registry's, all of them."""

import ast
import dataclasses
import glob
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

import ray_tpu
from ray_tpu.models import transformer
from ray_tpu.models.transformer import TransformerConfig
from ray_tpu.observability.metric_names import (DEVICE_SCOPES,
                                                LATER_DEVICE_SCOPES, SPANS)
from ray_tpu.parallel import expert
from ray_tpu.train.step import make_lm_train_step
from test_mixed_stack import MIXED
from test_shortcut_layer import TINY as SHORTCUT_STACK

DENSE = TransformerConfig(vocab_size=128, d_model=64, n_layers=2, n_heads=4,
                          n_kv_heads=2, d_ff=96, max_seq_len=32,
                          dtype=jnp.bfloat16, use_flash=True, remat=True)
LOOPED = dataclasses.replace(DENSE, n_passes=4, post_norm=True,
                             exit_beta=0.05)
LAYER = {"embed", "attn", "core", "mlp", "head"}
# (configuration, tokens a sequence, trains, the scopes it should carry);
# the mixed stack past ``dense_len``, so that its sparse layer selects
ARCHITECTURES = {
    "dense": (DENSE, 32, True, LAYER | {"optimizer"}),
    "looped": (LOOPED, 32, True, LAYER | {"optimizer"}),
    "mixed": (MIXED, 64, False, LAYER),
    "shortcut": (SHORTCUT_STACK, 32, False,
                 LAYER | {"moe", "router", "experts"}),
}
# the operations that are a model's work whatever the compiler makes of
# the rest: a matmul, a grouped matmul, a kernel
WORK = ("dot_general", "ragged_dot", "pallas_call")


def _op_names(compiled):
    return set(re.findall(r'op_name="([^"]*)"', compiled.as_text()))


def _scopes(op_name):
    return [t for t in re.split(r"[/():]", op_name) if t in DEVICE_SCOPES]


def _served(cfg, length):
    params = jax.eval_shape(
        lambda: transformer.init_params(jax.random.PRNGKey(0), cfg))
    tokens = jax.ShapeDtypeStruct((2, length), jnp.int32)
    return jax.jit(lambda p, t: transformer.head(
        p, transformer.backbone(p, t, cfg), cfg)).lower(
            params, tokens).compile()


def _trained(cfg, length):
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    init_fn, step_fn, _ = make_lm_train_step(cfg, mesh)
    state = init_fn.eval_shape(jax.ShapeDtypeStruct((2,), jnp.uint32))
    tokens = jax.ShapeDtypeStruct((2, length + 1), jnp.int32)
    # __wrapped__: the jitted step under goodput.instrument_jit
    return step_fn.__wrapped__.lower(state, tokens).compile()


def test_the_scopes_are_single_tokens_and_no_host_spans_name():
    assert len(DEVICE_SCOPES) == 9 and len(LATER_DEVICE_SCOPES) == 7
    assert all(re.fullmatch(r"[a-z]+", s)
               for s in DEVICE_SCOPES | LATER_DEVICE_SCOPES)
    assert not (DEVICE_SCOPES | LATER_DEVICE_SCOPES) & SPANS
    assert not DEVICE_SCOPES & LATER_DEVICE_SCOPES


@pytest.mark.parametrize("name", sorted(ARCHITECTURES))
def test_the_served_forward_names_its_layers(name):
    cfg, length, _, want = ARCHITECTURES[name]
    names = _op_names(_served(cfg, length))
    found = {s for n in names for s in _scopes(n)}
    assert found == want - {"optimizer"}
    # the inner scopes sit inside their layer's
    for n in names:
        path = _scopes(n)
        assert "core" not in path or path[0] == "attn", n
        assert not {"router", "experts"} & set(path) or path[0] == "moe", n
    bare = sorted(n for n in names if any(w in n for w in WORK)
                  and not _scopes(n))
    assert bare == []


@pytest.mark.parametrize("name", sorted(
    n for n, arch in ARCHITECTURES.items() if arch[2]))
def test_the_training_step_names_its_layers_forward_and_backward(name):
    cfg, length, _, want = ARCHITECTURES[name]
    names = _op_names(_trained(cfg, length))
    forward = {s for n in names if "transpose(" not in n for s in _scopes(n)}
    backward = {s for n in names if "transpose(" in n for s in _scopes(n)}
    assert forward == want
    # the optimizer is differentiated by nobody
    assert backward == want - {"optimizer"}
    # what a custom_vjp's own backward does is under the layer's name too:
    # the loss head's, and the looped stack's sum of its shared gradient
    assert any(_scopes(n)[:1] == ["head"] for n in names
               if "transpose(" in n)
    bare = sorted(n for n in names if any(w in n for w in WORK)
                  and not _scopes(n))
    assert bare == []


def test_the_switch_layer_names_its_router_and_experts():
    mesh = Mesh(np.array(jax.devices()[:4]), ("expert",))
    x = jax.ShapeDtypeStruct((16, 8), jnp.float32)
    router = jax.ShapeDtypeStruct((8, 4), jnp.float32)
    w = jax.ShapeDtypeStruct((4, 8, 8), jnp.float32)
    lowered = jax.jit(lambda x, r, w: expert.moe_apply(
        x, r, w, lambda p, t: t @ p, mesh)).lower(x, router, w)
    # the lowered module names an operation from its function down (the
    # shard_map's body is one); at these sizes the CPU's compiler folds
    # the experts' matmul into an operation it names itself
    names = set(re.findall(r'loc\("([^"]+)"', lowered.as_text(
        debug_info=True)))
    assert {n for n in names if "dot_general" in n} == {
        "router/dot_general", "experts/vmap()/dot_general"}
    # the compiled one from the program down
    found = {tuple(_scopes(n)) for n in _op_names(lowered.compile())
             if n.startswith("jit(")}
    assert {("moe",), ("moe", "router")} <= found
    assert all(path[0] == "moe" for path in found if path)


def _sambay_programs():
    """The tiny SambaY stack's three programs: the whole forward, a prefill
    and a decode step (``tests/phi4flash_tiny.py``; the kernels on)."""
    import phi4flash_tiny as tiny
    cfg = tiny.config(use_flash=True)
    params = jax.eval_shape(
        lambda: transformer.init_params(jax.random.PRNGKey(0), cfg))
    tokens = jax.ShapeDtypeStruct((1, 16), jnp.int32)
    state = jax.eval_shape(lambda: transformer.init_decode_state(cfg, 2, 32))
    return {
        "forward": _served(cfg, 16),
        "prefill": jax.jit(lambda p, t, n: transformer.prefill(
            p, t, n, cfg)).lower(
                params, tokens,
                jax.ShapeDtypeStruct((1,), jnp.int32)).compile(),
        "decode_step": jax.jit(lambda p, t, s: transformer.decode_step(
            p, t, s, cfg)).lower(
                params, jax.ShapeDtypeStruct((2,), jnp.int32),
                state).compile()}


@pytest.mark.parametrize("program", ["forward", "prefill", "decode_step"])
def test_the_sambay_stack_names_its_mixers(program):
    """Phi-4-mini-flash's kinds: a scan is ``mamba`` (its recurrence ``core``
    inside), an attention ``attn`` with ``swa``, ``global`` or ``cross``
    inside it round its ``core``, a gated memory unit ``gmu``; every matmul
    and kernel of the three programs is under one of them."""
    scopes = DEVICE_SCOPES | LATER_DEVICE_SCOPES
    names = _op_names(_sambay_programs()[program])
    paths = {n: [t for t in re.split(r"[/():]", n) if t in scopes]
             for n in names}
    found = {s for path in paths.values() for s in path}
    want = {"embed", "mamba", "core", "attn", "swa", "global", "cross",
            "gmu", "mlp"}
    assert found == want | ({"head"} if program != "prefill" else set())
    for n, path in paths.items():
        assert not {"swa", "global", "cross"} & set(path) \
            or path[0] == "attn", n
        assert "core" not in path or path[0] in ("attn", "mamba"), n
        assert "gmu" not in path or path[0] == "gmu", n
    bare = sorted(n for n in names if any(w in n for w in WORK)
                  and not paths[n])
    assert bare == []


def _named_scope_calls():
    """Every ``jax.named_scope(...)`` call under ``ray_tpu/``: (file, line,
    the argument where it is a string literal)."""
    root = os.path.dirname(ray_tpu.__file__)
    for path in glob.glob(os.path.join(root, "**", "*.py"), recursive=True):
        with open(path) as f:
            tree = ast.parse(f.read(), path)
        for node in ast.walk(tree):
            if (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr == "named_scope"):
                arg = node.args[0] if node.args else None
                yield (os.path.relpath(path, root), node.lineno,
                       arg.value if isinstance(arg, ast.Constant) else None)


def test_every_scope_entered_is_declared_and_every_declared_one_entered():
    calls = list(_named_scope_calls())
    # a name worked out at run time would slip past the registry
    assert [c for c in calls if not isinstance(c[2], str)] == []
    used = {c[2] for c in calls}
    assert used == DEVICE_SCOPES | LATER_DEVICE_SCOPES
    files = {c[0] for c in calls}
    assert {os.path.join("models", "transformer.py"),
            os.path.join("parallel", "expert.py"),
            os.path.join("train", "step.py")} <= files
