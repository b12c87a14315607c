"""Compiles for a described TPU v5e 2x2 host, without a chip: train steps.

The TPU compiler is installed where the tests run, and it compiles for a
topology that is described and not attached. These tests keep the main
path's programs compiling at their real widths: here the whole LM train step
on one chip, on three four-chip meshes and at the training cells' own sizes,
and the device scopes of the Mistral step and the InternLM2 forward; the
kernels alone are in ``test_chip_compile_kernels.py`` and the served
forwards in ``test_chip_compile_serve.py``. Each asserts the Pallas kernel is
in the compiled program (``tpu_custom_call``). Nothing runs, so they say
nothing about results or times.

Three files, because ``--dist loadfile`` gives a file to one worker and the
cases together take one worker over 250 s. Several processes may describe the
topology at once: the driver's command sets ``ALLOW_MULTIPLE_LIBTPU_LOAD=1``
(without it the TPU library takes a lock file, and a second process that
loads it fails; then run these three files in one process). The topology is
still described inside a fixture and never while a module is imported, so
every worker collects the same tests. ``topo``, ``no_compile_cache`` and
``mosaic`` are module-scoped fixtures of ``tests/conftest.py``; what the
three files lower, and their readers of a compiled module's text, are in
``tests/chip_programs.py``.

One more compile costs what its like costs here (one worker of six busy ones,
seconds): a kernel alone 1 to 5, a served forward at a cell's widths 5 to 20,
a train step at a cell's widths 30 to 70 (Mistral 48, Kanana-2 67). So a new
case that reads a program some test already compiles takes it from the
``compiled`` fixture below (keyed by the cell's name) and compiles nothing.
"""

import contextlib
import dataclasses
import math
import re

import jax
import pytest

from chip_programs import (ASSIGNED, CFG, CFG_GQA, KERNEL, cell_dims,
                           computations, first_token, loops, lower_cell_step,
                           lower_train_step, mesh_of, unscoped_work)
from ray_tpu.models import transformer


def test_train_step_compiles_on_one_chip(topo, mosaic):
    compiled = lower_train_step(mesh_of(topo.devices[:1], data=1),
                                 CFG_GQA).compile()
    assert KERNEL in compiled.as_text()
    mem = compiled.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            < 16 * 2 ** 30)


def test_looped_train_step_compiles_on_one_chip(topo, mosaic):
    """The looped decoder (the stack applied four times, sandwich norms,
    the exit gate and its loss): the kernel is inside two nested scans and
    the per-exit heads inside ``weighted_nll``'s scan."""
    looped = dataclasses.replace(CFG, n_layers=2, n_passes=4, post_norm=True,
                                 exit_beta=0.05)
    compiled = lower_train_step(mesh_of(topo.devices[:1], data=1),
                                 looped).compile()
    text = compiled.as_text()
    assert KERNEL in text
    # the shared weights' gradient is summed where each layer makes it: no
    # operation adds a whole stacked float32 leaf to another (autodiff's
    # ``add_any`` in the passes' loop, a ``select_add_fusion`` over
    # f32[2, ...] a leaf on this chip)
    blocks = jax.eval_shape(lambda: transformer.init_params(
        jax.random.PRNGKey(0), looped))["blocks"]
    stacked = {"f32[" + ",".join(map(str, p.shape)) + "]"
               for p in jax.tree.leaves(blocks)}
    assert [line for line in text.splitlines()
            if "add_any" in line and " = " in line
            and line.split(" = ")[1].split("{")[0] in stacked] == []
    # one exit's float32 logits at a time: [8, 1024, 32000] is 1.05 GB, and
    # four of them alive with their backward would not leave it here
    mem = compiled.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            < 8 * 2 ** 30)


# A one-chip training cell of the benchmark and the most its step may take
# by ``memory_analysis()`` (arguments + temporaries): what the step took
# before the loss head computed its own gradient (PERF.md section 4: 13.51 GB
# at 2 layers, 14.80 GB at 7 layers x 4 passes). The head may not cost memory.
CELL_STEP_BYTES = {"mistral7b-train-4k": 13.51e9, "ouro2.6b-train-4k": 14.80e9}


@pytest.fixture(scope="module")
def compiled(topo, on_chip, mosaic):
    """``compiled(name)``: the executable of a one-chip training cell's step
    (by the cell's name) or of ``internlm2-forward``, the offline cell's
    served program at its largest shape, compiled the first time a test of
    this module asks for it. A test that only reads a program's text or its
    ``memory_analysis()`` takes it from here."""
    made = {}

    def get(name):
        if name not in made:
            made[name] = _lower_program(topo, on_chip, name).compile()
        return made[name]
    return get


@pytest.mark.parametrize("cell_name", list(CELL_STEP_BYTES))
def test_benchmark_cells_train_step_fits_what_it_took(compiled, cell_name):
    program = compiled(cell_name)
    assert KERNEL in program.as_text()
    mem = program.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            <= CELL_STEP_BYTES[cell_name])


@pytest.mark.parametrize("axes", [
    dict(data=4), dict(data=2, tensor=2), dict(data=1, fsdp=4)],
    ids=["data4", "data2xtensor2", "fsdp4"])
def test_train_step_compiles_on_four_chip_mesh(topo, mosaic, axes):
    text = lower_train_step(mesh_of(topo.devices, **axes),
                             CFG_GQA).compile().as_text()
    assert KERNEL in text
    # the gradient reduction over the batch axes
    assert "all-reduce" in text or "reduce-scatter" in text


# -- the device scopes (``metric_names.DEVICE_SCOPES``) ----------------------


def _lower_program(topo, on_chip, name):
    return (first_token(on_chip, "internlm2-serve-offline")[0]
            if name == "internlm2-forward" else lower_cell_step(topo, name))


PROGRAMS = {"mistral-step": "mistral7b-train-4k",
            "internlm2-forward": "internlm2-forward"}


@pytest.mark.parametrize("program", sorted(PROGRAMS))
def test_the_chips_matmuls_and_kernels_all_name_a_scope(compiled, program):
    """What the compiler for the chip keeps of the scopes: every matmul
    (alone or as a fusion's root) and every Mosaic call of the Mistral step
    and of the InternLM2 forward carries one in its ``op_name``; what does
    not is the compiler's own (copies, slices of the stacked weights,
    multi-output fusions, which carry no metadata at all)."""
    text = compiled(PROGRAMS[program]).as_text()
    assert KERNEL in text and 'op_name="jit(' in text
    left = unscoped_work(text)
    assert [entry for entry in left
            if entry[0] in ("convolution", "convolution fusion", "mosaic")
            ] == []
    # and the list is no empty claim: the scan's own operations are on it
    assert any("dynamic" in line for _, line in left)


@pytest.mark.parametrize("program", sorted(PROGRAMS))
def test_the_scopes_change_an_operations_metadata_and_nothing_else(
        topo, on_chip, compiled, monkeypatch, program):
    """The witness that the programs are equal: with every
    ``metadata={...}`` struck out, and the tables of files, functions and
    stack frames at the module's head that ``stack_frame_id`` indexes, the
    optimized module is the same string with the scopes and with
    ``jax.named_scope`` patched to a null context. No instruction's name
    turned out to come from a scope, so nothing else is normalised."""
    def strip(text):
        assert "metadata={" in text and "\nStackFrames\n" in text
        text = re.sub(r"(?ms)^FileNames\n.*?^StackFrames\n.*?\n\n", "",
                      text)
        return re.sub(r",? ?metadata=\{[^{}]*\}", "", text)

    scoped = compiled(PROGRAMS[program]).as_text()
    assert "/attn/core/" in scoped and "/mlp/" in scoped
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    bare = _lower_program(topo, on_chip,
                          PROGRAMS[program]).compile().as_text()
    assert "/attn/" not in bare and "/mlp/" not in bare
    assert strip(bare) == strip(scoped)


# -- the latent mixer among the mixer-and-FFN kinds (Kanana-2): the trained step ----


# what ``memory_analysis()`` read of the cell's step at 1 x 8,192 tokens
# (arguments + temporaries + outputs - aliased, GB) when ISSUE 48's rule fixed
# the depth, as the configuration's ``reduced["train.1"]["why"]`` states it:
# the rule takes 5 mixture layers at 15.0 GB or less, else 4 (5 read 15.68)
KANANA2_STEP_GB = 13.60
# what it reads since the layers' checkpoint keeps the flash forward's output
# and log-sum-exp (PR 49): 0.34 GB of kept arrays over the five layers by the
# compiler's own peak (``peak_memory_in_bytes`` 11.65 -> 12.01), 0.62 GB by
# the temporaries it sets aside
KANANA2_STEP_KEPT_GB = 14.23


def _flash_calls(lines):
    """The flash kernels a computation calls itself, by the instructions'
    names (``%flash_fwd.31 = ... custom-call(``), sorted."""
    return sorted(m.group(1).split(".")[0]
                  for m in map(ASSIGNED.match, lines)
                  if m and m.group(3) == "custom-call"
                  and m.group(1).startswith("flash_"))


def test_the_kanana2_cells_step_fits_and_writes_a_layers_gradient_once(
        compiled):
    """``kanana2-train-8k``'s step at its own sizes: the three flash kernels
    and the grouped products are in it, **the forward kernel once a layer**
    (each run's forward loop calls ``flash_fwd``, its backward loop
    ``flash_dq`` and ``flash_dkv`` and no ``flash_fwd``: the checkpoint
    keeps the first call's output and log-sum-exp); the compiler's account
    of its memory is what the rule that fixed the depth read and the kept
    arrays; and in the layers' backward
    loop a stacked float32 gradient is only ever written by a
    ``dynamic-update-slice`` of the layer's slice: no operation of a loop
    adds a whole stacked leaf to another (what a scan over the layers'
    indices with the tree closed over did at every step)."""
    program = compiled("kanana2-train-8k")
    text = program.as_text()
    assert "ragged-dot" in text
    bodies, entry = computations(text)
    # the dense first layer is a run of one, which the compiler unrolls
    # into the entry; the four mixture layers are a loop each way
    assert _flash_calls(bodies[entry]) == ["flash_dkv", "flash_dq",
                                           "flash_fwd"]
    assert sorted(_flash_calls(bodies[loop])
                  for loop in loops(bodies[entry])) == [
        ["flash_dkv", "flash_dq"], ["flash_fwd"]]
    assert len(_flash_calls(text.splitlines())) == 6
    mem = program.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
             + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    print(f"kanana2-train-8k: arguments {mem.argument_size_in_bytes / 1e9:.3f}"
          f" + temporaries {mem.temp_size_in_bytes / 1e9:.3f} GB = "
          f"{total / 1e9:.3f} GB")
    assert total <= 15.0e9
    assert total / 1e9 == pytest.approx(KANANA2_STEP_KEPT_GB, abs=0.15)
    cell, adapter, dims = cell_dims("kanana2-train-8k")
    assert f"{KANANA2_STEP_GB:.2f} GB" in cell.config["reduced"]["train.1"][
        "why"]
    # the stacked float32 leaves of the mixture layers, large ones
    cfg = adapter.program_config(dims, 8192, cell.deploy["model"])
    blocks = jax.eval_shape(lambda: transformer.init_params(
        jax.random.PRNGKey(0), cfg))["blocks"][transformer.LATENT_MOE]
    stacked = {"f32[" + ",".join(map(str, p.shape)) + "]"
               for p in jax.tree.leaves(blocks)
               if 4 * math.prod(p.shape[1:]) >= 2 ** 20}
    assert "f32[4,16,2048,768]" in stacked
    bodies, entry = computations(text)
    inside, todo = [], loops(bodies[entry])
    while todo:
        inside.append(todo.pop())
        todo += loops(bodies[inside[-1]])
    written, other = set(), []
    for name in inside:
        for line in bodies[name]:
            m = ASSIGNED.match(line)
            if not m or m.group(2).split("{")[0] not in stacked:
                continue
            result, shape, kind = m.groups()
            if kind in ("parameter", "get-tuple-element", "bitcast"):
                continue
            if "dynamic-update-slice" in result or kind == \
                    "dynamic-update-slice":
                written.add(shape.split("{")[0])
            else:
                other.append((name, result, kind))
    assert other == []
    assert written == stacked
