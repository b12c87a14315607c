"""PR 60: one entry a reader, a closed loop level by construction, and the
decode step's mixture roofline pointed at what runs.

``per_layer_before_pr60.json`` is the list as PR 59 left it (128 entries:
name, reader, parameters, ``moves``, cells). Every cell must still report
each reader it reported then, under whatever name, unless ``CHANGES.md``
retires it; no two entries may repeat a reader, its parameters and its
``moves``; and the list keeps room for a later cell's entries."""

import hashlib
import json
import os
import tempfile

import numpy as np
import pytest

import benchmark_tiny
from benchmark import (device_scopes, generate_job, manifest, program_spans,
                       reducers, smallthinker_counts, trace_reduce, traffic)
from benchmark.program_spans import Span
from test_benchmark_device_scopes import (BASE_NS, T0_NS, _event, _field,
                                          _metadata, _plane, _written)

REPO = benchmark_tiny.REPO
HERE = os.path.dirname(os.path.abspath(__file__))
ROOM = 100          # ISSUE 60: at most 100 of the driver's 128

with open(os.path.join(HERE, "per_layer_before_pr60.json")) as _f:
    BEFORE = json.load(_f)

# retired, each with its evidence in CHANGES.md; what its cells report instead
RETIRED = {
    "replica_init_s.serve": "serve_startup_s.serve",
    "replica_init_s.longdoc": "serve_startup_s.serve",
    "replica_init_s.longcat": "serve_startup_s.serve",
    "replica_init_s.lfm2": "serve_startup_s.serve",
    "replica_init_s.granite": "serve_startup_s.serve",
    "embed_share_pct.kanana2": None,
}
# folded: the entry that went -> the first of its kind, which its cells
# report now
FOLDED = {
    "fit_startup_s.kanana2": "fit_startup_s.train",
    "serve_startup_s.longdoc": "serve_startup_s.serve",
    "serve_startup_s.granite": "serve_startup_s.serve",
    "serve_startup_s.smallthinker": "serve_startup_s.serve",
    "data_wait_ms.kanana2": "data_wait_ms.train",
    "host_gap_ms.kanana2": "host_gap_ms.train",
    "fwd_device_ms.longdoc": "fwd_device_ms.offline",
    "fwd_device_ms.longcat": "fwd_device_ms.offline",
    "step_device_ms.kanana2": "step_device_ms.train",
    "idle_batch_host_pct.longdoc": "idle_batch_host_pct.offline",
    "idle_batch_host_pct.longcat": "idle_batch_host_pct.offline",
    "idle_batch_host_pct.lfm2": "idle_batch_host_pct.offline",
    "flash_fwd_ms.looped": "flash_fwd_ms.train",
    "flash_dq_ms.looped": "flash_dq_ms.train",
    "flash_dkv_ms.looped": "flash_dkv_ms.train",
    "attn_share_pct.kanana2": "attn_share_pct.train",
    "mlp_share_pct.kanana2": "mlp_share_pct.train",
    "head_share_pct.kanana2": "head_share_pct.train",
    "optimizer_share_pct.kanana2": "optimizer_share_pct.train",
    "unscoped_share_pct.kanana2": "unscoped_share_pct.train",
    "attn_share_pct.granite": "attn_share_pct.lfm2",
    "mlp_share_pct.granite": "mlp_share_pct.lfm2",
    "moe_share_pct.smallthinker": "moe_share_pct.lfm2",
    "unscoped_share_pct.granite": "unscoped_share_pct.lfm2",
    "unscoped_share_pct.smallthinker": "unscoped_share_pct.lfm2",
    "expert_load_max_over_mean.lfm2": "expert_load_max_over_mean.longcat",
    "expert_load_max_over_mean.smallthinker":
        "expert_load_max_over_mean.longcat",
    "expert_share_pct.smallthinker": "expert_share_pct.lfm2",
    "decode_step_device_ms.smallthinker": "decode_step_device_ms.granite",
    "prefill_device_ms.smallthinker": "prefill_device_ms.granite",
    "admit_wait_ms.smallthinker": "admit_wait_ms.granite",
}
ADDED = {
    "tail_behind_call_pct.steady": ("tail_class_pct", "ttft_p95_ms"),
    "tail_own_call_pct.steady": ("tail_class_pct", "ttft_p95_ms"),
    "tail_held_pct.steady": ("tail_class_pct", "ttft_p95_ms"),
    "tail_queue_wait_share_pct.steady": ("tail_stage_share_pct",
                                         "ttft_p95_ms"),
    "hold_ms_per_min.steady": ("hold_ms_per_min", "ttft_p95_ms"),
    "hold_ms_per_min.offline": ("hold_ms_per_min", "serve_tokens_per_s"),
}


@pytest.fixture(scope="module")
def real():
    return manifest.Manifest(REPO)


# PR 59's files named some readers through an alias in the configuration's
# own module (``idle_class_pct = program_spans.idle_class_pct``); the aliases
# went with the entries that named them
ALIASES = {
    "benchmark.sala_counts:idle_class_pct": "program_spans:idle_class_pct",
    "benchmark.longcat_counts:idle_class_pct": "program_spans:idle_class_pct",
    "benchmark.lfm2_counts:idle_class_pct": "program_spans:idle_class_pct",
    "benchmark.sala_counts:gauge": "program_spans:gauge",
    "benchmark.longcat_counts:gauge": "program_spans:gauge",
    "benchmark.lfm2_counts:gauge": "program_spans:gauge",
    "benchmark.looped_counts:kernel_ms": "program_spans:kernel_ms",
    "benchmark.smallthinker_counts:execution_busy_ms":
        "lfm2_counts:execution_busy_ms",
    "benchmark.smallthinker_counts:scope_share_pct":
        "lfm2_counts:scope_share_pct",
    "benchmark.smallthinker_counts:span_attr_mean":
        "granite_counts:span_attr_mean",
}


def _kind(spec):
    """What makes two entries one: the reader itself (a module's alias of
    another's function is that function), its parameters and ``moves``."""
    name = spec["reducer"]
    if name in ALIASES:
        name = "benchmark." + ALIASES[name]
    return (reducers.resolve(name),
            json.dumps(spec.get("params", {}), sort_keys=True),
            spec["moves"])


def _reported(real, cell):
    return {_kind(m): m["name"] for m in real.cell(cell).per_layer}


# -- the list ---------------------------------------------------------------


def test_the_list_is_clean_and_keeps_room_for_a_tenth_configuration(real):
    assert manifest.check(real) == []
    names = [m["name"] for m in real.data["per_layer"]]
    assert len(names) == len(set(names)) <= ROOM
    assert len(real.data["workloads"]) == 11
    assert len(real.data["configs"]) == 9
    assert [(m["name"], m["bound"]) for m in real.data["end_to_end"]] == [
        ("train_tokens_per_s", 0.01), ("ttft_p95_ms", 0.05),
        ("serve_tokens_per_s", 0.06), ("setup_s", 0.1)]


def test_no_two_entries_share_a_reader_its_parameters_and_moves(real):
    seen = {}
    for m in real.data["per_layer"]:
        spec = real._file("layer_metrics", m["name"])
        kind = _kind({**spec, **m})
        assert kind not in seen, (m["name"], seen[kind])
        seen[kind] = m["name"]


def test_every_metric_file_has_an_entry_and_agrees_with_it(real):
    names = {m["name"]: m for m in real.data["per_layer"]}
    held = {f[:-len(".json")] for f in os.listdir(
        os.path.join(REPO, "benchmark", "layer_metrics"))}
    assert held == set(names)
    for name, entry in names.items():
        spec = real._file("layer_metrics", name)
        assert {k: spec[k] for k in entry} == entry, name
        assert spec["what"] and callable(reducers.resolve(spec["reducer"]))


@pytest.mark.parametrize("before", BEFORE, ids=[m["name"] for m in BEFORE])
def test_every_cell_still_reports_each_reader_it_reported(real, before):
    """A case an entry of PR 59's list: each of its cells reports the same
    reader with the same parameters, moving the same metric, today."""
    name = before["name"]
    if name in RETIRED:
        with open(os.path.join(REPO, "CHANGES.md")) as f:
            assert f"`{name}`" in f.read().partition("\nPR 60")[2]
        assert name not in {m["name"] for m in real.data["per_layer"]}
        return
    for cell in before["workloads"]:
        now = _reported(real, cell)
        assert _kind(before) in now, (name, cell)
        assert now[_kind(before)] == FOLDED.get(name, name)


@pytest.mark.parametrize("gone, kept", sorted(FOLDED.items()))
def test_a_folded_entry_went_with_its_file_into_the_first_of_its_kind(
        real, gone, kept):
    entries = {m["name"]: m for m in real.data["per_layer"]}
    assert gone not in entries and kept in entries
    assert not os.path.exists(os.path.join(
        REPO, "benchmark", "layer_metrics", gone + ".json"))
    was, = [m for m in BEFORE if m["name"] == gone]
    first, = [m for m in BEFORE if m["name"] == kept]
    assert _kind(was) == _kind(first)
    assert set(was["workloads"]) | set(first["workloads"]) <= set(
        entries[kept]["workloads"])
    # the list's order is the cells' own
    cells = [w["name"] for w in real.data["workloads"]]
    assert entries[kept]["workloads"] == [
        c for c in cells if c in entries[kept]["workloads"]]


@pytest.mark.parametrize("gone, instead", sorted(
    (k, v) for k, v in RETIRED.items() if v))
def test_a_retired_constructor_gauge_left_the_start_up_it_read(
        real, gone, instead):
    was, = [m for m in BEFORE if m["name"] == gone]
    for cell in was["workloads"]:
        assert instead in {m["name"] for m in real.cell(cell).per_layer}


@pytest.mark.parametrize("name", sorted(ADDED))
def test_the_tails_readers_have_their_entries(real, name):
    from benchmark import request_tail
    reader, moves = ADDED[name]
    entry, = [m for m in real.data["per_layer"] if m["name"] == name]
    spec = real._file("layer_metrics", name)
    assert reducers.resolve(spec["reducer"]) is getattr(request_tail, reader)
    assert (entry["moves"], entry["source"]) == (moves, "program_span")
    assert entry["workloads"] == [
        "internlm2-serve-offline" if name.endswith(".offline")
        else "internlm2-serve-steady"]
    assert real.data["per_layer"].index(entry) >= len(
        real.data["per_layer"]) - len(ADDED)


@pytest.mark.parametrize("cell", [
    "mistral7b-train-4k", "mistral7b-train-4k-fsdp4", "ouro2.6b-train-4k",
    "kanana2-train-8k", "internlm2-serve-offline",
    "minicpm-sala-serve-longdoc", "longcat-flash-serve-prefill",
    "lfm2-24b-serve-prefill", "granite4h-serve-chat",
    "smallthinker-serve-mixed"])
def test_a_cell_keeps_every_share_of_a_roofline_or_of_the_step(real, cell):
    def guards(names):
        return {n for n in names if "roofline" in n or "mfu" in n}
    before = guards(m["name"] for m in BEFORE if cell in m["workloads"])
    now = guards(m["name"] for m in real.cell(cell).per_layer)
    assert before <= now and (now or cell.startswith("internlm2"))


# -- a closed loop whose rounds are alike by construction -------------------


def _mix(name):
    with open(os.path.join(REPO, "benchmark", "traffic", name + ".json")) as f:
        return json.load(f)


SMALLTHINKER = "mixed3k-x384-closed48"
# sha256 of the plan PR 59's generator made (prompts, the generating cells'
# answers beside them), seeds 0, 7 and 2**31 + 7
PLANS = {
    "chat192x160-closed64": ("d9ec4cada44f1ace", "bf89ca86c2ff7745",
                             "36b4814b753fc249"),
    "lognormal12k-closed2": ("df11769f9af156ff", "57c12bd4d8e7232b",
                             "c218ea8010645cc3"),
    "lognormal350-closed8": ("9940de871576ec1b", "44bbb4913f34dd12",
                             "d789dfcd1c3fc14a"),
    "lognormal350-poisson": ("fc81059231d6b994", "3e61b37d13f9f281",
                             "21a55788c6fbda4f"),
    "lognormal4k-closed2": ("22b1ebfac8ec9cd8", "2890e5d772b62a4b",
                            "54e9ee16757b01b8"),
    SMALLTHINKER: ("25ee17d3f4906b97", "e50237d840f78a5f",
                   "25ee17d3f4906b97"),
}


def _plan(mix, seed):
    if "answer_len" in mix:
        return generate_job.request_plan(mix, seed)
    return traffic.request_plan(mix, 30.0, seed)


@pytest.mark.parametrize("name", sorted(PLANS))
def test_a_mix_without_the_key_makes_the_plan_it_made_bit_for_bit(name):
    mix = {k: v for k, v in _mix(name).items() if k != "arrange"}
    if "rate_rps" in mix:
        mix["rate_rps"] = 8.0       # the digests are of PR 59's rate
    for seed, digest in zip((0, 7, 2**31 + 7), PLANS[name]):
        text = json.dumps(_plan(mix, seed), sort_keys=True)
        assert hashlib.sha256(text.encode()).hexdigest()[:16] == digest


def test_only_the_smallthinker_mix_takes_the_key():
    took = [n for n in PLANS if "arrange" in _mix(n)]
    assert took == [SMALLTHINKER]
    mix = _mix(SMALLTHINKER)
    assert (mix["arrange"], mix["clients"], mix["n_lengths"],
            mix["pattern_seed"], mix["answer_pattern_seed"],
            mix["preroll_s"], mix["timeout_s"]) == (
        "by_client", 48, 144, 1564, 1565, 10.0, 120.0)


def _rounds(values, clients):
    return np.asarray(values).reshape(-1, clients)


@pytest.mark.parametrize("clients, r, seed", [
    (48, 3, 1564), (48, 3, 1565), (64, 6, 52), (8, 5, 3), (2, 26, 37)])
def test_every_round_holds_one_member_of_every_stratum(clients, r, seed):
    spec = {"dist": "lognormal", "median": 3072, "sigma": 1.0,
            "min": 128, "max": 12288}
    n = clients * r
    plain = np.sort(traffic.prompt_lengths(spec, n, seed))
    got = traffic.prompt_lengths(spec, n, seed, clients)
    assert sorted(got) == plain.tolist()         # the same work
    strata = plain.reshape(clients, r)
    rounds = _rounds(got, clients)
    # a client's requests are one stratum's, one a round
    for c in range(clients):
        mine = sorted(rounds[:, c])
        assert any(mine == s.tolist() for s in strata), c
    # so a round holds one length of every stratum: its k-th smallest lies
    # in the k-th stratum (lengths at a clip tie across strata)
    for row in rounds:
        ordered = np.sort(row)
        assert (strata[:, 0] <= ordered).all()
        assert (ordered <= strata[:, -1]).all()
    # a client's rounds run up its stratum or down it, and both kinds exist
    steps = np.diff(rounds, axis=0)
    up, down = (steps >= 0).all(axis=0), (steps <= 0).all(axis=0)
    assert (up | down).all() and (up & ~down).any() and (down & ~up).any()


@pytest.mark.parametrize("what", ["prompts", "answers", "tokens"])
@pytest.mark.parametrize("seed", [0, 1, 5, 2**31 + 7])
def test_any_rotations_rounds_carry_the_same_tokens_within_a_per_cent(
        what, seed):
    mix = _mix(SMALLTHINKER)
    plan = generate_job.request_plan(mix, seed)
    prompts, answers = (_rounds(plan[k], 48) for k in ("lengths", "answers"))
    sums = {"prompts": prompts, "answers": answers,
            "tokens": prompts + answers}[what].sum(axis=1)
    assert (sums.max() - sums.min()) / sums.mean() < 0.01
    # the old arrangement's rounds lay 12% apart
    old = generate_job.request_plan(
        {k: v for k, v in mix.items() if k != "arrange"}, seed)
    was = (_rounds(old["lengths"], 48) + _rounds(old["answers"], 48)).sum(1)
    assert (was.max() - was.min()) / was.mean() > 0.10


def test_a_seed_picks_a_rotation_of_the_same_rounds():
    mix = _mix(SMALLTHINKER)
    plans = {traffic.start_index(seed, 3): generate_job.request_plan(
        mix, seed) for seed in range(40)}
    assert sorted(plans) == [0, 1, 2]
    for k, plan in plans.items():
        for key in ("lengths", "answers"):
            assert plan[key] == np.roll(plans[0][key], -48 * k).tolist()


def test_prompts_and_answers_are_arranged_apart():
    mix = _mix(SMALLTHINKER)
    plan = generate_job.request_plan(mix, 0)
    prompts, answers = _rounds(plan["lengths"], 48), _rounds(
        plan["answers"], 48)
    by_prompt = np.argsort(prompts.mean(axis=0), kind="stable")
    by_answer = np.argsort(answers.mean(axis=0), kind="stable")
    assert by_prompt.tolist() != by_answer.tolist()
    # a long prompt does not bring a long answer
    r = np.corrcoef(np.log(prompts.ravel()), np.log(answers.ravel()))[0, 1]
    assert abs(r) < 0.3
    # every client's three requests are near one another, clients are not
    assert np.median(prompts.max(0) / prompts.min(0)) < 1.1
    assert prompts.max() / prompts.min() > 50


@pytest.mark.parametrize("mix, says", [
    ({"arrange": "by_round"}, "unknown arrangement"),
    ({"arrange": "by_client", "n_lengths": 100}, "not whole rounds")])
def test_an_arrangement_that_cannot_be_made_is_refused_by_name(mix, says):
    with pytest.raises(ValueError, match=says):
        generate_job.request_plan({**_mix(SMALLTHINKER), **mix}, 0)


# -- the decode step's mixture roofline on a plane this file encodes --------

DIMS = {"d_model": 64, "expert_width": 32, "n_experts": 16, "top_k": 2,
        "n_layers": 2, "n_heads": 4, "n_kv_heads": 2, "head_dim": 16,
        "vocab_size": 256, "window": 8, "layer_types": ["global", "window"]}
SLOTS = 4
A_STEP = SLOTS * DIMS["top_k"] * DIMS["n_layers"]       # 16 pairs a step
CALL = "custom-call"
STEP_OP = "jit(decode_step)/while/body/closed_call/"
TARGET = ", custom_call_target=\"tpu_custom_call\""
RECORDS = {
    1: _metadata(1, "jit_decode_step(123)"),
    2: _metadata(2, "jit_prefill(456)"),
    3: _metadata(3, "%experts_stream.3 = f32[4,64] custom-call(%p0)" + TARGET,
                 STEP_OP + "moe/experts/experts_stream/pallas_call:", CALL),
    4: _metadata(4, "%ragged-dot.4 = f32[8,32] custom-call(%p1)" + TARGET,
                 "", CALL),
    5: _metadata(5, "%fusion.5 = f32[4,64] fusion(%p2)",
                 STEP_OP + "attn/nope/core/dot_general:",
                 "convolution fusion"),
    6: _metadata(6, "%ragged-dot-metadata.6 = s32[17] custom-call(%p3)"
                 + TARGET, "", CALL),
}
# two decode steps of 100 us (a streamed call of 30 us a layer in the first;
# in the second one streamed call of 30 and one grouped product of 20 with
# its helper) and a prefill between them whose mixture is not the steps'
MODULES = [_event(1, 0, 100), _event(2, 100, 300), _event(1, 300, 400)]
OPS = [_event(5, 0, 20), _event(3, 20, 50), _event(3, 50, 80),
       _event(3, 120, 180), _event(4, 180, 260),
       _event(5, 300, 320), _event(3, 320, 350), _event(6, 350, 355),
       _event(4, 355, 375)]


def _space():
    host = _plane("/host:CPU", [("main", BASE_NS, [_event(11, 0, 400)])],
                  {11: _field(1, 11) + _field(2, "bench.window")}, {})
    first = _plane("/device:TPU:0", [("XLA Modules", BASE_NS, MODULES),
                                     ("XLA Ops", BASE_NS, OPS)], RECORDS)
    return b"".join(_field(1, p) for p in (host, first))


def _route(held, start_us, layers=DIMS["n_layers"]):
    at = T0_NS + start_us * 1000
    return Span("moe.route", at, at + 1000, 1,
                {"held": held, "absent": 0, "zero": 0, "load_max": 3,
                 "layers": layers, "experts": DIMS["n_experts"],
                 "steps": 0, "streamed": held})


@pytest.fixture
def traced(tmp_path, monkeypatch):
    monkeypatch.setattr(tempfile, "tempdir", str(tmp_path))
    path = _written(tmp_path, _space())
    reduced = trace_reduce.load(path, "bench.", "window")
    spans = [_route(A_STEP, 90), _route(512, 290), _route(A_STEP, 390),
             _route(A_STEP, 500)]
    monkeypatch.setattr(program_spans, "program_spans",
                        lambda ctx: tuple(spans))
    device_scopes._run_leaves.cache_clear()
    device_scopes._run_rows.cache_clear()

    def ctx(dims=DIMS, slots=SLOTS):
        cell = type("C", (), {"name": "smallthinker-serve-mixed",
                              "deploy": {}})()
        return reducers.Context(cell=cell, trace=reduced,
                                device_kind="TPU v5 lite",
                                counters={"dims": dims, "slots": slots})
    return ctx


def test_the_mixtures_calls_inside_a_decode_step_count_and_no_others(traced):
    from benchmark import lfm2_counts, peaks
    ctx = traced()
    got = smallthinker_counts.expert_matmul_roofline_pct(ctx, {})
    # inside the steps: 30 + 30, and 30 + 20 (the helper is no product); the
    # prefill's 60 + 80 are not the steps'
    spent = (30 + 30 + 30 + 20) * 1e-6
    # two steps' spans lie in the window: their pairs against every expert
    # of every layer read once a step, whichever call did it
    pairs, reads = 2 * A_STEP, 2 * DIMS["n_layers"] * DIMS["n_experts"]
    peak = peaks.peak("TPU v5 lite")
    least = max(
        lfm2_counts.expert_matmul_flops(pairs, DIMS) / peak.bf16_flops_per_s,
        lfm2_counts.expert_matmul_bytes(pairs, reads, DIMS)
        / peak.hbm_bytes_per_s)
    assert got == pytest.approx(100 * least / spent) and 0 < got < 100
    note, = [n for n in ctx.notes if n.startswith("decode mixture")]
    assert "32 pairs of 2 steps (0.50 rows an expert read)" in note
    assert "inside 2 steps; bound by bytes" in note


@pytest.mark.parametrize("without", ["steps", "spans", "slots", "dims",
                                     "calls"])
def test_the_pointed_reader_returns_nothing_where_nothing_is(
        traced, monkeypatch, without):
    ctx = traced()
    if without == "steps":
        monkeypatch.setattr(smallthinker_counts, "DECODE", "jit_other")
    elif without == "spans":
        monkeypatch.setattr(program_spans, "program_spans", lambda ctx: ())
    elif without == "slots":
        ctx = traced(slots=None)
    elif without == "dims":
        ctx = traced(dims={k: v for k, v in DIMS.items() if k != "window"})
    else:
        monkeypatch.setattr(smallthinker_counts, "STREAM_CALL", "no_such")
        monkeypatch.setattr(smallthinker_counts.lfm2_counts, "_is_product",
                            lambda op: False)
    assert smallthinker_counts.expert_matmul_roofline_pct(ctx, {}) is None


def test_at_the_published_sizes_a_steps_mixture_must_read_six_gigabytes():
    """8 layers x 64 experts x 3 x 2560 x 768 x 2 B = 6.04 GB of weights a
    step and the 2,304 pairs' rows in and out (0.02 GB): 7.40 ms at 819 GB/s,
    bound by bytes at 4.5 rows an expert."""
    from benchmark import lfm2_counts, sala_counts
    real = manifest.Manifest(REPO).cell("smallthinker-serve-mixed")
    dims = manifest.model_dims(real.config, real.job, real.chips)
    pairs = 48 * dims["top_k"] * dims["n_layers"]
    nbytes = lfm2_counts.expert_matmul_bytes(
        pairs, dims["n_layers"] * dims["n_experts"], dims)
    assert nbytes == 2 * (8 * 64 * 3 * 2560 * 768 + pairs * 2 * 2560)
    assert nbytes == pytest.approx(6.063e9, rel=1e-3)
    least, bound = sala_counts.min_seconds(
        lfm2_counts.expert_matmul_flops(pairs, dims), nbytes, "TPU v5 lite")
    assert bound == "bytes" and least == pytest.approx(7.40e-3, rel=2e-3)
