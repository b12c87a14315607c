"""``BENCHMARK.json`` and the data files it names are well formed, and a
later PR can add a cell and a per-layer metric without editing a file."""

import json
import os

import pytest

import benchmark_tiny
from benchmark import harness, manifest, peaks, reducers

REPO = benchmark_tiny.REPO
CELLS = ["mistral7b-train-4k", "internlm2-serve-steady",
         "mistral7b-train-4k-fsdp4", "internlm2-serve-offline"]
WIDTHS = ("hidden_size", "intermediate_size", "num_attention_heads",
          "num_key_value_heads", "vocab_size", "rope_theta", "rms_norm_eps")
ACCEPTED_E2E = ("train_tokens_per_s", "ttft_p95_ms", "serve_tokens_per_s",
                "setup_s")
PUBLISHED = {
    "mistral-7b-v0.3": (4096, 14336, 32, 8, 32768, 1e6, 1e-5, 32),
    "internlm2-1.8b": (2048, 8192, 16, 8, 92544, 1e6, 1e-5, 24),
}


ROOTS = ("as accepted", "with later cells")


def real_root(which, tmp_path_factory):
    """The repo as accepted, or a copy of it with what later PRs bring: a
    later configuration and later cells beside the real ones, as new files
    and entries (``benchmark_tiny.make_root``). Every guard written against
    the real manifest runs on both, so a guard that pins what a later PR
    must change (the number of cells, the last cell) fails in the PR that
    writes it."""
    if which == "as accepted":
        return REPO
    return benchmark_tiny.make_root(tmp_path_factory.mktemp("later"))


@pytest.fixture(scope="module", params=ROOTS)
def real(request, tmp_path_factory):
    return manifest.Manifest(real_root(request.param, tmp_path_factory))


def test_manifest_passes_every_check_of_form(real):
    assert manifest.check(real) == []


def test_manifest_has_exactly_the_contracts_keys(real):
    assert set(real.data) == {"command", "paths", "run_seconds", "configs",
                              "workloads", "end_to_end", "per_layer"}
    assert real.data["paths"] == ["benchmark", "tests/benchmark_suite"]
    assert len(json.dumps(real.data)) < 64 * 1024


def cell_order_faults(workloads):
    """The accepted cells are all there, first and in their order; what a
    later PR adds follows them; four-chip cells keep to the quota that
    ``manifest.check`` computes. Returns what is wrong, as a list."""
    names = [w["name"] for w in workloads]
    bad = [f"accepted cell {c} is missing" for c in CELLS if c not in names]
    if names[:len(CELLS)] != CELLS:
        bad.append(f"the first cells are {names[:len(CELLS)]}, not {CELLS}")
    four = [w["name"] for w in workloads if w["chips"] == 4]
    if len(four) > max(1, len(names) // 4):
        bad.append(f"too many four-chip cells: {four}")
    return bad


def test_cells_are_the_issues_and_in_its_order(real):
    assert cell_order_faults(real.data["workloads"]) == []
    assert real.cell_names()[0] == "mistral7b-train-4k"


def _cells(*names, four=("mistral7b-train-4k-fsdp4",)):
    return [{"name": n, "chips": 4 if n in four else 1} for n in names]


@pytest.mark.parametrize("workloads,says", [
    (_cells(*CELLS), None),
    (_cells(*CELLS, "internlm2-serve-short-burst"), None),
    (_cells(*CELLS, "a", "b", "c", "d4", four=(CELLS[2], "d4")), None),
    (_cells(*CELLS[:1], *CELLS[2:]), "internlm2-serve-steady is missing"),
    (_cells(CELLS[1], CELLS[0], *CELLS[2:]), "the first cells are"),
    (_cells("a-new-cell", *CELLS), "the first cells are"),
    (_cells(*CELLS[:2], "between", *CELLS[2:]), "the first cells are"),
    (_cells(*CELLS, "e4", four=(CELLS[2], "e4")), "too many four-chip"),
])
def test_the_order_test_admits_what_is_added_and_guards_what_is_accepted(
        workloads, says):
    faults = cell_order_faults(workloads)
    if says is None:
        assert faults == []
    else:
        assert any(says in f for f in faults), faults


def test_a_fifth_cell_in_a_copy_of_the_real_manifest_passes_every_guard(
        tmp_path):
    """``internlm2-serve-short-burst`` as a later PR would add it: a
    traffic file, a workload file, entries; the guards of form and of
    order hold with five cells."""
    root = benchmark_tiny.make_root(tmp_path, cells=())
    path = os.path.join(root, "BENCHMARK.json")
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        data = json.load(f)           # the real one, without the toy config
    base = os.path.join(root, "benchmark")
    with open(os.path.join(base, "traffic", "lognormal350-poisson.json")) as f:
        mix = json.load(f)
    with open(os.path.join(base, "traffic", "short-burst.json"), "w") as f:
        json.dump({**mix, "name": "short-burst", "rate_rps": 20.0}, f)
    with open(os.path.join(base, "workloads",
                           "internlm2-serve-steady.json")) as f:
        deploy = json.load(f)
    with open(os.path.join(base, "workloads",
                           "internlm2-serve-short-burst.json"), "w") as f:
        json.dump({**deploy, "name": "internlm2-serve-short-burst"}, f)
    data["workloads"].append({
        "name": "internlm2-serve-short-burst", "config": "internlm2-1.8b",
        "traffic": "short-burst", "chips": 1, "why": "a later PR's cell"})
    for m in data["end_to_end"] + data["per_layer"]:
        if "internlm2-serve-steady" in m.get("workloads", ()):
            m["workloads"].append("internlm2-serve-short-burst")
    with open(path, "w") as f:
        json.dump(data, f)
    m = manifest.Manifest(root)
    assert manifest.check(m) == []
    assert cell_order_faults(m.data["workloads"]) == []
    assert m.cell_names()[-1] == "internlm2-serve-short-burst"
    assert m.cell("internlm2-serve-short-burst").traffic["rate_rps"] == 20.0


@pytest.mark.parametrize("name", sorted(PUBLISHED))
def test_configuration_keeps_every_published_width(real, name):
    entry = next(c for c in real.data["configs"] if c["name"] == name)
    with open(os.path.join(real.root, entry["file"])) as f:
        config = json.load(f)
    *widths, layers = PUBLISHED[name]
    assert [config[k] for k in WIDTHS] == widths
    assert config["num_hidden_layers"] == layers
    assert config["source"] == entry["source"]
    for cut in config["reduced"].values():
        assert set(cut) <= {"num_hidden_layers", "why", "stands_for"}
    cuts_depth = any("num_hidden_layers" in c
                     for c in config["reduced"].values())
    assert entry["reduced"] == (["num_hidden_layers"] if cuts_depth else [])


def test_every_metric_has_units_and_a_bound_in_range(real):
    for m in real.data["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1, m
        # the four accepted ones are the host's clock; a later one may be
        # the trace's
        assert m["source"] == "host_clock" or m["name"] not in ACCEPTED_E2E
        assert m["source"] in ("host_clock", "device_trace")
    for m in real.data["end_to_end"] + real.data["per_layer"]:
        assert manifest.UNIT_RE.match(m["unit"]) and " " not in m["unit"]


def test_every_moves_names_a_metric_its_cells_report(real):
    e2e = {m["name"]: m for m in real.data["end_to_end"]}
    cells = set(real.cell_names())
    for m in real.data["per_layer"]:
        moved = e2e[m["moves"]]
        assert set(m.get("workloads", cells)) <= set(
            moved.get("workloads", cells)), m["name"]


def test_every_per_layer_metric_has_a_file_with_a_known_reader(real):
    for m in real.data["per_layer"]:
        path = os.path.join(real.root, "benchmark", "layer_metrics",
                            m["name"] + ".json")
        with open(path) as f:
            spec = json.load(f)
        for key in ("unit", "layer", "moves", "better", "source"):
            assert spec[key] == m[key], (m["name"], key)
        assert callable(reducers.resolve(spec["reducer"]))


def test_every_cell_resolves_to_its_files_and_sizes(real):
    for name in real.cell_names():
        cell = real.cell(name)
        dims = manifest.model_dims(cell.config, cell.job, cell.chips)
        assert dims["vocab_size"] > 0
        assert callable(harness.load_job(cell).run)
        if name in CELLS:       # a later cell's adapter spells its own
            assert dims["head_dim"] == 128
            assert cell.job in ("train", "serve")
        assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
        assert len(cell.end_to_end) >= 2 and cell.per_layer


def test_a_cell_whose_job_and_chips_have_no_reduced_entry_is_an_error(real):
    cell = real.cell("mistral7b-train-4k")
    with pytest.raises(manifest.ManifestError, match="reduced"):
        manifest.model_dims(cell.config, "serve", 1)
    assert manifest.model_dims(cell.config, "train", 1)["n_layers"] == 2
    if "train.4" in cell.config["reduced"]:
        assert manifest.model_dims(cell.config, "train", 4)["n_layers"] == 8


def test_unknown_cell_and_unknown_device_are_errors(real):
    with pytest.raises(manifest.ManifestError):
        real.cell("no-such-cell")
    with pytest.raises(peaks.UnknownDevice):
        peaks.peak("cpu")
    v5e = peaks.peak("TPU v5 lite")
    assert (v5e.bf16_flops_per_s, v5e.hbm_bytes_per_s,
            v5e.hbm_bytes) == (197e12, 819e9, 16e9)


def test_check_catches_malformed_manifests(tmp_path):
    root = benchmark_tiny.make_root(tmp_path, cells=("tiny-train",))
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        good = json.load(f)
    assert manifest.check(manifest.Manifest(root)) == []

    def faults(edit):
        data = json.loads(json.dumps(good))
        edit(data)
        with open(path, "w") as f:
            json.dump(data, f)
        return manifest.check(manifest.Manifest(root))

    assert faults(lambda d: d["end_to_end"][0].update(unit="tokens per s"))
    assert faults(lambda d: d["end_to_end"][0].update(bound=0.5))
    assert faults(lambda d: d["per_layer"][0].update(moves="nothing"))
    assert faults(lambda d: d["workloads"][-1].update(name="a b"))
    assert faults(lambda d: d["workloads"][-1].update(chips=2))
    assert faults(lambda d: d["configs"][0]["reduced"].append("hidden_size"))
    assert faults(lambda d: d.update(extra=1))


def test_a_new_cell_and_metric_are_files_and_entries_only(tmp_path):
    """What a later PR does: a fifth cell, a new traffic mix and a new
    per-layer metric with a reader of its own, and no file that was there
    is touched."""
    root = benchmark_tiny.make_root(tmp_path, cells=())
    before = benchmark_tiny.snapshot(root)
    base = os.path.join(root, "benchmark")
    with open(os.path.join(base, "traffic", "fifth-mix.json"), "w") as f:
        json.dump({**benchmark_tiny.TINY_TRAFFIC["tiny-batches"],
                   "name": "fifth-mix", "sequences_per_step": 4}, f)
    with open(os.path.join(base, "workloads", "fifth-cell.json"), "w") as f:
        cell = dict(benchmark_tiny.TINY_CELLS["tiny-train"],
                    name="fifth-cell")
        del cell["traffic"], cell["like"]
        json.dump(cell, f)
    with open(os.path.join(base, "layer_metrics", "steps.fifth.json"),
              "w") as f:
        json.dump({"name": "steps.fifth", "unit": "steps",
                   "reducer": "test_benchmark_manifest:count_steps"}, f)
    for path, content in before.items():
        if path.endswith("BENCHMARK.json"):
            continue
        with open(path, "rb") as f:
            assert f.read() == content, path
    data = json.loads(before[os.path.join(root, "BENCHMARK.json")])
    data["workloads"].append({"name": "fifth-cell", "config": "tiny",
                              "traffic": "fifth-mix", "chips": 1,
                              "why": "a later PR's cell"})
    for m in data["end_to_end"]:
        if m["name"] == "train_tokens_per_s":
            m["workloads"].append("fifth-cell")
    data["per_layer"].append({
        "name": "steps.fifth", "unit": "steps", "better": "higher",
        "source": "program_counter", "layer": "Train session",
        "moves": "train_tokens_per_s", "workloads": ["fifth-cell"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(data, f)
    m = manifest.Manifest(root)
    assert manifest.check(m) == []
    cell = m.cell("fifth-cell")
    assert cell.traffic["sequences_per_step"] == 4
    ctx = reducers.Context(cell=cell, trace=None, counters={"steps": 7},
                           device_kind="cpu")
    assert reducers.evaluate(cell.per_layer, ctx) == {"steps.fifth": 7.0}


def count_steps(ctx, params):
    """The fifth cell's own reader."""
    return float(ctx.counters["steps"])
