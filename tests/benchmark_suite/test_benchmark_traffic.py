"""The traffic generator and the arithmetic on the load generator's
records."""

import sys

import numpy as np
import pytest

from benchmark import serve_job, traffic

LENGTHS = {"dist": "lognormal", "median": 350, "sigma": 0.9, "min": 32,
           "max": 2048}
OPEN = {"loop": "open", "pattern_seed": 25, "rate_rps": 8.0,
        "preroll_s": 3.0, "prompt_len": LENGTHS}
CLOSED = {"loop": "closed", "pattern_seed": 25, "clients": 8,
          "n_lengths": 496, "preroll_s": 3.0, "prompt_len": LENGTHS}
BIG_SEED = 2**31 + 12345      # more than 32 signed bits hold


def _window(plan, seconds=30.0):
    return [(d, n) for d, n in zip(plan["due_s"], plan["lengths"])
            if 0.0 <= d < seconds]


def test_the_same_seed_gives_the_same_plan():
    a = traffic.request_plan(OPEN, 30.0, BIG_SEED)
    b = traffic.request_plan(OPEN, 30.0, BIG_SEED)
    assert a == b
    assert traffic.prompt_tokens(BIG_SEED, 7, 40, 92544) == \
        traffic.prompt_tokens(BIG_SEED, 7, 40, 92544)
    assert traffic.prompt_tokens(BIG_SEED, 7, 40, 92544) != \
        traffic.prompt_tokens(BIG_SEED, 8, 40, 92544)
    assert traffic.prompt_tokens(BIG_SEED, 7, 40, 92544) != \
        traffic.prompt_tokens(1, 7, 40, 92544)


def test_every_seed_offers_the_same_sizes_and_gaps_in_another_order():
    a = traffic.request_plan(OPEN, 30.0, 1)
    b = traffic.request_plan(OPEN, 30.0, BIG_SEED)
    wa, wb = _window(a), _window(b)
    assert [n for _, n in wa] != [n for _, n in wb]
    assert sorted(n for _, n in wa) == sorted(n for _, n in wb)
    # the window is one whole cycle: the gaps round it are the same set
    gaps_a = np.diff([d for d, _ in wa] + [30.0])
    gaps_b = np.diff([d for d, _ in wb] + [30.0])
    assert np.allclose(np.sort(gaps_a), np.sort(gaps_b), atol=1e-9)
    # and b's order is a's, started elsewhere in the cycle
    la, lb = [n for _, n in wa], [n for _, n in wb]
    assert any(la[k:] + la[:k] == lb for k in range(len(la)))


def test_open_window_holds_rate_times_seconds_requests_for_every_seed():
    for seed in (0, 5, BIG_SEED):
        plan = traffic.request_plan(OPEN, 30.0, seed)
        assert len(plan["due_s"]) == len(plan["lengths"])
        assert plan["due_s"] == sorted(plan["due_s"])
        assert len(_window(plan)) == 240
        assert 0.0 in plan["due_s"]
        before = [d for d in plan["due_s"] if d < 0]
        assert before and min(before) >= -3.0
        assert abs(len(before) - 24) <= 12      # about 3 s of pre-roll


def test_another_pattern_seed_is_another_arrangement_of_the_same_work():
    a = traffic.request_plan(OPEN, 30.0, 1)
    b = traffic.request_plan({**OPEN, "pattern_seed": 26}, 30.0, 1)
    assert sorted(n for _, n in _window(a)) == sorted(
        n for _, n in _window(b))
    la, lb = [n for _, n in _window(a)], [n for _, n in _window(b)]
    assert not any(la[k:] + la[:k] == lb for k in range(len(la)))


def test_lengths_follow_the_mix_and_its_limits():
    lengths = traffic.prompt_lengths(LENGTHS, 2000, 3)
    assert lengths.min() == 32 and lengths.max() == 2048
    assert abs(np.median(lengths) - 350) <= 2
    fixed = traffic.prompt_lengths(
        {"dist": "fixed", "value": 100, "min": 1, "max": 200}, 5, 0)
    assert fixed.tolist() == [100] * 5
    with pytest.raises(ValueError):
        traffic.prompt_lengths({"dist": "zipf", "min": 1, "max": 2}, 5, 0)


def test_closed_plan_names_clients_and_rotates_a_fixed_set_of_lengths():
    a = traffic.request_plan(CLOSED, 30.0, BIG_SEED)
    b = traffic.request_plan(CLOSED, 30.0, 3)
    assert a["loop"] == "closed" and a["clients"] == 8
    assert len(a["lengths"]) == 496 and a["lengths"] != b["lengths"]
    la, lb = a["lengths"], b["lengths"]
    assert any(la[k:] + la[:k] == lb for k in range(0, 496, 8))
    with pytest.raises(ValueError):
        traffic.request_plan({**CLOSED, "loop": "spiral"}, 30.0, 1)


def test_token_rows_are_sequences_with_their_labels():
    rows = traffic.token_rows(BIG_SEED, 8, 32, 128)
    assert rows.shape == (8, 33) and rows.dtype == np.int32
    assert rows.min() >= 0 and rows.max() < 128
    assert np.array_equal(rows, traffic.token_rows(BIG_SEED, 8, 32, 128))
    assert not np.array_equal(rows, traffic.token_rows(1, 8, 32, 128))


@pytest.mark.parametrize("values,q,want", [
    ([1.0, 2.0, 3.0, 4.0], 50, 2.5), ([1.0, 2.0, 3.0, 4.0], 95, 3.85),
    ([5.0], 95, 5.0), (list(range(101)), 95, 95.0)])
def test_percentile_is_numpys(values, q, want):
    assert traffic.percentile(values, q) == pytest.approx(want)
    assert traffic.percentile(values, q) == pytest.approx(
        float(np.percentile(values, q)))


def _rec(i, due, sent, done, status=200, length=10):
    rec = {"i": i, "len": length, "due": due, "sent": sent, "done": done,
           "status": status}
    if status == 200:
        rec.update(token=3, logit=1.5)
    return rec


def test_open_loop_times_from_the_due_instant_not_the_send():
    # the generator was 40 ms late sending request 1: the user still waited
    records = [_rec(0, 1.0, 1.0, 1.1), _rec(1, 2.0, 2.04, 2.14),
               _rec(2, -1.0, -1.0, -0.9),          # pre-roll: not counted
               _rec(3, 9.99, 9.99, 10.5)]           # due inside: counted
    plan = {"loop": "open"}
    got = serve_job.reduce_records(plan, records, 10.0, 30.0, 128)
    assert got["attempted"] == 3 and got["failed"] == 0
    assert got["metrics"]["ttft_p50_ms"] == pytest.approx(140.0)
    assert got["lateness_p95_ms"] == pytest.approx(36.0, abs=0.5)
    assert got["backlog_end"] == 1 and got["tokens"] == 30


def test_a_failed_or_refused_request_counts_as_the_slowest():
    records = [_rec(i, float(i), float(i), i + 0.1) for i in range(9)]
    records.append(_rec(9, 9.0, 9.0, 9.01, status=503))
    got = serve_job.reduce_records({"loop": "open"}, records, 10.0, 30.0,
                                   128)
    assert got["attempted"] == 10 and got["failed"] == 1
    assert got["metrics"]["ttft_p95_ms"] > 10_000     # pulled to the timeout
    assert got["metrics"]["ttft_p50_ms"] == pytest.approx(100.0)


def test_closed_loop_counts_real_tokens_of_replies_inside_the_window():
    records = [_rec(0, -0.5, -0.5, 0.2, length=100),
               _rec(1, 0.2, 0.2, 0.9, length=50),
               _rec(2, 9.5, 9.5, 10.2, length=70),   # answered after the end
               _rec(3, -1.0, -1.0, -0.2, length=30)]  # answered before it
    got = serve_job.reduce_records({"loop": "closed"}, records, 10.0, 30.0,
                                   128)
    assert got["attempted"] == 2 and got["tokens"] == 150
    assert got["metrics"]["serve_tokens_per_s"] == pytest.approx(15.0)


def test_a_malformed_reply_is_counted():
    bad = dict(_rec(0, 1.0, 1.0, 1.1), token=999)   # outside the vocabulary
    got = serve_job.reduce_records({"loop": "open"}, [bad], 10.0, 30.0, 128)
    assert got["malformed"] == 1 and got["failed"] == 1


def test_the_load_generator_never_imports_jax():
    import subprocess
    code = ("import sys, benchmark.loadgen, benchmark.traffic; "
            "sys.exit(int('jax' in sys.modules or 'ray_tpu' in sys.modules))")
    proc = subprocess.run([sys.executable, "-c", code],
                          cwd=traffic.__file__.rsplit("/", 2)[0], timeout=60)
    assert proc.returncode == 0
