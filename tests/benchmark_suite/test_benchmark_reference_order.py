"""The serving job compares with the float32 reference once the replica's
bfloat16 weights are freed: the seeded prompts are served first and their
replies kept, then the deployment is shut down and its parameters deleted,
and only then are the reference's made. A wrong reference still makes the
run incorrect."""

from benchmark import harness, serve_job
from benchmark.adapters import dense_decoder
from test_benchmark_jobs import root, runtime  # noqa: F401 - fixtures

SEED = 2**31 + 36
SECONDS = 1.0


def _run(root, cell):
    return harness.run_cell(cell, SEED, SECONDS, False, root=root,
                            require_tpu=False)


def test_the_reference_is_made_once_the_replicas_weights_are_freed(
        root, runtime, monkeypatch):
    from ray_tpu import serve
    events = []
    real = {name: getattr(serve_job, name)
            for name in ("_post", "_free", "_compare")}
    real_shutdown = serve.shutdown

    def post(*args):
        events.append("post")
        return real["_post"](*args)

    def shutdown():
        events.append("shutdown")
        return real_shutdown()

    def free(name, replica):
        events.append("free")
        real["_free"](name, replica)
        events.append(("freed", name in serve_job._LIVE, replica.params))

    def compare(replies, prompts, *args):
        events.append(("compare", len(replies), len(prompts),
                       dict(serve_job._LIVE)))
        return real["_compare"](replies, prompts, *args)

    monkeypatch.setattr(serve_job, "_post", post)
    monkeypatch.setattr(serve, "shutdown", shutdown)
    monkeypatch.setattr(serve_job, "_free", free)
    monkeypatch.setattr(serve_job, "_compare", compare)
    result = _run(root, "tiny-serve-closed")
    assert result["correct"] and result["failed"] == 0
    # every sample prompt is served first; then the deployment goes, then
    # the weights, and only then is the reference made and compared
    n = events.count("post")
    assert n == 4
    assert events[:n] == ["post"] * n
    assert events[n:n + 4] == ["shutdown", "free", ("freed", False, None),
                               ("compare", n, n, {})]


def test_a_wrong_reference_makes_the_run_incorrect(root, runtime,
                                                   monkeypatch, capfd):
    real = dense_decoder.last_logits
    monkeypatch.setattr(dense_decoder, "last_logits",
                        lambda params, tokens, dims: -real(params, tokens,
                                                           dims))
    result = _run(root, "tiny-serve-open")
    assert not result["correct"] and result["failed"] == 0
    assert "FAULT: served logits off the reference" in capfd.readouterr().err
