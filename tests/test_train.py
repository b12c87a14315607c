"""Train layer: JaxTrainer end-to-end (the minimum e2e slice, SURVEY §7),
checkpoint/resume, failure handling, collective use inside the loop."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import ray_tpu
from ray_tpu.air import Checkpoint, FailureConfig, RunConfig, ScalingConfig
from ray_tpu.train import JaxTrainer, session


def _linear_loop(config):
    """Tiny synthetic regression trained data-parallel via collective."""
    from ray_tpu import collective as col
    rank = session.get_world_rank()
    world = session.get_world_size()
    key = jax.random.PRNGKey(rank)
    w = jnp.zeros((4,))
    ckpt = session.get_checkpoint()
    start_epoch = 0
    if ckpt is not None:
        state = ckpt.to_dict()
        w = jnp.asarray(state["w"])
        start_epoch = state["epoch"] + 1
    x = jax.random.normal(key, (64, 4))
    true_w = jnp.array([1.0, -2.0, 3.0, 0.5])
    y = x @ true_w

    for epoch in range(start_epoch, config["epochs"]):
        grad = jax.grad(lambda w: jnp.mean((x @ w - y) ** 2))(w)
        if world > 1:
            grad = jnp.asarray(
                col.allreduce(np.asarray(grad),
                              config["group_name"])) / world
        w = w - 0.1 * grad
        loss = float(jnp.mean((x @ w - y) ** 2))
        session.report(
            {"loss": loss, "epoch": epoch},
            checkpoint=Checkpoint.from_dict(
                {"w": np.asarray(w), "epoch": epoch}))


def test_trainer_single_worker(ray_start_regular):
    trainer = JaxTrainer(
        _linear_loop,
        train_loop_config={"epochs": 20, "group_name": None},
        scaling_config=ScalingConfig(num_workers=1),
        collective_backend=None)
    result = trainer.fit()
    assert result.error is None
    assert result.metrics["loss"] < 1.0
    assert result.checkpoint is not None
    assert result.checkpoint.to_dict()["epoch"] == 19


def test_trainer_data_parallel(ray_start_regular):
    trainer = JaxTrainer(
        _linear_loop,
        train_loop_config={"epochs": 15, "group_name": None},
        scaling_config=ScalingConfig(num_workers=4,
                                     resources_per_worker={"CPU": 1}),
        collective_backend="cpu")

    # The executor-created group is exposed on the session (public API).
    def loop(config):
        config = dict(config)
        config["group_name"] = session.get_collective_group_name()
        assert config["group_name"] is not None
        _linear_loop(config)

    trainer._train_loop = loop
    result = trainer.fit()
    assert result.error is None
    assert result.metrics["loss"] < 2.0
    assert len(result.metrics_history) == 15 * 4


def test_trainer_resume_from_checkpoint(ray_start_regular):
    ckpt = Checkpoint.from_dict({"w": np.zeros(4), "epoch": 9})
    trainer = JaxTrainer(
        _linear_loop,
        train_loop_config={"epochs": 12, "group_name": None},
        scaling_config=ScalingConfig(num_workers=1),
        collective_backend=None,
        resume_from_checkpoint=ckpt)
    result = trainer.fit()
    assert result.error is None
    # only epochs 10 and 11 ran
    assert len(result.metrics_history) == 2
    assert result.metrics_history[0]["epoch"] == 10


def test_trainer_worker_failure_restarts(ray_start_regular):
    """A crashing worker triggers group restart from the last checkpoint
    (reference: backend_executor.py:510-531)."""

    def crashy_loop(config):
        ckpt = session.get_checkpoint()
        start = 0 if ckpt is None else ckpt.to_dict()["epoch"] + 1
        for epoch in range(start, 6):
            if epoch == 3 and ckpt is None:
                raise RuntimeError("simulated worker crash")
            session.report({"epoch": epoch},
                           checkpoint=Checkpoint.from_dict({"epoch": epoch}))

    trainer = JaxTrainer(
        crashy_loop, train_loop_config={},
        scaling_config=ScalingConfig(num_workers=1),
        run_config=RunConfig(failure_config=FailureConfig(max_failures=2)),
        collective_backend=None)
    result = trainer.fit()
    assert result.error is None
    assert result.metrics["epoch"] == 5


def test_trainer_failure_exhausted(ray_start_regular):
    def always_crash(config):
        raise RuntimeError("boom")

    trainer = JaxTrainer(
        always_crash, train_loop_config={},
        scaling_config=ScalingConfig(num_workers=1),
        run_config=RunConfig(failure_config=FailureConfig(max_failures=1)),
        collective_backend=None)
    result = trainer.fit()
    assert result.error is not None


def test_checkpoint_directory_roundtrip(tmp_path):
    ckpt = Checkpoint.from_dict({
        "params": {"w": jnp.arange(8.0)},
        "epoch": 3,
    })
    path = ckpt.to_directory(str(tmp_path / "ckpt"))
    restored = Checkpoint.from_directory(path).to_dict()
    assert restored["epoch"] == 3
    np.testing.assert_allclose(np.asarray(restored["params"]["w"]),
                               np.arange(8.0))


def test_trainer_persists_checkpoints_with_pruning(ray_start_regular,
                                                   tmp_path):
    """storage_path routes reported checkpoints through the engine:
    manifests are pruned to num_to_keep and the newest commit restores."""

    def loop(config):
        for epoch in range(5):
            session.report({"epoch": epoch},
                           checkpoint=Checkpoint.from_dict({"epoch": epoch}))

    trainer = JaxTrainer(
        loop, train_loop_config={},
        scaling_config=ScalingConfig(num_workers=1),
        run_config=RunConfig(
            name="exp", storage_path=str(tmp_path),
            checkpoint_config=__import__(
                "ray_tpu.air", fromlist=["CheckpointConfig"]
            ).CheckpointConfig(num_to_keep=2)),
        collective_backend=None)
    result = trainer.fit()
    assert result.error is None
    from ray_tpu.checkpoint import list_manifest_names
    root = str(tmp_path / "exp" / "checkpoints")
    kept = list_manifest_names(root)
    assert len(kept) == 2
    restored = Checkpoint.from_manifest(root).to_dict()
    assert restored["epoch"] == 4


def test_trainer_dataset_shards(ray_start_regular):
    """datasets= splits across the worker group; each worker reads its own
    shard via session.get_dataset_shard (DataParallelTrainer contract)."""
    import ray_tpu.data as rd

    ds = rd.from_items([{"x": float(i)} for i in range(40)])

    def loop(config):
        shard = session.get_dataset_shard("train")
        total, rows = 0.0, 0
        for batch in shard.iter_batches(batch_size=8, batch_format="numpy",
                                        prefetch_batches=1):
            total += float(batch["x"].sum())
            rows += len(batch["x"])
        session.report({"total": total, "rows": rows})

    result = JaxTrainer(
        loop, scaling_config=ScalingConfig(num_workers=2),
        collective_backend=None,
        datasets={"train": ds}).fit()
    assert result.error is None
    rows = [m["rows"] for m in result.metrics_history]
    totals = [m["total"] for m in result.metrics_history]
    assert sum(rows) == 40          # full partition, no overlap/loss
    assert abs(max(rows) - min(rows)) <= 1
    assert sum(totals) == float(sum(range(40)))


# -- make_lm_train_step: the looped decoder and the pipeline's shared layers --

def _lm_cfg(**kw):
    from ray_tpu.models.transformer import TransformerConfig
    return TransformerConfig(**{**dict(
        vocab_size=96, d_model=32, n_layers=2, n_heads=4, d_ff=64,
        max_seq_len=32, dtype=jnp.float32, use_flash=False), **kw})


def _one_step(cfg, mesh, tokens, **kw):
    from ray_tpu.train import make_lm_train_step
    init_fn, step_fn, shard_batch = make_lm_train_step(cfg, mesh, **kw)
    _, metrics = step_fn(init_fn(jax.random.PRNGKey(0)), shard_batch(tokens))
    return jax.device_get(metrics)


def test_the_steps_metrics_carry_the_exit_distribution_only_when_looped(
        eight_device_mesh):
    from ray_tpu.parallel import MeshConfig, build_mesh
    mesh = build_mesh(MeshConfig(data=2), eight_device_mesh[:2])
    tokens = np.random.default_rng(0).integers(0, 96, (4, 17), dtype=np.int32)
    plain = _one_step(_lm_cfg(), mesh, tokens)
    assert sorted(plain) == ["grad_norm", "loss"]
    looped = _one_step(_lm_cfg(n_passes=3, post_norm=True, exit_beta=0.05),
                       mesh, tokens)
    assert sorted(looped) == ["exit_entropy", "exit_p", "grad_norm", "loss"]
    assert looped["exit_p"].shape == (3,)
    assert float(looped["exit_p"].sum()) == pytest.approx(1.0, abs=1e-5)
    assert 0.0 < float(looped["exit_entropy"]) <= np.log(3) + 1e-6
    assert np.isfinite(looped["loss"]) and looped["grad_norm"] > 0


def test_the_looped_step_over_fsdp2_compiles_once_and_matches_one_device(
        eight_device_mesh):
    """The looped stack's own backward pass (one float32 accumulator of the
    stacked blocks' shape, carried through the pass x layer loop) with the
    state split over ``fsdp=2``: two steps run one compiled program, and
    each step's loss and gradient norm are the one-device step's."""
    from ray_tpu.parallel import MeshConfig, build_mesh
    from ray_tpu.train import make_lm_train_step
    cfg = _lm_cfg(n_passes=3, post_norm=True, exit_beta=0.05)
    tokens = np.random.default_rng(2).integers(0, 96, (4, 17), dtype=np.int32)
    seen = []
    for mesh in (build_mesh(MeshConfig(data=1), eight_device_mesh[:1]),
                 build_mesh(MeshConfig(data=1, fsdp=2),
                            eight_device_mesh[:2])):
        init_fn, step_fn, shard_batch = make_lm_train_step(cfg, mesh)
        state, steps = init_fn(jax.random.PRNGKey(0)), []
        for _ in range(2):
            state, metrics = step_fn(state, shard_batch(tokens))
            steps.append(jax.device_get(metrics))
        assert step_fn.__wrapped__._cache_size() == 1
        seen.append(steps)
    for one, two in zip(*seen):
        assert float(two["loss"]) == pytest.approx(float(one["loss"]),
                                                   rel=1e-5)
        assert float(two["grad_norm"]) == pytest.approx(
            float(one["grad_norm"]), rel=1e-4)
    assert seen[0][1]["loss"] < seen[0][0]["loss"]      # the state moved


def test_pipeline_with_several_passes_is_refused_by_name(eight_device_mesh):
    from ray_tpu.parallel import MeshConfig, build_mesh
    from ray_tpu.train import make_lm_train_step
    mesh = build_mesh(MeshConfig(pipe=2, data=1), eight_device_mesh[:2])
    with pytest.raises(ValueError, match=r"pipe=2 with n_passes=3"):
        make_lm_train_step(_lm_cfg(n_passes=3), mesh)
    make_lm_train_step(_lm_cfg(n_passes=1), mesh)      # one pass is fine


@pytest.mark.parametrize("remat", [True, False])
def test_the_pipelines_stages_run_the_models_own_layers(eight_device_mesh,
                                                        remat):
    """pipe=2 through ``transformer.apply_layers`` gives the scan path's
    loss and gradient norm."""
    from ray_tpu.parallel import MeshConfig, build_mesh
    tokens = np.random.default_rng(1).integers(0, 96, (4, 17), dtype=np.int32)
    cfg = _lm_cfg(n_layers=4, remat=remat)
    flat = _one_step(cfg, build_mesh(MeshConfig(data=1),
                                     eight_device_mesh[:1]), tokens)
    piped = _one_step(cfg, build_mesh(MeshConfig(pipe=2, data=1),
                                      eight_device_mesh[:2]), tokens,
                      num_microbatches=2)
    assert float(piped["loss"]) == pytest.approx(float(flat["loss"]),
                                                 rel=1e-5)
    assert float(piped["grad_norm"]) == pytest.approx(
        float(flat["grad_norm"]), rel=1e-4)
