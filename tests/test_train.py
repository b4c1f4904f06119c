"""Train layer tests (reference test model: ``python/ray/train/tests/
test_data_parallel_trainer.py`` and v2 controller/worker-group tests —
in-process cluster, fake resources, no real accelerator; SURVEY.md §4)."""
import json
import os

import pytest

import ray_tpu
from ray_tpu import train
from ray_tpu.train import (
    Checkpoint,
    CheckpointConfig,
    DataParallelTrainer,
    FailureConfig,
    JaxTrainer,
    RunConfig,
    ScalingConfig,
    TrainingFailedError,
)


def _run_config(tmp_path, name, **kw):
    return RunConfig(name=name, storage_path=str(tmp_path), **kw)


def test_two_workers_report_ranks(rt_start, tmp_path):
    def train_fn(config):
        ctx = train.get_context()
        train.report(
            {"rank": ctx.get_world_rank(), "world": ctx.get_world_size(),
             "cfg": config["x"]}
        )

    result = DataParallelTrainer(
        train_fn,
        train_loop_config={"x": 41},
        scaling_config=ScalingConfig(num_workers=2),
        run_config=_run_config(tmp_path, "ranks"),
    ).fit()
    # rank 0's report is the tracked metrics stream
    assert result.metrics["rank"] == 0
    assert result.metrics["world"] == 2
    assert result.metrics["cfg"] == 41
    assert result.error is None


def test_checkpointing_and_topk(rt_start, tmp_path):
    def train_fn(config):
        import tempfile

        for step in range(5):
            with tempfile.TemporaryDirectory() as d:
                with open(os.path.join(d, "step.txt"), "w") as f:
                    f.write(str(step))
                train.report(
                    {"score": step}, checkpoint=Checkpoint.from_directory(d)
                )

    result = DataParallelTrainer(
        train_fn,
        scaling_config=ScalingConfig(num_workers=1),
        run_config=_run_config(
            tmp_path, "topk",
            checkpoint_config=CheckpointConfig(
                num_to_keep=2, checkpoint_score_attribute="score"
            ),
        ),
    ).fit()
    run_dir = os.path.join(str(tmp_path), "topk")
    kept = sorted(d for d in os.listdir(run_dir) if d.startswith("checkpoint_"))
    assert len(kept) == 2
    with open(os.path.join(result.checkpoint.path, "step.txt")) as f:
        assert f.read() == "4"  # latest
    assert result.metrics["score"] == 4


def test_failure_retry_resumes_from_checkpoint(rt_start, tmp_path):
    marker = str(tmp_path / "fail_once")

    def train_fn(config):
        import tempfile

        start = 0
        ckpt = train.get_checkpoint()
        if ckpt is not None:
            with open(os.path.join(ckpt.path, "step.txt")) as f:
                start = int(f.read()) + 1
        for step in range(start, 6):
            with tempfile.TemporaryDirectory() as d:
                with open(os.path.join(d, "step.txt"), "w") as f:
                    f.write(str(step))
                train.report(
                    {"step": step, "resumed_from": start},
                    checkpoint=Checkpoint.from_directory(d),
                )
            if step == 2 and not os.path.exists(config["marker"]):
                open(config["marker"], "w").close()
                raise RuntimeError("injected failure at step 2")

    result = DataParallelTrainer(
        train_fn,
        train_loop_config={"marker": marker},
        scaling_config=ScalingConfig(num_workers=1),
        run_config=_run_config(
            tmp_path, "resume", failure_config=FailureConfig(max_failures=1)
        ),
    ).fit()
    assert result.metrics["step"] == 5
    assert result.metrics["resumed_from"] == 3  # resumed, not restarted


def test_failure_exhausted_raises(rt_start, tmp_path):
    def train_fn(config):
        raise ValueError("always fails")

    with pytest.raises(TrainingFailedError, match="always fails"):
        DataParallelTrainer(
            train_fn,
            scaling_config=ScalingConfig(num_workers=1),
            run_config=_run_config(
                tmp_path, "exhaust", failure_config=FailureConfig(max_failures=1)
            ),
        ).fit()


@pytest.mark.parametrize("rt_start", [{"num_cpus": 8}], indirect=True)
def test_jax_trainer_end_to_end(rt_start, tmp_path):
    """Full SPMD GPT-2 loop through the default train loop: loss decreases
    shape-wise (finite), checkpoints written, resume state round-trips."""
    result = JaxTrainer(
        train_loop_config={
            "model": {
                "vocab_size": 128, "max_seq_len": 32, "num_layers": 2,
                "num_heads": 2, "embed_dim": 32, "dtype": "float32",
                "attention_impl": "xla",
            },
            "mesh": {"data": -1},  # all local devices (8 on the test mesh)
            "num_steps": 3,
            "batch_size": 8,
            "seq_len": 16,
            "checkpoint_every": 0,
            "optimizer": {"warmup_steps": 1, "total_steps": 3},
        },
        scaling_config=ScalingConfig(num_workers=1),
        run_config=_run_config(tmp_path, "jax_e2e"),
    ).fit()
    import math

    assert math.isfinite(result.metrics["loss"])
    assert result.metrics["step"] == 3
    assert result.checkpoint is not None
    # checkpoint restores
    from ray_tpu.train import load_pytree

    state = load_pytree(result.checkpoint.path)
    assert int(state["step"]) == 3


def test_elastic_shrinks_after_node_death(rt_cluster, tmp_path):
    """Kill a node mid-training: the controller restarts the group at the
    smaller world size from the latest checkpoint (SURVEY.md §5 elastic
    training; reference: train/v2 elastic.py + chaos NodeKiller)."""
    ray_tpu_mod, cluster = rt_cluster

    def train_fn(config):
        import tempfile
        import time

        ctx = train.get_context()
        start = 0
        ckpt = train.get_checkpoint()
        if ckpt is not None:
            with open(os.path.join(ckpt.path, "step.txt")) as f:
                start = int(f.read()) + 1
        for step in range(start, 8):
            if ctx.get_world_rank() == 0:
                with tempfile.TemporaryDirectory() as d:
                    with open(os.path.join(d, "step.txt"), "w") as f:
                        f.write(str(step))
                    train.report(
                        {"step": step, "world": ctx.get_world_size()},
                        checkpoint=Checkpoint.from_directory(d),
                    )
            else:
                train.report({"step": step, "world": ctx.get_world_size()})
            time.sleep(0.15)

    import threading

    def killer():
        import time

        time.sleep(1.2)
        cluster.kill_node(cluster.nodes[1])

    t = threading.Thread(target=killer, daemon=True)
    t.start()
    result = DataParallelTrainer(
        train_fn,
        scaling_config=ScalingConfig(
            num_workers=2, min_workers=1,
            resources_per_worker={"CPU": 2},
            placement_strategy="SPREAD",
        ),
        run_config=_run_config(
            tmp_path, "elastic", failure_config=FailureConfig(max_failures=3)
        ),
    ).fit()
    t.join()
    assert result.metrics["step"] == 7
    worlds = {m["world"] for m in result.metrics_history}
    assert 1 in worlds, f"expected shrink to world=1, saw {worlds}"


def test_train_collectives(rt_start, tmp_path):
    """broadcast_from_rank_zero + barrier across a 2-worker group
    (reference: train/collective/collectives.py)."""
    from ray_tpu.train import DataParallelTrainer, ScalingConfig

    def loop(config):
        from ray_tpu.train.collective import barrier, broadcast_from_rank_zero
        from ray_tpu.train.context import get_context, report

        ctx = get_context()
        value = broadcast_from_rank_zero(
            {"master": "rank0-data"} if ctx.get_world_rank() == 0 else None
        )
        barrier()
        report({"got": value["master"], "rank": ctx.get_world_rank()})

    result = DataParallelTrainer(
        loop,
        scaling_config=ScalingConfig(num_workers=2),
        run_config=_run_config(tmp_path, "collectives"),
    ).fit()
    assert result.metrics["got"] == "rank0-data"


def test_torch_trainer_ddp_gloo(rt_cluster, tmp_path):
    """TorchTrainer: gloo process group forms, DDP gradients sync
    (reference: train/torch TorchConfig + prepare_model). Needs one worker
    per host process (torch.distributed is per-process global), so the
    cluster fixture provides two nodes and workers SPREAD."""
    from ray_tpu.train import ScalingConfig
    from ray_tpu.train.torch import TorchTrainer

    def loop(config):
        import torch
        import torch.distributed as dist

        from ray_tpu.train.context import get_context, report
        from ray_tpu.train.torch import prepare_model

        ctx = get_context()
        assert dist.is_initialized()
        assert dist.get_world_size() == 2
        model = prepare_model(torch.nn.Linear(4, 1))
        opt = torch.optim.SGD(model.parameters(), lr=0.1)
        # rank-dependent data: DDP must average gradients across ranks
        x = torch.ones(8, 4) * (ctx.get_world_rank() + 1)
        y = torch.zeros(8, 1)
        loss = torch.nn.functional.mse_loss(model(x), y)
        loss.backward()
        grad = model.module.weight.grad.clone()
        # allreduce(grad)/world must equal DDP's averaged grad already
        check = grad.clone()
        dist.all_reduce(check)
        assert torch.allclose(check / 2, grad, atol=1e-6)
        opt.step()
        report({"loss": float(loss), "rank": ctx.get_world_rank()})

    result = TorchTrainer(
        loop,
        scaling_config=ScalingConfig(
            num_workers=2, placement_strategy="SPREAD",
            resources_per_worker={"CPU": 2},
        ),
        run_config=_run_config(tmp_path, "torch_ddp"),
    ).fit()
    import math

    assert math.isfinite(result.metrics["loss"])


@pytest.mark.parametrize(
    "rt_cluster", [{"num_cpus": 2, "num_nodes": 2}], indirect=True
)
def test_elastic_grows_back_when_node_returns(rt_cluster, tmp_path):
    """2 -> 1 -> 2: kill a node (shrink), return capacity (grow-back from
    the latest checkpoint) — the round-trip the reference's elastic.py
    resize decisions cover (train/v2/.../scaling_policy/elastic.py:29)."""
    import threading
    import time as _t

    ray_tpu_mod, cluster = rt_cluster
    # rank 0 marks the world it runs in, and the chaos goes by the marks:
    # by the clock, a loaded box was back at two before a step at one
    marks = str(tmp_path / "marks")
    os.makedirs(marks)

    def train_fn(config):
        import tempfile
        import time

        ctx = train.get_context()
        start = 0
        ckpt = train.get_checkpoint()
        if ckpt is not None:
            with open(os.path.join(ckpt.path, "step.txt")) as f:
                start = int(f.read()) + 1
        for step in range(start, 44):
            if ctx.get_world_rank() == 0:
                open(os.path.join(
                    marks, f"world_{ctx.get_world_size()}"), "w").close()
                with tempfile.TemporaryDirectory() as d:
                    with open(os.path.join(d, "step.txt"), "w") as f:
                        f.write(str(step))
                    train.report(
                        {"step": step, "world": ctx.get_world_size()},
                        checkpoint=Checkpoint.from_directory(d),
                    )
            else:
                train.report({"step": step, "world": ctx.get_world_size()})
            time.sleep(0.25)

    def seen(mark, deadline=120.0):
        end = _t.monotonic() + deadline
        while not os.path.exists(os.path.join(marks, mark)):
            if _t.monotonic() > end:
                return
            _t.sleep(0.05)

    def chaos():
        seen("world_2")  # a step was taken on both nodes
        cluster.kill_node(cluster.nodes[1])  # shrink to 1
        seen("world_1")  # and one on the node that is left
        cluster.add_node({"CPU": 2})  # capacity returns: grow back

    t = threading.Thread(target=chaos, daemon=True)
    t.start()
    result = DataParallelTrainer(
        train_fn,
        scaling_config=ScalingConfig(
            num_workers=2, min_workers=1,
            resources_per_worker={"CPU": 2},
            placement_strategy="SPREAD",
        ),
        run_config=_run_config(
            tmp_path, "elastic_grow",
            failure_config=FailureConfig(max_failures=3),
        ),
    ).fit()
    t.join()
    assert result.metrics["step"] == 43
    worlds = [m["world"] for m in result.metrics_history]
    assert 1 in worlds, f"expected shrink to world=1, saw {set(worlds)}"
    # after the shrink, the world grew back to 2 and training RESUMED
    # (later steps at world=2 than the last world=1 step)
    last_w1 = max(i for i, w in enumerate(worlds) if w == 1)
    assert any(w == 2 for w in worlds[last_w1 + 1:]), (
        f"no grow-back after shrink: {worlds}"
    )


def test_megascale_env_rendezvous(tmp_path):
    """get_tpu_coordinator_env_vars output actually lets two simulated
    slices rendezvous: two processes run jax.distributed.initialize with
    the generated MEGASCALE/coordinator settings and agree on the process
    count (reference: util/tpu.py:205 + train/v2/jax/config.py)."""
    import socket
    import subprocess
    import sys

    from ray_tpu.util.tpu import get_tpu_coordinator_env_vars

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    coord = f"127.0.0.1:{port}"

    script = """
import json, os, sys
import jax
jax.config.update("jax_platforms", "cpu")
jax.distributed.initialize(
    coordinator_address=os.environ["RT_COORD"],
    num_processes=2,
    process_id=int(os.environ["RT_PID"]),
)
print(json.dumps({
    "procs": jax.process_count(),
    "idx": jax.process_index(),
    "megascale": {
        k: v for k, v in os.environ.items() if k.startswith("MEGASCALE")
    },
}), flush=True)
"""
    procs = []
    for slice_id in range(2):
        env = dict(
            os.environ,
            RT_COORD=coord,
            RT_PID=str(slice_id),
            JAX_PLATFORMS="cpu",
            **get_tpu_coordinator_env_vars(coord, 2, slice_id),
        )
        procs.append(subprocess.Popen(
            [sys.executable, "-c", script],
            stdout=subprocess.PIPE, text=True, env=env,
        ))
    outs = []
    for p in procs:
        out, _ = p.communicate(timeout=180)
        assert p.returncode == 0
        outs.append(json.loads(out.strip().splitlines()[-1]))
    assert {o["idx"] for o in outs} == {0, 1}
    assert all(o["procs"] == 2 for o in outs)
    assert all(
        o["megascale"]["MEGASCALE_COORDINATOR_ADDRESS"] == coord
        for o in outs
    )
    assert {o["megascale"]["MEGASCALE_SLICE_ID"] for o in outs} == {"0", "1"}
