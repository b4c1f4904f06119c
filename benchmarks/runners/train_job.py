"""Runner ``train_job``: a cell trained through ``JaxTrainer.fit()`` with
``default_jax_train_loop`` unmodified, one worker holding the machine's
chips.

The window is bounded without touching the program: ``timed_train_loop``
wraps the default loop in the worker, stamps the host clock at every
``report()`` and, once ``seconds`` have passed since the last warm-up
step's report, sets the context's ``stop_event``. The loop checks it after
its next dispatch and leaves without the final checkpoint (4-19 GB that no
run should pay for). Chosen over sizing ``num_steps`` from a recorded step
time: that would put a number measured on one commit into the data file
and make the window's length drift with every optimisation.
"""
from __future__ import annotations

import functools
import math
import os
import shutil
import threading
import time

import numpy as np

from benchmarks.lib import cluster, named, peaks, program


def timed_train_loop(config: dict):
    """Runs in the train worker (the chip-holding process)."""
    from ray_tpu.train.context import get_context
    from ray_tpu.train.trainer import default_jax_train_loop

    config = dict(config)
    bench = config.pop("_bench")
    ctx = get_context()
    inner_report = ctx.report
    seen = {"reports": 0, "window_start": None, "trace_thread": None}

    def capture():
        from benchmarks.lib.capture import capture as profiler_capture

        time.sleep(bench["trace_after_s"])
        profiler_capture(bench["trace_s"], bench["trace_dir"])

    def report(metrics, checkpoint=None):
        now = time.monotonic()
        seen["reports"] += 1
        if seen["reports"] == bench["warmup_steps"]:
            seen["window_start"] = now
            if bench["trace_s"] > 0:
                seen["trace_thread"] = threading.Thread(
                    target=capture, daemon=True, name="bench-trace")
                seen["trace_thread"].start()
        inner_report(dict(metrics, bench_clock_s=now), checkpoint)
        start = seen["window_start"]
        if start is not None and now - start >= bench["seconds"]:
            ctx.stop_event.set()

    ctx.report = report
    try:
        return default_jax_train_loop(config)
    finally:
        # the worker is killed once this returns: let the profiler finish
        # writing first (four chips' worth of a 48-layer step takes a while)
        if seen["trace_thread"] is not None:
            seen["trace_thread"].join(timeout=300)


def first_batch(seed: int, vocab_size: int, batch: int, seq_len: int):
    """The first batch the default loop draws from ``data_seed``."""
    return np.random.default_rng(seed).integers(
        0, vocab_size, (batch, seq_len + 1), dtype=np.int32)


def reference_first_loss(config: dict, tokens, block: int) -> float:
    """Plain float32 loss of the first batch under the program's own
    initial weights (PRNGKey(0)), by the reference the configuration names,
    on this process's first device — called only after the cluster has
    released the chip."""
    import jax

    from benchmarks.lib import reference

    loss = functools.partial(reference.loss, reference.logits_of(config))
    with jax.default_device(jax.devices()[0]):
        params = reference.program_initial_weights(config)
        per_block = reference.in_blocks(loss, params, tokens, block)
    return float(np.mean(per_block))


def run(cell: dict, config: dict, *, seed: int, seconds: float, trace: bool,
        platform: str, chips: int, started: float) -> dict:
    import ray_tpu
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    job, model = cell["job"], dict(config["model"])
    batch, seq_len = int(job["batch_size"]), int(job["seq_len"])
    warmup = int(job["warmup_steps"])
    trace_dir = os.path.join(cluster.WORK_DIR, "trace", cell["name"])
    shutil.rmtree(trace_dir, ignore_errors=True)
    storage = os.path.join(cluster.WORK_DIR, "train_storage")
    shutil.rmtree(storage, ignore_errors=True)
    loop_config = {
        "model": program.trainer_model(config),
        "mesh": job["mesh"],
        "optimizer": job.get("optimizer", {}),
        "num_steps": 10 ** 9,  # the stop_event ends the job, not a count
        "batch_size": batch,
        "seq_len": seq_len,
        "checkpoint_every": 0,
        "data_seed": seed,
        "_bench": {
            "warmup_steps": warmup, "seconds": float(seconds),
            "trace_s": float(job["trace_seconds"]) if trace else 0.0,
            "trace_after_s": float(job["trace_after_seconds"]),
            "trace_dir": trace_dir,
        },
    }
    cluster.start("train")
    try:
        result = JaxTrainer(
            timed_train_loop,
            train_loop_config=loop_config,
            scaling_config=ScalingConfig(
                num_workers=1, use_tpu=(platform == "tpu")),
            run_config=RunConfig(name=cell["name"], storage_path=storage),
        ).fit()
        cluster.require(result.error is None, f"fit() failed: {result.error}")
        memory = cluster.node_memory_stats()
    finally:
        ray_tpu.shutdown()
        shutil.rmtree(storage, ignore_errors=True)

    history = result.metrics_history
    cluster.require(len(history) > warmup + 1,
                    f"{len(history)} steps reported, {warmup} are warm-up")
    last = history[-1]
    device = {"platform": last["platform"], "kind": last["device_kind"],
              "count": int(last["device_count"]),
              "memory_peak_bytes": cluster.memory_peak_bytes(memory)}
    cluster.require(
        device["platform"] == platform and device["count"] == chips,
        f"the train worker computed on {device}, the cell needs {chips} "
        f"{platform} device(s)")

    # setup ends, and the window starts, at the last warm-up step's report
    clocks = [m["bench_clock_s"] for m in history]
    window = clocks[-1] - clocks[warmup - 1]
    steps = len(history) - warmup
    tokens_per_s_per_chip = steps * batch * seq_len / window / chips
    # bench_clock_s is time.monotonic() in the worker, `started` the same
    # clock in this process: one machine, one boot, one clock
    setup_s = clocks[warmup - 1] - started
    step_times = np.diff(clocks[warmup - 1:])
    cluster.log({
        "cell": cell["name"], "steps_measured": steps, "window_s": window,
        "step_s_median": float(np.median(step_times)),
        "step_s_max": float(step_times.max()),
        "first_step_s": clocks[0] - started,
        "losses_first_last": [history[0]["loss"], last["loss"]],
        "memory_stats": memory[:1],
    })

    losses = [m["loss"] for m in history]
    finite = all(math.isfinite(x) for x in losses)
    tokens = first_batch(seed, model["vocab_size"], batch, seq_len)
    ref = reference_first_loss(config, tokens, int(job["reference_block"]))
    tol = float(job["first_loss_tolerance"])
    close = abs(losses[0] - ref) <= tol
    cluster.log({"cell": cell["name"], "first_loss": losses[0],
                 "reference_first_loss": ref, "tolerance": tol,
                 "all_losses_finite": finite})

    flops = named.load(config["files"]["costs"]).train_flops_per_token(
        model, seq_len)
    peak = peaks.peaks_for(device["kind"])["bf16_flops_per_s"] \
        if platform == "tpu" else None
    return {
        "correct": bool(finite and close),
        "attempted": steps, "failed": 0,
        "end_to_end": {
            "train_tokens_per_s_per_chip": tokens_per_s_per_chip,
            "setup_s": setup_s,
        },
        "device": device,
        "trace_dir": trace_dir if trace else None,
        # what the per-layer readers may use besides the trace
        "facts": {
            "tokens_per_s_per_chip": tokens_per_s_per_chip,
            "flops_per_token": flops, "peak_flops_per_s": peak,
            "batch_per_chip": batch // chips, "seq_len": seq_len,
            "model": model, "chips": chips, "device_kind": device["kind"],
            "train_program": job["train_program"],
        },
    }
