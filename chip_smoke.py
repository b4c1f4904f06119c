"""Bring-up proof: drive the Trainer and Serve->engine paths on the chip.

    python chip_smoke.py            # one TPU chip: train phase, serve phase
    python chip_smoke.py --chips 4  # four chips: sharded train vs one device

Both paths go through the entry points a user calls — ``JaxTrainer.fit()``
with ``default_jax_train_loop`` and ``serve.run(build_openai_app(...))``
behind the HTTP proxy — at GPT-2-small's published width (vocab 50304, seq
1024, 12 layers, 12 heads, embed 768, bf16) with random weights from a seed.
Each phase runs inside its own ``ray_tpu.init()`` ... ``shutdown()``, so the
node process that held the chip has exited before the next one starts. This
process never initialises a JAX backend: a chip belongs to one process, and
that process is the node. What the device is (platform, kind, count) is read
there and passed back.

Every stdout line is one JSON object. The last one is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``
and is printed only if every check of every phase passed on a TPU; any
failed check, phase exception, other platform or native library that did not
build exits non-zero without it. The same lines are appended to
``chiprun_out/chip_smoke.jsonl`` (worker logs go to ``chiprun_out/`` too).

The phase functions take the model configuration and the expected platform,
so ``tests/test_chip_smoke.py`` drives them on the CPU at a toy size with
``attention_impl="flash_interpret"``. ``main()`` accepts only ``tpu``.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import shutil
import statistics
import sys
import tempfile
import threading
import time
import urllib.request
from typing import Callable, List, Optional

REPO = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(REPO, "chiprun_out")

GPT2_SMALL = {
    "vocab_size": 50304, "max_seq_len": 1024, "num_layers": 12,
    "num_heads": 12, "embed_dim": 768,
}
# B=32 needs remat: the v5e compiler refuses the step without it (25.85 GB
# of 15.75 GB HBM) and accepts it with the "dots" policy (10.5 GB of
# temporaries).
TRAIN_BATCH = 32
TRAIN_STEPS = 6
# bf16 has 8 mantissa bits: one ulp at the outputs' magnitude (|x| < 8) is
# 2**-5. Flash and XLA attention accumulate in f32 in different orders, so
# elements may differ by about one rounding of the result.
PARITY_SHAPE = (4, 12, 1024, 64)   # [B, H, T, D], heads-major
PARITY_MAX_ABS = 2.0 ** -4
PARITY_REL_FRO = 1e-2
# Sharded vs one-device losses differ only by the order of f32 sums and
# bf16 roundings: 8.1e-5 measured on four chips (my chip run, PR 21); the
# tolerance is about ten times that. The comparison runs without warm-up:
# the default schedule's first updates have a learning rate of ~0, and a leg
# that dropped its update would then differ by nothing.
SHARDED_LOSS_TOL = 1e-3
SHARDED_OPTIMIZER = {"warmup_steps": 0}

Emit = Callable[[dict], None]


class SmokeFailure(Exception):
    """A check of a phase did not hold."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


# --------------------------------------------------- code run on the node
# These run in the process that holds the chip (remote tasks / the train
# worker); they are the only code of this file that touches JAX.


def _attention_parity(shape, impl: str, mesh_axes: Optional[dict] = None
                      ) -> dict:
    """flash_attention vs attention_xla: outputs and q/k/v grads. With
    ``mesh_axes`` the inputs are sharded over that mesh of this process's
    devices (batch over data/fsdp, heads over tensor) and the dispatcher is
    given the mesh, as the sharded train step gives it."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ray_tpu.ops.attention import attention, attention_xla
    from ray_tpu.parallel.mesh import MeshConfig

    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q, k, v, g = (
        jax.random.normal(kk, shape, jnp.float32).astype(jnp.bfloat16)
        for kk in ks
    )
    mesh = None
    if mesh_axes:
        mesh = MeshConfig(**mesh_axes).build()
        q, k, v, g = jax.device_put((q, k, v, g), NamedSharding(
            mesh, P(("data", "fsdp"), "tensor", None, None)))

    def fwd_bwd(fn):
        def run(q, k, v, g):
            out, vjp = jax.vjp(fn, q, k, v)
            return (out, *vjp(g))

        return jax.jit(run)

    resolved = jax.jit(
        lambda q, k, v: attention(q, k, v, causal=True, impl="auto",
                                  mesh=mesh)
    ).lower(q, k, v).as_text()
    got = fwd_bwd(
        lambda q, k, v: attention(q, k, v, causal=True, impl=impl, mesh=mesh)
    )(q, k, v, g)
    want = fwd_bwd(lambda q, k, v: attention_xla(q, k, v, causal=True))(
        q, k, v, g
    )
    out = {
        "shape": list(shape),
        "impl": impl,
        "mesh": mesh_axes,
        "devices_holding_out": len(got[0].sharding.device_set),
        "auto_resolves_to": (
            "flash" if "tpu_custom_call" in resolved else "xla"
        ),
    }
    for name, a, b in zip(("out", "dq", "dk", "dv"), got, want):
        a = np.asarray(a.astype(jnp.float32))
        b = np.asarray(b.astype(jnp.float32))
        out[name] = {
            "max_abs_diff": float(np.abs(a - b).max()),
            "rel_fro": float(np.linalg.norm(a - b) / np.linalg.norm(b)),
            "finite": bool(np.isfinite(a).all()),
        }
    return out


def _node_report() -> dict:
    """Device triple, per-device memory and native planes of this node."""
    import jax

    from ray_tpu import native
    from ray_tpu._private.accelerators.tpu import local_device_info

    return {
        **local_device_info(),
        "pid": os.getpid(),
        "memory_stats": [
            {"id": d.id, **{
                k: v for k, v in (d.memory_stats() or {}).items()
                if k in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")
            }}
            for d in jax.devices()
        ],
        "native_libs": native.load_report(),
        "compilation_cache_dir": jax.config.jax_compilation_cache_dir,
    }


def one_device_train_loop(config: dict):
    """The comparison leg of ``--chips 4``: ``default_jax_train_loop``'s
    model, seed and global batch on a mesh of ONE of this process's
    devices (the default loop always spans all of them)."""
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ray_tpu.models.gpt2 import GPT2Config
    from ray_tpu.parallel.mesh import MeshConfig, make_mesh
    from ray_tpu.train.context import report
    from ray_tpu.train.step import (
        OptimizerConfig, create_train_state, make_train_step,
    )

    model = dict(config["model"])
    model["dtype"] = jax.numpy.dtype(model["dtype"]).type
    cfg = GPT2Config(**model)
    mesh = make_mesh(MeshConfig(data=1), devices=jax.devices()[:1])
    opt = OptimizerConfig(**config.get("optimizer", {})).build()
    state = create_train_state(cfg, opt, jax.random.PRNGKey(0), mesh)
    step_fn = make_train_step(cfg, opt, mesh)
    rng = np.random.default_rng(int(config["data_seed"]))
    sharding = NamedSharding(mesh, P(("data", "fsdp"), None))
    for step in range(int(config["num_steps"])):
        toks = rng.integers(
            0, cfg.vocab_size,
            (int(config["batch_size"]), int(config["seq_len"]) + 1),
            dtype=np.int32,
        )
        state, metrics = step_fn(
            state, jax.device_put({"tokens": toks}, {"tokens": sharding})
        )
        report({"loss": float(metrics["loss"]), "step": step + 1})


# ------------------------------------------------------- driver-side code


def _discovery() -> dict:
    """How the chip was found (no JAX: device files and environment)."""
    import ray_tpu
    from ray_tpu._private.accelerators import tpu

    return {
        "device_files": {
            p: tpu._vfio_group_vendors(os.path.basename(p))
            for p in tpu._chip_device_files()
        },
        "env": {
            k: os.environ[k] for k in (
                "TPU_ACCELERATOR_TYPE", "TPU_CHIPS_PER_HOST_BOUNDS",
                "TPU_SKIP_MDS_QUERY", "JAX_PLATFORMS",
            ) if k in os.environ
        },
        "cluster_tpu_resource": ray_tpu.cluster_resources().get("TPU", 0),
    }


def _start_cluster(phase: str, out_dir: Optional[str]):
    import ray_tpu

    if out_dir:
        # worker stdout/stderr files: what to read when a phase dies
        os.environ["RT_SESSION_DIR"] = os.path.join(out_dir, f"logs_{phase}")
    ray_tpu.init(num_cpus=8, num_nodes=1)


def _remote_on_chip(fn, tpus: float):
    """``fn`` as a task in the node process (it asks for the chips when
    there are any, so it can only land on the node that holds them)."""
    import ray_tpu

    if not tpus:
        return ray_tpu.remote(fn)
    return ray_tpu.remote(num_tpus=int(tpus))(fn)


def _check_node(report: dict, expected_platform: str) -> None:
    check(report["platform"] == expected_platform,
          f"node computes on {report['platform']!r}, "
          f"expected {expected_platform!r}")
    from ray_tpu import native

    if native.toolchain_available():
        missing = [k for k, ok in report["native_libs"].items() if not ok]
        check(not missing, f"native libraries did not build/load: {missing}")


def _check_parity(parity: dict, on_tpu: bool) -> None:
    if on_tpu:
        check(parity["auto_resolves_to"] == "flash",
              f"attention_impl='auto' did not resolve to the pallas kernel "
              f"on tpu (mesh {parity['mesh']})")
    for name in ("out", "dq", "dk", "dv"):
        d = parity[name]
        check(d["finite"] and d["max_abs_diff"] <= PARITY_MAX_ABS
              and d["rel_fro"] <= PARITY_REL_FRO,
              f"flash vs xla {name} (mesh {parity['mesh']}): {d} exceeds "
              f"max_abs {PARITY_MAX_ABS} / rel_fro {PARITY_REL_FRO}")


def _train_config(model: dict, *, attention_impl: str, batch_size: int,
                  num_steps: int, seed: int, mesh: dict,
                  optimizer: Optional[dict] = None) -> dict:
    return {
        "optimizer": optimizer or {},
        "model": {**model, "dtype": "bfloat16", "remat": True,
                  "attention_impl": attention_impl},
        "mesh": mesh,
        "num_steps": num_steps,
        "batch_size": batch_size,
        "seq_len": model["max_seq_len"],
        "checkpoint_every": 0,  # one checkpoint, at the end
        "data_seed": seed,
    }


def _fit(train_loop, config: dict, *, use_tpu: bool, name: str,
         storage: str):
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    t0 = time.monotonic()
    result = JaxTrainer(
        train_loop,
        train_loop_config=config,
        scaling_config=ScalingConfig(num_workers=1, use_tpu=use_tpu),
        run_config=RunConfig(name=name, storage_path=storage),
    ).fit()
    check(result.error is None, f"{name}: fit() failed: {result.error}")
    return result, time.monotonic() - t0


def _losses(result, vocab_size: int, num_steps: int, name: str) -> List[float]:
    losses = [m["loss"] for m in result.metrics_history]
    check(len(losses) == num_steps,
          f"{name}: {len(losses)} reports for {num_steps} steps")
    check(all(math.isfinite(x) for x in losses),
          f"{name}: non-finite loss in {losses}")
    check(abs(losses[0] - math.log(vocab_size)) < 1.0,
          f"{name}: step-1 loss {losses[0]:.3f} is not within 1.0 of "
          f"ln({vocab_size})={math.log(vocab_size):.3f}")
    return losses


def _device_triple(metrics: dict) -> dict:
    """The device as the train worker's ``report()`` carried it."""
    return {"platform": metrics["platform"], "kind": metrics["device_kind"],
            "count": metrics["device_count"]}


def _step_seconds(result, batch_size: int, seq_len: int) -> List[float]:
    return [
        batch_size * seq_len / m["tokens_per_sec"]
        for m in result.metrics_history
    ]


def train_phase(model: dict, *, expected_platform: str, attention_impl: str,
                batch_size: int, num_steps: int, parity_shape, seed: int,
                emit: Emit, out_dir: Optional[str] = None) -> dict:
    """``JaxTrainer.fit()`` on one worker that holds every chip the node
    advertises, then flash-vs-XLA parity in the same process. Returns the
    device triple as the worker reported it."""
    import ray_tpu

    on_tpu = expected_platform == "tpu"
    t_phase = time.monotonic()
    storage = tempfile.mkdtemp(prefix="chip_smoke_train_")
    _start_cluster("train", out_dir)
    try:
        emit({"phase": "train", "discovery": _discovery()})
        cfg = _train_config(
            model, attention_impl=attention_impl, batch_size=batch_size,
            num_steps=num_steps, seed=seed, mesh={"data": -1},
        )
        result, fit_s = _fit(
            None, cfg, use_tpu=on_tpu, name="smoke_train", storage=storage
        )
        losses = _losses(result, model["vocab_size"], num_steps, "train")
        last = result.metrics
        check(last["platform"] == expected_platform,
              f"train worker ran on {last['platform']!r}, expected "
              f"{expected_platform!r}")
        ckpt = result.checkpoint
        check(ckpt is not None and os.path.isdir(ckpt.path)
              and os.listdir(ckpt.path), "Result carries no checkpoint")
        ckpt_bytes = sum(
            os.path.getsize(os.path.join(d, f))
            for d, _, files in os.walk(ckpt.path) for f in files
        )
        steps_s = _step_seconds(result, batch_size, model["max_seq_len"])
        steady = statistics.median(steps_s[1:])
        tpus = ray_tpu.cluster_resources().get("TPU", 0) if on_tpu else 0
        parity = ray_tpu.get(
            _remote_on_chip(_attention_parity, tpus).remote(
                tuple(parity_shape),
                "flash" if attention_impl == "auto" else attention_impl,
            ),
            timeout=600,
        )
        node = ray_tpu.get(
            _remote_on_chip(_node_report, tpus).remote(), timeout=120
        )
        emit({
            "phase": "train", "fit_seconds": fit_s, "losses": losses,
            "batch_size": batch_size, "remat": True, "steps": num_steps,
            "first_step_seconds": steps_s[0],
            "steady_step_seconds": steady,
            "compile_seconds": steps_s[0] - steady,
            "tokens_per_sec_steady": batch_size * model["max_seq_len"]
            / steady,
            "checkpoint_bytes": ckpt_bytes,
            "attention_parity": parity,
            "node": node,
        })
        _check_parity(parity, on_tpu)
        _check_node(node, expected_platform)
        emit({"phase": "train", "ok": True,
              "wall_seconds": time.monotonic() - t_phase})
        return _device_triple(last)
    finally:
        ray_tpu.shutdown()
        shutil.rmtree(storage, ignore_errors=True)


def _post_completion(port: int, payload: dict) -> dict:
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/v1/completions",
        data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"},
    )
    t0 = time.monotonic()
    with urllib.request.urlopen(req, timeout=600) as r:
        status, body = r.status, json.loads(r.read())
    return {"status": status, "body": body,
            "seconds": time.monotonic() - t0}


def serve_phase(model: dict, *, expected_platform: str, max_batch_slots: int,
                prefill_buckets, max_tokens: int, emit: Emit,
                out_dir: Optional[str] = None) -> None:
    """``serve.run(build_openai_app(...))``, the replica holding one chip,
    then concurrent greedy ``/v1/completions`` through the HTTP proxy."""
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.llm import LLMConfig, build_openai_app

    on_tpu = expected_platform == "tpu"
    t_phase = time.monotonic()
    _start_cluster("serve", out_dir)
    try:
        config = LLMConfig(
            model_id="gpt2-small-random", dtype="bfloat16",
            max_batch_slots=max_batch_slots,
            prefill_buckets=tuple(prefill_buckets),
            deployment_config=(
                {"ray_actor_options": {"num_tpus": 1}} if on_tpu else {}
            ),
            **model,
        )
        t0 = time.monotonic()
        # a cold replica takes the chip (~10 s) and compiles its parameter
        # init before it is ready; the default wait is 60 s
        handle = serve.run(
            build_openai_app(config), name="llm", route_prefix="/v1",
            _blocking_timeout=300.0,
        )
        port = serve.start_http_proxy()
        info = handle.replica_info.remote().result(timeout=300)
        deploy_s = time.monotonic() - t0
        check(info["platform"] == expected_platform,
              f"replica computes on {info['platform']!r}, expected "
              f"{expected_platform!r}")
        # Two identical prompts (greedy must agree token for token), the
        # rest distinct and of different lengths so several slots and more
        # than one prefill bucket are live in the same ticks.
        filler = max(prefill_buckets) // 40  # longest prompt: ~70% of it
        prompts = ["The chip answers."] * 2 + [
            f"request {i}: " + "tokens " * (filler * i) for i in range(1, 5)
        ]
        payloads = [
            {"prompt": p, "max_tokens": max_tokens, "temperature": 0.0,
             "logprobs": 1}
            for p in prompts
        ]
        # one warm-up request compiles the bucket, insert and decode programs
        warm = _post_completion(port, payloads[0])
        answers: List[Optional[dict]] = [None] * len(payloads)
        errors: List[str] = []

        def client(i: int):
            try:
                answers[i] = _post_completion(port, payloads[i])
            except Exception as e:  # noqa: BLE001 — reported, then fails
                errors.append(f"request {i}: {type(e).__name__}: {e}")

        t0 = time.monotonic()
        threads = [
            threading.Thread(target=client, args=(i,))
            for i in range(len(payloads))
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=900)
        burst_s = time.monotonic() - t0
        check(not errors and all(a is not None for a in answers),
              f"requests failed: {errors}")
        stats = handle.replica_info.remote().result(timeout=60)
        tokens = [
            a["body"]["choices"][0]["logprobs"]["tokens"] for a in answers
        ]
        emit({
            "phase": "serve", "deploy_seconds": deploy_s,
            "first_request_seconds": warm["seconds"],
            "burst_seconds": burst_s, "concurrent_requests": len(payloads),
            "request_seconds": [a["seconds"] for a in answers],
            "completion_tokens": [
                a["body"]["usage"]["completion_tokens"] for a in answers
            ],
            "prompt_tokens": [
                a["body"]["usage"]["prompt_tokens"] for a in answers
            ],
            "first_tokens": [t[:8] for t in tokens],
            "replica": stats,
        })
        for i, a in enumerate([warm] + answers):
            check(a["status"] == 200, f"request {i}: HTTP {a['status']}")
            check("error" not in a["body"], f"request {i}: {a['body']}")
            n = a["body"]["usage"]["completion_tokens"]
            check(n == max_tokens,
                  f"request {i}: {n} tokens returned, {max_tokens} asked")
        check(tokens[0] == tokens[1] and
              answers[0]["body"]["choices"][0]["text"]
              == answers[1]["body"]["choices"][0]["text"],
              "two identical greedy requests returned different tokens: "
              f"{tokens[0]} vs {tokens[1]}")
        check(stats["engine_stats"]["requests"] >= len(payloads) + 1,
              f"engine saw {stats['engine_stats']['requests']} requests")
        emit({"phase": "serve", "ok": True,
              "wall_seconds": time.monotonic() - t_phase})
    finally:
        try:
            serve.shutdown()
        finally:
            ray_tpu.shutdown()


def sharded_phase(model: dict, *, expected_platform: str,
                  attention_impl: str, mesh: dict, batch_size: int,
                  num_steps: int, parity_shape, seed: int, emit: Emit,
                  out_dir: Optional[str] = None) -> dict:
    """The train phase on ``mesh`` over every chip of the node, then the
    same model, seed and global batch on one device of the same process;
    the first losses must agree. Then flash-vs-XLA parity with the kernel
    under the dispatcher's shard_map on that mesh, which also shows what
    ``auto`` lowers to inside a sharded program."""
    import ray_tpu

    on_tpu = expected_platform == "tpu"
    t_phase = time.monotonic()
    storage = tempfile.mkdtemp(prefix="chip_smoke_sharded_")
    _start_cluster("sharded", out_dir)
    try:
        emit({"phase": "sharded", "discovery": _discovery()})
        tpus = ray_tpu.cluster_resources().get("TPU", 0) if on_tpu else 0
        cfg = _train_config(
            model, attention_impl=attention_impl, batch_size=batch_size,
            num_steps=num_steps, seed=seed, mesh=mesh,
            optimizer=SHARDED_OPTIMIZER,
        )
        sharded, sharded_s = _fit(
            None, cfg, use_tpu=on_tpu, name="smoke_sharded", storage=storage
        )
        node_after_sharded = ray_tpu.get(
            _remote_on_chip(_node_report, tpus).remote(), timeout=120
        )
        single, single_s = _fit(
            one_device_train_loop, cfg, use_tpu=on_tpu, name="smoke_single",
            storage=storage,
        )
        node_after_single = ray_tpu.get(
            _remote_on_chip(_node_report, tpus).remote(), timeout=120
        )
        parity = ray_tpu.get(
            _remote_on_chip(_attention_parity, tpus).remote(
                tuple(parity_shape),
                "flash" if attention_impl == "auto" else attention_impl,
                mesh,
            ),
            timeout=600,
        )
        a = _losses(sharded, model["vocab_size"], num_steps, "sharded")
        b = _losses(single, model["vocab_size"], num_steps, "single")
        last = sharded.metrics
        emit({
            "phase": "sharded", "mesh": mesh, "batch_size": batch_size,
            "attention_parity": parity,
            "sharded_losses": a, "one_device_losses": b,
            "max_loss_diff": max(abs(x - y) for x, y in zip(a, b)),
            "sharded_fit_seconds": sharded_s,
            "one_device_fit_seconds": single_s,
            "sharded_step_seconds": _step_seconds(
                sharded, batch_size, model["max_seq_len"]),
            "memory_after_sharded": node_after_sharded["memory_stats"],
            "memory_after_one_device": node_after_single["memory_stats"],
            "node": {k: v for k, v in node_after_single.items()
                     if k != "memory_stats"},
        })
        check(last["platform"] == expected_platform,
              f"train worker ran on {last['platform']!r}")
        for i, (x, y) in enumerate(zip(a[:3], b[:3])):
            check(abs(x - y) <= SHARDED_LOSS_TOL,
                  f"step {i + 1}: sharded loss {x:.4f} vs one-device "
                  f"{y:.4f} differ by more than {SHARDED_LOSS_TOL}")
        _check_parity(parity, on_tpu)
        check(parity["devices_holding_out"] == last["device_count"],
              f"sharded attention left its output on "
              f"{parity['devices_holding_out']} of {last['device_count']} "
              f"devices")
        peaks = [m.get("peak_bytes_in_use", 0)
                 for m in node_after_sharded["memory_stats"]]
        if on_tpu:
            check(min(peaks) > 0.5 * max(peaks),
                  f"state is not spread over the devices: peaks {peaks}")
        _check_node(node_after_single, expected_platform)
        emit({"phase": "sharded", "ok": True,
              "wall_seconds": time.monotonic() - t_phase})
        return _device_triple(last)
    finally:
        ray_tpu.shutdown()
        shutil.rmtree(storage, ignore_errors=True)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1)
    parser.add_argument("--seed", type=int, default=0)
    args = parser.parse_args(argv)

    # Node processes inherit the variable; nothing else places the cache.
    os.environ.setdefault(
        "JAX_COMPILATION_CACHE_DIR", os.path.join(REPO, ".jax_cache")
    )
    os.makedirs(OUT_DIR, exist_ok=True)
    log_path = os.path.join(OUT_DIR, "chip_smoke.jsonl")

    def emit(record: dict) -> None:
        line = json.dumps(record)
        print(line, flush=True)
        with open(log_path, "a") as f:
            f.write(line + "\n")

    from ray_tpu._private.accelerators import TPUAcceleratorManager

    found = TPUAcceleratorManager.get_current_node_num_accelerators()
    if found < args.chips:
        print(f"chip_smoke: needs {args.chips} TPU chip(s), this machine "
              f"exposes {found}", file=sys.stderr)
        return 2
    emit({"phase": "start", "chips": args.chips, "seed": args.seed,
          "compilation_cache_dir": os.environ["JAX_COMPILATION_CACHE_DIR"],
          "argv": sys.argv[1:]})
    try:
        if args.chips == 4:
            device = sharded_phase(
                GPT2_SMALL, expected_platform="tpu", attention_impl="auto",
                mesh={"data": 1, "fsdp": 2, "tensor": 2},
                batch_size=TRAIN_BATCH, num_steps=TRAIN_STEPS,
                parity_shape=PARITY_SHAPE, seed=args.seed, emit=emit,
                out_dir=OUT_DIR,
            )
        else:
            device = train_phase(
                GPT2_SMALL, expected_platform="tpu", attention_impl="auto",
                batch_size=TRAIN_BATCH, num_steps=TRAIN_STEPS,
                parity_shape=PARITY_SHAPE, seed=args.seed, emit=emit,
                out_dir=OUT_DIR,
            )
            serve_phase(
                GPT2_SMALL, expected_platform="tpu", max_batch_slots=8,
                prefill_buckets=(64, 128, 256, 512), max_tokens=32,
                emit=emit, out_dir=OUT_DIR,
            )
        check(device["platform"] == "tpu" and device["count"] == args.chips,
              f"expected {args.chips} tpu device(s), the worker saw {device}")
        if "jax" in sys.modules:
            from jax._src import xla_bridge

            check(not xla_bridge.backends_are_initialized(),
                  "the driver process initialised a JAX backend")
    except Exception as e:  # noqa: BLE001 — any failure is the verdict
        import traceback

        traceback.print_exc()
        with open(log_path, "a") as f:
            f.write(json.dumps(
                {"ok": False, "error": f"{type(e).__name__}: {e}"}) + "\n")
        return 1
    emit({"ok": True, "device": device})
    return 0


if __name__ == "__main__":
    sys.exit(main())
