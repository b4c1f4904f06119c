"""Round benchmark: prints ONE JSON line with the headline metric.

Headline = single_client_tasks_async vs the reference's checked-in number
(BASELINE.md: 7,096.8 tasks/s on a release CPU node). Extra fields carry the
other core microbenchmarks plus GPT-2 train throughput on the local
accelerator (tokens/sec/chip — the BASELINE.json north star; the reference
publishes no TPU number for it, so vs_baseline stays anchored to tasks/s).

Usage: python bench.py [--quick] [--no-train]
"""
from __future__ import annotations

import argparse
import json
import os
import time

# Persistent XLA compilation cache: compiles are paid once per machine, not
# once per bench run. Where JAX_COMPILATION_CACHE_DIR is set from outside it
# is used as it is. Must be set before jax initializes.
os.environ.setdefault(
    "JAX_COMPILATION_CACHE_DIR",
    os.path.join(os.path.dirname(os.path.abspath(__file__)), ".jax_cache"),
)

BASELINE_TASKS_ASYNC = 7096.8  # reference release/perf_metrics/microbenchmark.json

# Peak bf16 FLOP/s per chip, keyed by ``device_kind`` as JAX reports it.
# Source: Google Cloud documentation, "TPU v5e" system architecture page.
# A device that is not in the table is an error, not a default.
PEAK_BF16_FLOPS = {
    "TPU v5 lite": 197e12,
}


def peak_bf16_flops(device_kind: str) -> float:
    if device_kind not in PEAK_BF16_FLOPS:
        raise KeyError(
            f"no peak FLOP/s on record for device_kind {device_kind!r}; "
            f"known: {sorted(PEAK_BF16_FLOPS)}"
        )
    return PEAK_BF16_FLOPS[device_kind]


def measure_achievable_tflops() -> float:
    """Measured matmul roof of the local accelerator (bf16 4k x 4k,
    chained INSIDE one jit so per-dispatch overhead cannot deflate the
    roof).

    MFU against the nominal datasheet peak can be misleading: real chips
    execute below it even on pure matmul chains. Reporting the measured
    roof lets
    `gpt2_train_mfu_vs_achievable` say how close the train step is to what
    this device can actually do."""
    import time as _t

    import jax
    import jax.numpy as jnp

    # Transformer-MLP-shaped chain with resident weights — the sustained
    # rate a well-tiled model layer can actually reach.
    M, E, H = 32 * 1024, 1024, 4096
    inner = 12
    x = jnp.full((M, E), 1.0 / E, jnp.bfloat16)
    w1 = jnp.full((E, H), 1.0 / H, jnp.bfloat16)
    w2 = jnp.full((H, E), 1.0 / E, jnp.bfloat16)

    @jax.jit
    def chain(x):
        for _ in range(inner):
            x = (x @ w1) @ w2
        return x

    out = chain(x)
    float(jnp.sum(out[:1, :1]))  # real device->host sync
    steps = 5
    t0 = _t.perf_counter()
    for _ in range(steps):
        out = chain(out)
    float(jnp.sum(out[:1, :1]))
    dt = _t.perf_counter() - t0
    return 2 * M * E * H * 2 * inner * steps / dt


def require_tpu():
    """The train legs measure the chip: anything else is an error, never a
    smaller model under the same metric name."""
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise RuntimeError(
            f"bench.py train legs need a TPU; JAX runs on {dev.platform!r} "
            f"({dev.device_kind}). Use --no-train off the chip."
        )
    return dev


def _release_device_memory() -> int:
    """Drop what a finished leg left on the chip — its compiled programs
    and any array only a cycle keeps alive — and return the bytes still in
    use. The legs share one process (one process per chip), so the next
    one needs the HBM the last one held."""
    import gc

    import jax

    gc.collect()
    jax.clear_caches()
    return (jax.devices()[0].memory_stats() or {}).get("bytes_in_use", 0)


def _time_train_steps(config, B: int, T: int, steps: int):
    """(tokens/s, final loss) of ``steps`` train steps of ``config`` on one
    repeated batch, after a compile+warm-up step."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.train.step import (
        OptimizerConfig,
        create_train_state,
        make_train_step,
    )

    opt = OptimizerConfig().build()
    state = create_train_state(config, opt, jax.random.PRNGKey(0))
    step = make_train_step(config, opt)
    rng = np.random.RandomState(0)
    batch = {
        "tokens": jnp.asarray(rng.randint(0, config.vocab_size, (B, T + 1)))
    }
    state, m = step(state, batch)  # compile
    jax.block_until_ready((jax.tree.leaves(state), m["loss"]))
    t0 = time.perf_counter()
    for _ in range(steps):
        state, m = step(state, batch)
    # Block on the FULL final state and read the loss on the host: the
    # timed region ends only when the last step's bytes exist.
    jax.block_until_ready(jax.tree.leaves(state))
    loss = float(m["loss"])
    dt = time.perf_counter() - t0
    return steps * B * T / dt, loss


def bench_train_tokens_per_sec(quick: bool = False):
    """GPT-2-small train step on the local chip. One configuration: B=32
    with remat ("dots" policy) — the v5e compiler refuses B=32 without remat
    (25.85 GB of 15.75 GB HBM; sandbox compile, PR 21)."""
    from ray_tpu.models import gpt2

    dev = require_tpu()
    peak = peak_bf16_flops(dev.device_kind)
    config = gpt2.GPT2Config(
        vocab_size=50304, max_seq_len=1024, num_layers=12, num_heads=12,
        embed_dim=768, remat=True,
    )
    B, T = 32, 1024
    tokens_per_sec, loss = _time_train_steps(
        config, B, T, steps=5 if quick else 20
    )
    flops = gpt2.flops_per_token(config) * tokens_per_sec
    out = {
        "gpt2_train_tokens_per_sec_per_chip": tokens_per_sec,
        "gpt2_train_loss": loss,
        "gpt2_train_mfu_est": flops / peak,
        "gpt2_train_remat": bool(config.remat),
        "gpt2_train_batch": B,
        "train_backend": dev.platform,
        "train_device_kind": dev.device_kind,
    }
    _release_device_memory()
    roof = measure_achievable_tflops()
    out["tpu_matmul_tflops_measured"] = roof / 1e12
    out["gpt2_train_mfu_vs_achievable"] = flops / roof
    _release_device_memory()
    ref = bench_reference_jax_step(quick=quick)
    out.update(ref)
    if ref:
        out["gpt2_train_vs_reference_impl"] = (
            tokens_per_sec / ref["gpt2_reference_impl_tokens_per_sec"]
        )
    if not quick:
        out["hbm_bytes_in_use_before_medium"] = _release_device_memory()
        out.update(bench_train_medium())
    return out


def bench_train_medium():
    """GPT-2-medium (350M) tokens/sec/chip — the BASELINE.md north-star
    model size, in this process (one process per chip). One configuration:
    B=16 with remat ("dots" policy)."""
    from ray_tpu.models import gpt2

    dev = require_tpu()
    config = gpt2.GPT2Config(
        vocab_size=50304, max_seq_len=1024, num_layers=24, num_heads=16,
        embed_dim=1024, remat=True,
    )
    B = 16
    tps, _ = _time_train_steps(config, B, 1024, steps=10)
    return {
        "gpt2_medium_tokens_per_sec_per_chip": tps,
        "gpt2_medium_mfu_est": (
            gpt2.flops_per_token(config) * tps
            / peak_bf16_flops(dev.device_kind)
        ),
        "gpt2_medium_remat": True,
        "gpt2_medium_batch": B,
    }


def bench_reference_jax_step(quick: bool = False):
    """A deliberately *stock* JAX GPT-2-small train step, written the way a
    typical user would (plain remat'd blocks, optax softmax-xent on full
    logits, no pallas / no vocab chunking / no fused policies). Same chip,
    same model dims, same token budget — the denominator the north-star
    metric needs in the absence of a torch-xla install (BASELINE.md: target
    >=90% of a stock SPMD implementation; we aim to beat it outright)."""
    import time as _t

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    if quick:
        return {}
    V, T, L, H, E = 50304, 1024, 12, 12, 768
    key = jax.random.PRNGKey(0)

    def init(key):
        ks = jax.random.split(key, 6)
        def nrm(k, shape, s=0.02):
            return (s * jax.random.normal(k, shape)).astype(jnp.float32)
        return {
            "wte": nrm(ks[0], (V, E)),
            "wpe": nrm(ks[1], (T, E)),
            "blocks": {
                "ln1": jnp.ones((L, E)), "ln1b": jnp.zeros((L, E)),
                "qkv": nrm(ks[2], (L, E, 3 * E)), "qkvb": jnp.zeros((L, 3 * E)),
                "proj": nrm(ks[3], (L, E, E)), "projb": jnp.zeros((L, E)),
                "ln2": jnp.ones((L, E)), "ln2b": jnp.zeros((L, E)),
                "fc": nrm(ks[4], (L, E, 4 * E)), "fcb": jnp.zeros((L, 4 * E)),
                "out": nrm(ks[5], (L, 4 * E, E)), "outb": jnp.zeros((L, E)),
            },
            "lnf": jnp.ones((E,)), "lnfb": jnp.zeros((E,)),
        }

    def ln(x, g, b):
        x32 = x.astype(jnp.float32)
        y = (x32 - x32.mean(-1, keepdims=True)) * jax.lax.rsqrt(
            x32.var(-1, keepdims=True) + 1e-5)
        return (y * g + b).astype(x.dtype)

    def block(x, lp):
        B = x.shape[0]
        h = ln(x, lp["ln1"], lp["ln1b"])
        qkv = (h @ lp["qkv"].astype(h.dtype)) + lp["qkvb"].astype(h.dtype)
        q, k, v = jnp.split(qkv.reshape(B, T, 3, 12, 64), 3, axis=2)
        q, k, v = (t[:, :, 0].transpose(0, 2, 1, 3) for t in (q, k, v))
        s = (q @ k.transpose(0, 1, 3, 2)) * (64 ** -0.5)
        mask = jnp.tril(jnp.ones((T, T), bool))
        s = jnp.where(mask, s.astype(jnp.float32), -1e30)
        p = jax.nn.softmax(s, axis=-1).astype(x.dtype)
        a = (p @ v).transpose(0, 2, 1, 3).reshape(B, T, E)
        x = x + (a @ lp["proj"].astype(x.dtype)) + lp["projb"].astype(x.dtype)
        h = ln(x, lp["ln2"], lp["ln2b"])
        h = jax.nn.gelu((h @ lp["fc"].astype(h.dtype)) + lp["fcb"].astype(h.dtype))
        return x + (h @ lp["out"].astype(h.dtype)) + lp["outb"].astype(h.dtype)

    def loss_fn(params, tokens):
        inp, tgt = tokens[:, :-1], tokens[:, 1:]
        x = params["wte"][inp].astype(jnp.bfloat16)
        x = x + params["wpe"][None].astype(jnp.bfloat16)
        body = jax.checkpoint(block)
        x, _ = jax.lax.scan(
            lambda c, lp: (body(c, lp), None), x, params["blocks"]
        )
        x = ln(x, params["lnf"], params["lnfb"])
        logits = (x @ params["wte"].T.astype(x.dtype)).astype(jnp.float32)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, tgt).mean()

    opt = optax.chain(optax.clip_by_global_norm(1.0), optax.adamw(3e-4))
    B = 16  # full f32 logits cap the feasible batch (5.6 GB of temporaries)
    params = init(key)
    opt_state = opt.init(params)

    @jax.jit
    def step(params, opt_state, tokens):
        l, g = jax.value_and_grad(loss_fn)(params, tokens)
        up, opt_state = opt.update(g, opt_state, params)
        return optax.apply_updates(params, up), opt_state, l

    rng = np.random.RandomState(0)
    tokens = jnp.asarray(rng.randint(0, V, (B, T + 1)))
    params, opt_state, l = step(params, opt_state, tokens)
    jax.block_until_ready(jax.tree.leaves(params)); float(l)
    n = 10
    t0 = _t.perf_counter()
    for _ in range(n):
        params, opt_state, l = step(params, opt_state, tokens)
    # same sync discipline as the framework-step timing above
    jax.block_until_ready(jax.tree.leaves(params)); float(l)
    rate = n * B * T / (_t.perf_counter() - t0)
    return {"gpt2_reference_impl_tokens_per_sec": rate}


def run_flight_benchmarks(quick: bool = False, phases: bool = False,
                          attrib_path: str = None) -> dict:
    """Flight-instrumented runs of the two ROADMAP perf open items
    (``queued_*_tasks_s``, ``many_actors_per_s``): the recorder stays ON,
    and after each leg the cluster-wide ring is drained into a per-verb
    time-attribution table — the measured breakdown the next perf
    tentpoles (batched lease-grant, batch create_actor) design against.

    ``phases=True`` (``bench.py --phases``) additionally joins the task
    phase spans to the task events and records the per-function phase
    table (p50/p99 per submit/queue/exec/... phase) under ``task_phases``
    in the bench JSON — the perf trajectory carries attribution, not just
    totals.

    Writes ``flight_attrib.json`` next to the bench JSON and prints the
    tables to stderr."""
    import sys

    from ray_tpu._private import flight, taskpath
    from ray_tpu._private.perf import bench_many_actors, bench_queued_tasks
    from ray_tpu._private.worker import get_global_worker

    flight.enable()
    w = get_global_worker()

    def drain():
        h, _ = w.run_sync(w._head_call("flight_snapshot", {}), 60)
        snaps = h["snapshots"]
        return flight.merge_snapshots(snaps), snaps

    def transit_stats():
        """Cluster transit-pacing snapshot: the DRIVER contributes the
        per-peer push windows + its settle stats; node processes are
        probed for the executor-side pump drain histogram (deduped by
        node id — a handful of spread probes covers small clusters).
        BENCH_r09's attribution needs these three series: peak/steady
        push-window per peer, pump messages-per-drain, and frames
        settled per driver recv wakeup. Round 20 adds the driver-loop
        scale-out ledgers: settle_plane / pack_plane snapshots and the
        per-shard pusher table (chunks/tasks per rt-pusher loop) ride
        the driver snapshot; pusher_shard_count is surfaced even when
        the auto knob resolves to 0 shards (small hosts), so an A/B
        over RT_PUSHER_LOOP_SHARDS reads from the bench JSON alone."""
        import ray_tpu

        stats = {"driver": w.transit_stats()}
        stats["driver"]["pusher_shard_count"] = len(w._pusher_loops)

        @ray_tpu.remote
        def _probe(_i):
            from ray_tpu._private.worker import get_global_worker

            gw = get_global_worker()
            return (
                gw.node_id,
                gw.transit_stats(),
                {k: v for k, v in gw._stats.items()
                 if k.startswith("pump_")},
            )

        nodes = {}
        try:
            for nid, ts, ps in ray_tpu.get(
                [_probe.remote(i) for i in range(8)], timeout=60
            ):
                ts["pump_exec"] = ps
                nodes[nid] = ts
        except Exception as e:
            stats["probe_error"] = f"{type(e).__name__}: {e}"
        stats["nodes"] = nodes
        return stats

    out = {"flight": True}
    attrib_all = {}
    legs = (
        ("many_actors_per_s",
         lambda: bench_many_actors(200 if quick else 1000)),
        ("queued_5k_tasks_s" if quick else "queued_1m_tasks_s",
         lambda: bench_queued_tasks(5_000 if quick else 1_000_000)),
    )
    for key, fn in legs:
        drain()  # discard events from the previous leg / warmup
        print(f"[bench --flight] {key}...", file=sys.stderr, flush=True)
        try:
            out[key] = fn()
        except Exception as e:
            out[key + "_error"] = f"{type(e).__name__}: {e}"
            continue
        merged, snaps = drain()
        dropped = sum(int(s.get("dropped") or 0) for s in snaps)
        recorded = sum(int(s.get("recorded") or 0) for s in snaps)
        attrib = flight.attribution(merged)
        transit = transit_stats()
        out.setdefault("transit", {})[key] = transit
        attrib_all[key] = {
            "verbs": attrib,
            "events_recorded": recorded,
            "events_dropped": dropped,
            "transit": transit,
        }
        print(f"--- per-verb attribution: {key} "
              f"({len(merged)} spans) ---", file=sys.stderr)
        if dropped:
            # No silent caps: a 1M-task leg overflows the per-process
            # rings, so the table attributes the TAIL window, not the
            # whole run.
            print(f"NOTE: rings kept the last {len(merged)} of "
                  f"{recorded} events ({dropped} overwritten) — totals "
                  f"are tail-window attribution, not the whole leg "
                  f"(raise RT_FLIGHT_RING_SIZE for full coverage)",
                  file=sys.stderr)
        print(flight.format_attribution(attrib), file=sys.stderr,
              flush=True)
        if phases:
            from ray_tpu.util import state

            # The leg's tail events ride the workers' 0.25s flusher tick:
            # wait for the head's event count to settle before joining
            # names, or the table degrades to the "task" bucket.
            events = state.list_tasks(limit=100_000)
            settle_deadline = time.time() + 3.0
            while time.time() < settle_deadline:
                time.sleep(0.35)
                nxt = state.list_tasks(limit=100_000)
                if len(nxt) == len(events):
                    events = nxt
                    break
                events = nxt
            table = taskpath.phase_table(merged, events)
            out.setdefault("task_phases", {})[key] = table
            attrib_all[key]["task_phases"] = table
            print(f"--- per-function task phases: {key} ---",
                  file=sys.stderr)
            print(taskpath.format_phase_table(table), file=sys.stderr,
                  flush=True)
    path = attrib_path or _attrib_path()
    with open(path, "w") as f:
        json.dump(attrib_all, f, indent=1)
    out["flight_attrib_file"] = path
    return out


def _attrib_path(output_dir: str = None) -> str:
    """Where attribution scratch output lands: --output-dir when given,
    else next to bench.py (gitignored — scratch files must never end up
    committed at the repo root again)."""
    d = output_dir or os.path.dirname(os.path.abspath(__file__))
    os.makedirs(d, exist_ok=True)
    return os.path.join(d, "flight_attrib.json")


def record_peak_object_store(core: dict):
    """Record the cluster's peak object-store watermark into the bench
    JSON (the arena's high-water mark per node, summed): the put/get
    traffic a bench leg actually cost in store memory, alongside its
    throughput numbers. Soft dependency — a summary failure annotates
    instead of failing the run."""
    try:
        from ray_tpu.util import state

        summary = state.memory_summary()
        core["peak_object_store_bytes"] = int(
            summary["totals"]["arena_peak_bytes"]
        )
        core["object_store_leak_candidates"] = int(
            summary["totals"]["leak_candidates"]
        )
    except Exception as e:
        core["peak_object_store_bytes_error"] = f"{type(e).__name__}: {e}"


def run_serve_benchmarks(quick: bool = False) -> dict:
    """Closed-loop + spiky open-loop serve bench over the HTTP ingress
    (ISSUE 6 / ROADMAP "Serving plane under production traffic"):

    - ``serve_qps`` + ``serve_p50_ms``/``serve_p99_ms``: closed-loop
      (W workers, sequential requests) steady-state throughput/latency
      through proxy -> router -> replica and back;
    - ``serve_spike_p99_ms`` + ``serve_spike_shed``: spiky open-loop
      bursts (K concurrent requests at once, idle between bursts) — the
      proxy's admission control may shed with typed 503s, which are
      counted, not failed;
    - ``serve_drain_dropped``: scale 4 -> 1 mid-load; graceful drain
      must complete every in-flight request (the acceptance gate: 0).

    When the flight recorder is enabled (``bench.py --serve --flight``)
    the per-verb attribution table for the serve legs lands in
    flight_attrib.json alongside the RPC-plane legs.
    """
    import http.client
    import statistics
    import sys
    import threading

    from ray_tpu import serve

    @serve.deployment(num_replicas=2, max_ongoing_requests=32)
    class Echo:
        def __call__(self, req):
            return {"ok": True}

    serve.run(Echo.bind(), name="bench_app", route_prefix="/bench")
    port = serve.start_http_proxy(port=0)

    def one_request(lat, errs, sheds, timeout=30):
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
        t0 = time.perf_counter()
        try:
            conn.request("GET", "/bench")
            status = conn.getresponse().status
            if status == 200:
                lat.append(time.perf_counter() - t0)
            elif status == 503:
                sheds.append(status)  # typed shed: by design under spikes
            else:
                errs.append(status)
        except Exception as e:
            errs.append(f"{type(e).__name__}")
        finally:
            conn.close()

    def pcts(lat):
        if len(lat) < 2:
            return (lat[0] * 1e3, lat[0] * 1e3) if lat else (None, None)
        qs = statistics.quantiles(lat, n=100, method="inclusive")
        return qs[49] * 1e3, qs[98] * 1e3

    out = {}
    # ---- leg 1: closed loop ------------------------------------------
    print("[bench --serve] closed-loop...", file=sys.stderr, flush=True)
    workers, duration = (4, 3.0) if quick else (8, 10.0)
    lat, errs, sheds = [], [], []
    stop_at = time.perf_counter() + duration

    def closed_loop():
        while time.perf_counter() < stop_at:
            one_request(lat, errs, sheds)

    threads = [threading.Thread(target=closed_loop) for _ in range(workers)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    dt = time.perf_counter() - t0
    p50, p99 = pcts(lat)
    out.update({
        "serve_qps": len(lat) / dt,
        "serve_p50_ms": p50,
        "serve_p99_ms": p99,
        "serve_errors": len(errs),
    })
    # ---- leg 2: spiky open-loop bursts -------------------------------
    print("[bench --serve] spiky bursts...", file=sys.stderr, flush=True)
    bursts, burst_size = (3, 16) if quick else (6, 48)
    lat, errs, sheds = [], [], []
    for _ in range(bursts):
        burst = [
            threading.Thread(target=one_request, args=(lat, errs, sheds))
            for _ in range(burst_size)
        ]
        for t in burst:
            t.start()
        for t in burst:
            t.join()
        time.sleep(0.3)  # open-loop idle gap between spikes
    p50, p99 = pcts(lat)
    out.update({
        "serve_spike_p50_ms": p50,
        "serve_spike_p99_ms": p99,
        "serve_spike_shed": len(sheds),
        "serve_spike_errors": len(errs),
    })
    # ---- leg 3: graceful drain under load ----------------------------
    print("[bench --serve] graceful drain 4->1...", file=sys.stderr,
          flush=True)
    serve.run(Echo.options(num_replicas=4).bind(), name="bench_app",
              route_prefix="/bench")
    lat, errs, sheds = [], [], []
    n_drain = 24 if quick else 80
    drain_threads = [
        threading.Thread(target=one_request, args=(lat, errs, sheds))
        for _ in range(n_drain)
    ]
    for t in drain_threads[: n_drain // 2]:
        t.start()
    serve.run(Echo.options(num_replicas=1).bind(), name="bench_app",
              route_prefix="/bench")  # scale down with the burst in flight
    for t in drain_threads[n_drain // 2:]:
        t.start()
    for t in drain_threads:
        t.join()
    out.update({
        "serve_drain_total": n_drain,
        "serve_drain_dropped": len(errs) + len(sheds),
    })
    serve.shutdown()
    return out


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--quick", action="store_true")
    parser.add_argument("--no-train", action="store_true")
    parser.add_argument("--train-only", action="store_true",
                        help="skip the core cluster benchmarks (debugging)")
    parser.add_argument(
        "--flight", action="store_true",
        help="flight-instrumented run of queued_tasks + many_actors only: "
             "recording ON cluster-wide, per-verb time-attribution table "
             "emitted next to the bench JSON (flight_attrib.json)")
    parser.add_argument(
        "--phases", action="store_true",
        help="implies --flight; after each leg, join the task phase spans "
             "to the task events and record the per-function phase table "
             "(submit/queue/exec/result p50+p99) into the bench JSON under "
             "task_phases — the perf trajectory carries attribution")
    parser.add_argument(
        "--output-dir", default=None, dest="output_dir",
        help="directory for attribution scratch files "
             "(flight_attrib.json); default: next to bench.py — those "
             "paths are gitignored scratch, never committed")
    parser.add_argument(
        "--serve", action="store_true",
        help="closed-loop serve bench only: serve_qps + p50/p99 through "
             "the HTTP ingress, spiky open-loop bursts (admission-control "
             "sheds counted), and a graceful-drain leg (scale 4->1 under "
             "load; dropped must be 0). Combine with --flight for per-verb "
             "attribution of the serving path")
    args = parser.parse_args()

    import os

    # Sentinel, not 0.0: a --train-only line must never read as a real
    # throughput collapse to anything parsing the headline contract.
    core = {"single_client_tasks_async_per_s": None, "core_skipped": True}
    if args.phases:
        args.flight = True
    if args.flight:
        # Recording must be on in every process: workers inherit the env.
        os.environ["RT_FLIGHT_ENABLED"] = "1"
        args.no_train = True  # flight mode measures the RPC plane only
    if args.serve:
        args.no_train = True  # serve mode measures the serving path only
    if not args.train_only:
        import ray_tpu
        from ray_tpu._private.perf import run_core_benchmarks

        # Scale worker processes to the machine: task execution is
        # GIL-bound per process, so on many-core hosts (TPU VMs have ~100
        # vCPUs) throughput comes from multiple node processes. On tiny CI
        # hosts stay small.
        cores = os.cpu_count() or 1
        if args.serve:
            # Serve bench: replicas/proxy/controller are IO-light actors
            # sharing node processes — schedule on virtual CPU slots (the
            # closed loop saturates the proxy event loop, not the cores).
            ray_tpu.init(num_cpus=16, num_nodes=1)
        elif cores >= 8:
            ray_tpu.init(num_cpus=4, num_nodes=min(cores // 4, 8))
        else:
            ray_tpu.init(num_cpus=max(cores, 2), num_nodes=1)
        try:
            if args.serve:
                core = {
                    "single_client_tasks_async_per_s": None,
                    "serve_bench": True,
                    **run_serve_benchmarks(quick=args.quick),
                }
                if args.flight:
                    import sys

                    from ray_tpu._private import flight
                    from ray_tpu._private.worker import get_global_worker

                    w = get_global_worker()
                    h, _ = w.run_sync(
                        w._head_call("flight_snapshot", {}), 60
                    )
                    merged = flight.merge_snapshots(h["snapshots"])
                    attrib = flight.attribution(merged)
                    print("--- per-verb attribution: serve bench ---",
                          file=sys.stderr)
                    print(flight.format_attribution(attrib),
                          file=sys.stderr, flush=True)
                    path = _attrib_path(args.output_dir)
                    # merge: the core legs' attribution (plain --flight
                    # runs) and the serve leg share the file
                    try:
                        with open(path) as f:
                            existing = json.load(f)
                    except (OSError, json.JSONDecodeError):
                        existing = {}
                    existing["serve_bench"] = {"verbs": attrib}
                    with open(path, "w") as f:
                        json.dump(existing, f, indent=1)
                    core["flight_attrib_file"] = path
            elif args.flight:
                core = {
                    "single_client_tasks_async_per_s": None,
                    **run_flight_benchmarks(
                        quick=args.quick, phases=args.phases,
                        attrib_path=_attrib_path(args.output_dir),
                    ),
                }
            else:
                core = run_core_benchmarks(quick=args.quick)
            # Peak store watermark rides every bench JSON: throughput
            # numbers carry their object-plane memory cost.
            record_peak_object_store(core)
        finally:
            ray_tpu.shutdown()

    extra = {}
    if not args.no_train:
        # A train leg that raises (no chip, a refused compile, an OOM) ends
        # the run with a traceback and a non-zero exit code; what the core
        # legs measured is on stderr by then, stdout keeps its ONE line.
        if not args.train_only:
            import sys

            print("[bench] core legs:", json.dumps(core), file=sys.stderr,
                  flush=True)
        extra = bench_train_tokens_per_sec(quick=args.quick)

    value = core["single_client_tasks_async_per_s"]
    result = {
        "metric": "single_client_tasks_async",
        "value": round(value, 1) if value is not None else None,
        "unit": "tasks/s",
        "vs_baseline": (
            round(value / BASELINE_TASKS_ASYNC, 3)
            if value is not None else None
        ),
        **{
            k: (round(v, 2) if isinstance(v, float) else v)
            for k, v in core.items()
        },
        **{
            k: (round(v, 2) if isinstance(v, float) else v)
            for k, v in extra.items()
        },
    }
    print(json.dumps(result))


if __name__ == "__main__":
    main()
