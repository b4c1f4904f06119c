"""Round benchmark: prints ONE JSON line with the headline metric.

Headline = single_client_tasks_async vs the reference's checked-in number
(BASELINE.md: 7,096.8 tasks/s on a release CPU node). Extra fields carry the
other core microbenchmarks. These legs run on the host; what the accelerator
does (train and serve throughput, by cell) is ``benchmarks/run.py``'s.

Usage: python bench.py [--quick] [--flight | --phases | --serve]
"""
from __future__ import annotations

import argparse
import json
import os
import time

BASELINE_TASKS_ASYNC = 7096.8  # reference release/perf_metrics/microbenchmark.json

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

    if args.phases:
        args.flight = True
    if args.flight:
        # Recording must be on in every process: workers inherit the env.
        os.environ["RT_FLIGHT_ENABLED"] = "1"
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
    }
    print(json.dumps(result))


if __name__ == "__main__":
    main()
