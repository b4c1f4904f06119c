"""A request's way through the proxy, the handle and the replica's pulls, read
from the profiler trace the device metrics come from (PR 53).

``serve/http_proxy.py``, ``serve/handle.py`` and ``serve/replica.py`` leave
``serve.proxy.request``, ``serve.route``, ``serve.replica.call`` and
``serve.replica.pull`` on the host plane of the replica's capture: under one
node the proxy, the handle and the replica are threads of the process that
holds the chip. ``host_spans.PROGRAM_SPAN`` keeps ``engine.``, ``llm.`` and
``train.`` and no ``serve.``, so these have a parser of their own; it also
keeps ``llm.request``, ``llm.done`` and ``engine.finish`` for the join. One
request's spans share ``req``, the id the proxy minted, up to ``llm.done``,
which ties it to the engine's ``rid``.

Every ``serve.*`` span is short and sits at the END of what it describes:
its arguments are the ledger, each part taken with ``time.monotonic()`` where
the work happens, in milliseconds. A trace of a program without them (the
commits before PR 53) gives none, and every reader built on this returns
``None``.

``python -m benchmarks.lib.serve_spans <trace dir or file>`` prints one row a
request the capture holds a ``serve.proxy.request`` of: the total at the HTTP
handler, the engine's, and each part between.
"""
from __future__ import annotations

import functools
import os
import statistics
import sys
from typing import Callable, Dict, List, Optional

from benchmarks.lib import host_spans
from benchmarks.lib import trace as T
from benchmarks.lib.host_spans import Span

SERVE_SPAN = "serve."
JOINED = ("llm.request", "llm.done", "engine.finish")
# the awaits of the handler, which its ledger names; what ``total_ms`` has
# beyond their sum is the handler's own lines between them
PARTS = ("route_ms", "read_ms", "submit_ms", "register_ms", "pull_wait_ms",
         "write_ms")


@functools.lru_cache(maxsize=2)
def _parse(path: str, mtime_ns: int) -> Dict[str, List[Span]]:
    from jax.profiler import ProfileData

    by_name: Dict[str, List[Span]] = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name != host_spans.HOST_PLANE:
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(SERVE_SPAN) or ev.name in JOINED:
                    by_name.setdefault(ev.name, []).append(Span(
                        ev.name, int(ev.start_ns), int(ev.duration_ns),
                        dict(ev.stats)))
    for spans in by_name.values():
        spans.sort(key=lambda s: s.start_ns)
    return by_name


def load(path: Optional[str] = None) -> Optional[Dict[str, List[Span]]]:
    """The spans of the newest trace under ``path`` (default: this run's,
    where ``host_spans`` looks) by name, each list by start; None where
    there is no trace or it holds none of them."""
    try:
        found = T.find_xplane(path or host_spans.TRACE_ROOT)
        by_name = _parse(found, os.stat(found).st_mtime_ns)
    except (OSError, ValueError):
        return None
    return by_name or None


def mean(name: str, value: Callable[[dict], Optional[float]]
         ) -> Optional[float]:
    """Mean of ``value(arguments)`` over the spans called ``name`` in this
    run's trace, those for which it gives None left out; None where nothing
    is left."""
    by_name = load()
    if by_name is None:
        return None
    got = [v for v in (value(s.args) for s in by_name.get(name, []))
           if v is not None]
    return statistics.fmean(got) if got else None


def rows(path: Optional[str] = None) -> List[dict]:
    """One row a ``serve.proxy.request`` of the capture, by its end: the
    handler's ledger and, joined by ``req`` (then ``rid`` for the engine's),
    what the replica and the deployment say of the same request. Beside the
    spans' own arguments a row holds ``tokens`` (of ``llm.done``),
    ``engine_total_ms`` and ``engine_produced`` (of ``engine.finish``), the
    replica's side of the stream's pulls summed (``replica_turn_ms``,
    ``replica_pool_wait_ms``, ``replica_wait_ms``) and three differences:
    ``other_ms``, the handler's total less its awaits (``PARTS``);
    ``outside_engine_ms``, the handler's total less the engine's; and
    ``way_back_ms``, that less the way to the engine (``since_received_ms``
    of ``llm.request``), ``after_finish_ms`` and ``after_last_pull_ms``: the
    last piece's way from the replica to the handler. A key is left out
    where the capture lacks the span it comes from: all of a request's
    spans but ``llm.request`` sit at its end, so one that ends inside the
    capture has them."""
    by_name = load(path)
    if by_name is None:
        return []

    def first_by(name: str, key: str) -> Dict[str, dict]:
        got: Dict[str, dict] = {}
        for s in by_name.get(name, []):
            got.setdefault(s.args.get(key), s.args)
        return got

    requests, dones = first_by("llm.request", "req"), first_by(
        "llm.done", "req")
    finishes = first_by("engine.finish", "rid")
    out = []
    for span in by_name.get("serve.proxy.request", []):
        row = dict(span.args)
        req = row["req"]
        row["other_ms"] = row["total_ms"] - sum(row[k] for k in PARTS)
        pulls = [s.args for s in by_name.get("serve.replica.pull", [])
                 if s.args.get("req") == req]
        for key in ("turn_ms", "pool_wait_ms", "wait_ms") if pulls else ():
            row["replica_" + key] = sum(a[key] for a in pulls)
        for key in ("since_received_ms", "lock_wait_ms"):
            if key in requests.get(req, {}):
                row[key] = requests[req][key]
        done = dones.get(req)
        finish = finishes.get(done["rid"]) if done else None
        if done:
            row.update(rid=done["rid"], tokens=done["tokens"],
                       after_finish_ms=done["after_finish_ms"])
        if finish:
            row.update(engine_total_ms=finish["total_ms"],
                       engine_produced=finish["produced"],
                       outside_engine_ms=row["total_ms"] - finish["total_ms"])
            if "since_received_ms" in row:
                row["way_back_ms"] = (
                    row["outside_engine_ms"] - row["since_received_ms"]
                    - row["after_finish_ms"] - row["after_last_pull_ms"])
        out.append(row)
    return out


def whole(path: Optional[str] = None) -> List[dict]:
    """The rows whose request the capture holds from the HTTP handler to the
    engine: ``serve.proxy.request``, ``llm.done`` and ``engine.finish``."""
    return [r for r in rows(path) if "engine_total_ms" in r]


COLUMNS = (
    "req", "status", "stream", "tokens", "total_ms", "engine_total_ms",
    "outside_engine_ms", "route_ms", "route_fetched", "read_ms", "submit_ms",
    "register_ms", "pulls", "pull_wait_ms", "write_ms", "other_ms",
    "since_received_ms", "lock_wait_ms", "after_finish_ms", "way_back_ms",
    "after_last_pull_ms", "replica_turn_ms", "replica_pool_wait_ms",
    "replica_wait_ms", "bytes")


def table(path: str) -> str:
    """``rows`` as text, one line a request under a line of names, and under
    them the medians a token over the requests held whole: the handler's
    (``proxy.request_ms_per_token``) beside the engine's over the SAME
    requests (``engine.request_ms_per_token`` is over every
    ``engine.finish`` of the capture)."""
    def cell(value) -> str:
        if value is None:
            return "-"
        return f"{value:.3f}" if isinstance(value, float) else str(value)

    lines = [COLUMNS] + [
        tuple(cell(row.get(c)) for c in COLUMNS) for row in rows(path)]
    widths = [max(len(line[i]) for line in lines)
              for i in range(len(COLUMNS))]
    text = ["  ".join(c.rjust(w) for c, w in zip(line, widths))
            for line in lines]
    held = [r for r in whole(path) if r["tokens"]]
    if held:
        text.append(
            f"median ms/token over {len(held)} requests: handler "
            f"{statistics.median(r['total_ms'] / r['tokens'] for r in held):.3f}"
            f", engine "
            f"{statistics.median(r['engine_total_ms'] / r['engine_produced'] for r in held):.3f}")
    return "\n".join(text)


if __name__ == "__main__":
    print(table(sys.argv[1]))
