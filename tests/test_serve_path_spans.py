"""A request's way through the HTTP proxy, the handle and the replica's pulls
in the profiler's own trace: one ``req`` from ``serve.proxy.request`` to
``llm.done``, the two ledger sums, the route cache's lapse, a stream's pulls,
and the counters that sum the same quantities with no capture running.

One cluster of one node (the proxy, the replicas and the capture are threads
of its process), a toy generator deployment and the toy ``LLMServer`` of
``tests/test_hot_path_spans.py`` behind the real proxy, in-flight cap 1. The
scenario (three streamed and two unary requests, one sent after the route
cache has lapsed, a shed and two 404s) runs once with no capture and once
under one, started and stopped as remote tasks in the node process, Python
tracer off, read back with ``benchmarks/lib/serve_spans.py``; every test below
looks at that one recording. Nothing timed here is a device number.
"""
import glob
import http.client
import json
import os
import threading
import time

import pytest

import ray_tpu
from ray_tpu import serve
from ray_tpu.llm.serving import LLMServer

_MODEL = dict(
    vocab_size=300, max_seq_len=64, num_layers=2, num_heads=2, embed_dim=32,
    dtype="float32", max_batch_slots=2, prefill_buckets=(16, 32),
)
SPANS = ["serve.proxy.request", "serve.route", "serve.replica.call",
         "serve.replica.pull", "llm.request", "llm.done", "engine.finish"]
TTL_S = 1.0  # serve/http_proxy.py:_ROUTE_TTL_S
CHUNKS = 20  # of the toy stream: two pulls of at most sixteen


class ToyLLM(LLMServer):
    def __init__(self):
        from ray_tpu.llm import DecodeEngine, LLMConfig

        self.config = LLMConfig(**_MODEL)
        self.engine = DecodeEngine(self.config, seed=0)


class Chunks:
    """``stream`` true: ``n`` lines, a pause before each; else one answer."""

    def __call__(self, request):
        body = json.loads(request["body"])
        if body.get("stream"):
            return self._lines(body["n"], body["pause_s"])
        return {"n": body["n"]}

    def _lines(self, n, pause_s):
        for i in range(n):
            time.sleep(pause_s)
            yield f"line {i}\n"


def _start_trace(logdir):
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(logdir, profiler_options=options)
    return os.getpid()


def _stop_trace():
    import jax

    jax.profiler.stop_trace()
    return True


def _post(port, path, payload, headers=None):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=120)
    try:
        conn.request("POST", path, body=json.dumps(payload),
                     headers=headers or {})
        resp = conn.getresponse()
        return resp.status, resp.read()
    finally:
        conn.close()


def _llm(prompt, max_tokens, seed, **extra):
    return {"prompt": prompt, "max_tokens": max_tokens, "temperature": 1.0,
            "seed": seed, **extra}


def _scenario(port):
    """The requests, one after another (the in-flight cap is 1), but for the
    one that is to be shed: sent while the toy stream is under way. Returns
    each answer as (status, body) by a name."""
    out = {}
    out["s1"] = _post(port, "/v1/completions",
                      _llm("what is", 6, 14, stream=True),
                      {"X-Request-Id": "chosen-by-the-client"})
    slow = threading.Thread(target=lambda: out.update(s2=_post(
        port, "/chunks", {"stream": True, "n": CHUNKS, "pause_s": 0.02})))
    slow.start()
    time.sleep(0.15)
    out["shed"] = _post(port, "/chunks", {"n": 1})
    slow.join(120)
    out["u1"] = _post(port, "/v1/completions", _llm("hello there", 5, 12))
    out["nope1"] = _post(port, "/nope", {})
    time.sleep(TTL_S + 0.2)  # the route table lapses
    out["s3"] = _post(port, "/v1/completions",
                      _llm("abc", 4, 13, stream=True))
    out["u2"] = _post(port, "/chunks", {"n": 3})
    out["nope2"] = _post(port, "/nope", {})
    return out


# by the handlers' ends: the shed request leaves while the toy stream runs
ORDER = ["s1", "shed", "s2", "u1", "nope1", "s3", "u2", "nope2"]
SERVED = ["s1", "s2", "u1", "s3", "u2"]  # three streamed, two unary


def _what_was_said(answers):
    """What two runs may be compared by: ids and times differ, statuses and
    the answers' text may not."""
    def text(body):
        if body.startswith(b"data: "):  # an LLM stream's lines
            return [json.loads(l[6:])["choices"][0]["text"]
                    for l in body.decode().split("\n\n")
                    if l.startswith("data: {")]
        try:
            got = json.loads(body)
        except ValueError:
            return body.decode()
        return got["choices"][0]["text"] if "choices" in got else got

    return {k: (status, text(body) if status == 200 else None)
            for k, (status, body) in answers.items()}


@pytest.fixture(scope="module")
def recording(tmp_path_factory):
    from benchmarks.lib import serve_spans
    from ray_tpu.serve.controller import CONTROLLER_NAME

    root = str(tmp_path_factory.mktemp("serve_spans"))
    logdir = os.path.join(root, "trace")
    ray_tpu.init(num_cpus=8, num_nodes=1,
                 _system_config={"serve_max_inflight": 1})
    try:
        serve.run(serve.deployment(max_ongoing_requests=4)(ToyLLM).bind(),
                  name="llm", route_prefix="/v1")
        serve.run(serve.deployment(max_ongoing_requests=4)(Chunks).bind(),
                  name="chunks", route_prefix="/chunks")
        port = serve.start_http_proxy()
        proxy = ray_tpu.get_actor("__serve_proxy")
        controller = ray_tpu.get_actor(CONTROLLER_NAME)
        replicas = [
            r for name in ("ToyLLM", "Chunks")
            for r in ray_tpu.get(controller.get_handles.remote(name),
                                 timeout=30)]

        def stats():
            return (ray_tpu.get(proxy.stats.remote(), timeout=30),
                    ray_tpu.get([r.stats.remote() for r in replicas],
                                timeout=30))

        # the engine's programs compile here, outside both runs
        for payload in (_llm("what is", 6, 14), _llm("hello there", 5, 12),
                        _llm("abc", 4, 13)):
            assert _post(port, "/v1/completions", payload)[0] == 200
        time.sleep(TTL_S + 0.2)
        plain = _scenario(port)
        files_without_capture = glob.glob(
            os.path.join(root, "**", "*.xplane.pb"), recursive=True)
        time.sleep(TTL_S + 0.2)  # the traced run starts on a lapsed table
        before = stats()
        capture_pid = ray_tpu.get(
            ray_tpu.remote(_start_trace).remote(logdir), timeout=120)
        try:
            traced = _scenario(port)
            time.sleep(0.1)  # the last handler's span
        finally:
            ray_tpu.get(ray_tpu.remote(_stop_trace).remote(), timeout=120)
        after = stats()
    finally:
        try:
            serve.shutdown()
        finally:
            ray_tpu.shutdown()
    spans = serve_spans.load(logdir)
    assert spans is not None, "the capture holds no span of the serve path"
    proxy_spans = spans["serve.proxy.request"]
    assert [p.args["status"] for p in proxy_spans] == [
        traced[k][0] for k in ORDER] == [
        200, 503, 200, 200, 404, 200, 200, 404]

    def delta(a, b):
        return {k: b[k] - a[k] for k in b if k not in ("pid", "draining")}

    return {
        "spans": spans, "logdir": logdir, "plain": plain, "traced": traced,
        # the handler's span of each request of the scenario, by its name
        "proxy": dict(zip(ORDER, proxy_spans)),
        "proxy_stats": delta(before[0], after[0]),
        "replica_stats": [delta(a, b) for a, b in zip(before[1], after[1])],
        "pids": {"driver": os.getpid(), "capture": capture_pid,
                 "proxy": after[0]["pid"],
                 "replicas": [s["pid"] for s in after[1]]},
        "files_without_capture": files_without_capture,
    }


def _args_of(recording, name, req):
    return [s.args for s in recording["spans"].get(name, [])
            if s.args.get("req") == req]


def test_proxy_and_replicas_are_threads_of_the_one_node_process(recording):
    """Under ``num_nodes=1`` (``benchmarks/lib/cluster.py:start``) an actor
    lives in the node's worker process: the capture that a remote task
    starts there holds the proxy's and the replicas' threads."""
    pids = recording["pids"]
    assert pids["proxy"] == pids["capture"]
    assert pids["replicas"] == [pids["capture"]] * 2
    assert pids["driver"] != pids["capture"]


@pytest.mark.parametrize("name", SPANS)
def test_every_span_is_in_the_trace(recording, name):
    assert recording["spans"].get(name), sorted(recording["spans"])


@pytest.mark.parametrize("key", SERVED)
def test_one_req_a_request_from_the_handler_to_the_answer(recording, key):
    """``serve.proxy.request``, ``serve.route``, ``serve.replica.call``, a
    stream's ``serve.replica.pull`` and the deployment's ``llm.request`` and
    ``llm.done`` carry the id the handler minted, or the client's; ``llm.done``
    ties it to the ``rid`` the answer and the engine's spans carry."""
    proxy = recording["proxy"][key].args
    req = proxy["req"]
    assert (req == "chosen-by-the-client") == (key == "s1")
    # sixteen hex digits; of decimal digits alone (one id in 1,800) they
    # come back from the capture as a number
    assert len(str(req).zfill(16)) == 16 or key == "s1"
    reqs = [p.args["req"] for p in recording["proxy"].values()]
    assert reqs.count(req) == 1
    (route,) = _args_of(recording, "serve.route", req)
    (call,) = _args_of(recording, "serve.replica.call", req)
    assert route["deployment"] == (
        "Chunks" if key in ("s2", "u2") else "ToyLLM")
    assert (route["replicas"], route["inflight"]) == (1, 1)
    assert route["refreshed"] in (0, 1)
    assert (call["method"], call["stream"], call["ongoing"]) == (
        "__call__", proxy["stream"], 1)
    assert proxy["stream"] == int(key.startswith("s"))
    assert bool(_args_of(recording, "serve.replica.pull", req)) == bool(
        proxy["stream"])
    # from the handler's stamp: to the replica's first line, and on to the
    # end of its call, lie inside the handler's total
    assert 0 <= call["since_received_ms"]
    assert call["since_received_ms"] + call["call_ms"] <= (
        proxy["total_ms"] + 0.01)
    if key in ("s2", "u2"):
        assert not _args_of(recording, "llm.done", req)
        return
    (request,) = _args_of(recording, "llm.request", req)
    (done,) = _args_of(recording, "llm.done", req)
    assert request["rid"] == done["rid"]
    assert done["stream"] == request["stream"] == proxy["stream"]
    body = recording["traced"][key][1]
    answer = json.loads(
        body.decode().split("\n\n")[0][6:] if proxy["stream"] else body)
    assert answer["id"] == done["rid"]
    (finish,) = [s.args for s in recording["spans"]["engine.finish"]
                 if s.args["rid"] == done["rid"]]
    assert finish["produced"] >= done["tokens"] > 0
    assert call["since_received_ms"] <= request["since_received_ms"]
    # the hand-over's wait for the lock: the caller's, beside the engine's
    # work; what of it outlasts that work is ``after_finish_ms``'s
    assert 0 <= request["lock_wait_ms"] <= (
        finish["total_ms"] + done["after_finish_ms"] + 0.01)


def test_a_pick_says_whether_a_refresh_was_on_its_path(monkeypatch):
    """``refreshed`` of ``serve.route``: ``_Router.pick`` hands it out with
    the replica, from what ``_refresh`` says it did; a fresh replica set is
    no trip to the controller."""
    from ray_tpu.serve.handle import _Router

    class Replica:
        _actor_id = "a"

    router = _Router("d")
    router._fetched_at = time.monotonic()
    assert router._refresh() is False  # fresh: the controller is not asked

    def refresh(force=False):  # the controller's answer, once
        if router._replicas:
            return False
        router._replicas, router._inflight = [Replica()], {"a": 0}
        return True

    monkeypatch.setattr(router, "_refresh", refresh)
    assert router.pick()[2] == {"replicas": 1, "inflight": 1, "refreshed": 1}
    assert router.pick()[2] == {"replicas": 1, "inflight": 2, "refreshed": 0}


@pytest.mark.parametrize("key", ORDER)
def test_the_handlers_ledger_closes(recording, key):
    """``route + read + submit + register + pull_wait + write`` is the
    handler's every await: what ``total_ms`` has beyond them is its own
    lines between (the payload, the response object, the stream's
    teardown), on the loop's thread."""
    from benchmarks.lib import serve_spans

    a = recording["proxy"][key].args
    parts = sum(a[k] for k in serve_spans.PARTS)
    assert -0.01 <= a["total_ms"] - parts <= 100, a
    if key == "shed":
        assert parts == 0 and a["inflight"] == 1
        return
    assert a["inflight"] == 0 and a["route_ms"] > 0
    if a["status"] == 404:
        assert a["submit_ms"] == a["register_ms"] == a["bytes"] == 0
        return
    assert min(a["read_ms"], a["submit_ms"], a["register_ms"]) > 0
    assert a["bytes"] == len(recording["traced"][key][1])
    if a["stream"]:
        assert min(a["pull_wait_ms"], a["write_ms"]) > 0
        # the pull that said done came back before the last write
        assert 0 < a["after_last_pull_ms"] <= a["total_ms"]
    else:
        # a whole answer leaves after the handler returned
        assert (a["pulls"], a["pull_wait_ms"], a["write_ms"],
                a["after_last_pull_ms"]) == (0, 0, 0, 0)


@pytest.mark.parametrize("key", ["s1", "u1", "s3"])
def test_a_requests_way_closes_from_the_handler_to_the_engine_and_back(
        recording, key):
    """``serve.proxy.request.total_ms`` = the way to the engine
    (``since_received_ms`` of ``llm.request``) + ``engine.finish.total_ms`` +
    ``llm.done.after_finish_ms`` + the last piece's way back to the handler
    + ``after_last_pull_ms``. No argument measures the way back: the
    profiler's own clock does, from the end of ``llm.done`` to the handler's
    span less ``after_last_pull_ms``, and the two clocks agree."""
    from benchmarks.lib import serve_spans

    span = recording["proxy"][key]
    (row,) = [r for r in serve_spans.rows(recording["logdir"])
              if r["req"] == span.args["req"]]
    (done,) = [s for s in recording["spans"]["llm.done"]
               if s.args["req"] == row["req"]]
    assert row["way_back_ms"] == pytest.approx(
        row["total_ms"] - row["since_received_ms"] - row["engine_total_ms"]
        - row["after_finish_ms"] - row["after_last_pull_ms"], abs=1e-6)
    by_the_trace = ((span.start_ns - done.end_ns) / 1e6
                    - row["after_last_pull_ms"])
    assert row["way_back_ms"] == pytest.approx(by_the_trace, abs=20), row
    assert row["way_back_ms"] > -1
    assert row["outside_engine_ms"] > 0
    # a stream's whole answer waits in one pull (the byte tokenizer's deltas
    # are held to the end: ``chunks`` 2 of ``llm.done`` and ``[DONE]``)
    assert row["pulls"] == (1 if row["stream"] else 0)


def test_the_route_table_is_fetched_first_and_again_after_its_lapse(
        recording):
    """``route_fetched``: 1 on a lapsed table, then 0 for ``TTL_S`` from
    that FETCH, not from the last use; a path no route matches goes to the
    controller once more before its 404. A shed request never routes."""
    fetched = {k: p.args["route_fetched"]
               for k, p in recording["proxy"].items()}
    assert fetched["s1"] == fetched["s3"] == 1
    assert fetched["nope1"] == fetched["nope2"] == 1
    assert fetched["shed"] == 0
    # between: by the handlers' own times (a slow machine may take more
    # than the table's second over the first three requests)
    table_at = None
    for key in ORDER:
        p = recording["proxy"][key]
        if key == "shed":
            continue
        received = p.start_ns / 1e9 - p.args["total_ms"] / 1e3
        age = None if table_at is None else received - table_at
        if age is not None and abs(age - TTL_S) < 0.05 + (
                p.args["route_ms"] / 1e3):
            pytest.skip(f"{key} arrived as the table lapsed")
        want = int(age is None or age > TTL_S or p.args["status"] == 404)
        assert p.args["route_fetched"] == want, (key, age)
        if want:
            table_at = received + p.args["route_ms"] / 1e3
    assert (fetched["s2"], fetched["u1"]) == (0, 0) or (
        recording["proxy"]["u1"].start_ns
        - recording["proxy"]["s1"].start_ns) > 0.5e9


@pytest.mark.parametrize("key", ["s1", "s2", "s3"])
def test_a_streams_pulls_count_from_one_and_the_last_says_done(
        recording, key):
    proxy = recording["proxy"][key].args
    pulls = _args_of(recording, "serve.replica.pull", proxy["req"])
    assert [a["n"] for a in pulls] == list(range(1, len(pulls) + 1))
    assert [a["done"] for a in pulls] == [0] * (len(pulls) - 1) + [1]
    assert proxy["pulls"] == len(pulls)
    body = recording["traced"][key][1].decode()
    if key == "s2":
        assert [a["chunks"] for a in pulls] == [16, CHUNKS - 16]
        assert body == "".join(f"line {i}\n" for i in range(CHUNKS))
        # each line's pause is spent inside the generator
        assert all(a["wait_ms"] >= 20 * a["chunks"] for a in pulls)
    else:
        (done,) = _args_of(recording, "llm.done", proxy["req"])
        assert sum(a["chunks"] for a in pulls) == done["chunks"] == len(
            body.split("\n\n")) - 1
    for a in pulls:
        assert min(a["turn_ms"], a["pool_wait_ms"], a["wait_ms"]) >= 0
    # what the replica saw of the stream lies inside the handler's waits
    # for it: the registration's reply and every pull
    assert sum(a["turn_ms"] + a["pool_wait_ms"] + a["wait_ms"]
               for a in pulls) <= (
        proxy["register_ms"] + proxy["pull_wait_ms"] + proxy["write_ms"]
        + 5)  # the handler's own lines between its awaits


def test_the_proxys_counters_equal_the_sums_of_the_spans_arguments(
        recording):
    stats = recording["proxy_stats"]
    spans = [p.args for p in recording["spans"]["serve.proxy.request"]]
    assert stats["inflight"] == 0
    assert stats["requests"] == len(spans) == len(ORDER)
    assert stats["streams"] == sum(a["stream"] for a in spans) == 3
    assert stats["shed"] == 1
    assert stats["shed"] + stats["errors"] == sum(
        a["status"] >= 500 for a in spans)
    assert stats["errors"] == 0
    assert stats["route_fetches"] == sum(a["route_fetched"] for a in spans)
    assert stats["pulls"] == sum(a["pulls"] for a in spans) == len(
        recording["spans"]["serve.replica.pull"])
    assert stats["bytes"] == sum(a["bytes"] for a in spans) == sum(
        len(recording["traced"][k][1]) for k in SERVED)
    for part in ("route", "submit", "register", "pull_wait", "write",
                 "total"):
        assert stats[part + "_s"] == pytest.approx(
            sum(a[part + "_ms"] for a in spans) / 1e3,
            abs=1e-6 * len(spans)), part
    assert sorted(stats) == sorted([
        "inflight", "requests", "streams", "shed", "errors",
        "route_fetches", "route_s", "submit_s", "register_s", "pull_wait_s",
        "write_s", "total_s", "pulls", "bytes"])


def test_the_replicas_counters_equal_the_sums_of_the_spans_arguments(
        recording):
    pulls = [s.args for s in recording["spans"]["serve.replica.pull"]]
    stats = recording["replica_stats"]
    assert [s["total"] for s in stats] == [3, 2]  # calls: ToyLLM, Chunks
    assert len(recording["spans"]["serve.replica.call"]) == 5
    assert sum(s["pulls"] for s in stats) == len(pulls) == 4
    assert sum(s["pull_turn_s"] for s in stats) == pytest.approx(
        sum(a["turn_ms"] for a in pulls) / 1e3, abs=1e-5)
    assert sum(s["pool_wait_s"] for s in stats) == pytest.approx(
        sum(a["pool_wait_ms"] for a in pulls) / 1e3, abs=1e-5)
    assert all(s["ongoing"] == s["streams"] == 0 for s in stats)


@pytest.mark.parametrize("key,status", [
    ("shed", 503), ("nope1", 404), ("nope2", 404)])
def test_a_shed_and_a_404_each_leave_the_handlers_span(
        recording, key, status):
    a = recording["proxy"][key].args
    assert a["status"] == status == recording["traced"][key][0]
    assert len(str(a["req"]).zfill(16)) == 16 and a["total_ms"] > 0
    for name in ("serve.route", "serve.replica.call", "llm.request"):
        assert not _args_of(recording, name, a["req"])


def test_without_a_capture_the_same_answers_and_no_trace(recording):
    assert recording["files_without_capture"] == []
    assert _what_was_said(recording["plain"]) == _what_was_said(
        recording["traced"])
    said = _what_was_said(recording["traced"])
    assert said["u2"] == (200, {"n": 3}) and said["shed"] == (503, None)
    assert len(said["s1"][1]) >= 2  # the held-back text, the closing line


def _mean(values):
    values = list(values)
    return sum(values) / len(values)


def _whole(spans):
    """(handler's, ``llm.done``'s, ``engine.finish``'s arguments) of the
    three requests that reached the engine."""
    dones = {s.args["req"]: s.args for s in spans["llm.done"]}
    finishes = {s.args["rid"]: s.args for s in spans["engine.finish"]}
    return [(p.args, dones[p.args["req"]],
             finishes[dones[p.args["req"]]["rid"]])
            for p in spans["serve.proxy.request"] if p.args["req"] in dones]


def _proxy(spans):
    return [p.args for p in spans["serve.proxy.request"]]


READERS = {
    "proxy.outside_engine_ms": lambda spans: _mean(
        p["total_ms"] - f["total_ms"] for p, _, f in _whole(spans)),
    "proxy.request_ms_per_token": lambda spans: sorted(
        p["total_ms"] / d["tokens"] for p, d, _ in _whole(spans))[1],
    "proxy.route_ms": lambda spans: _mean(
        a["route_ms"] for a in _proxy(spans)),
    "proxy.route_fetch_share": lambda spans: _mean(
        a["route_fetched"] for a in _proxy(spans)),
    "proxy.submit_ms": lambda spans: _mean(
        a["submit_ms"] + a["register_ms"] for a in _proxy(spans)
        if a["status"] == 200),
    "serve.pull_turn_ms": lambda spans: _mean(
        s.args["turn_ms"] for s in spans["serve.replica.pull"]),
    "proxy.egress_ms": lambda spans: _mean(
        a["after_last_pull_ms"] for a in _proxy(spans) if a["stream"]),
    "serve.submit_lock_ms": lambda spans: _mean(
        s.args["lock_wait_ms"] for s in spans["llm.request"]),
}


@pytest.mark.parametrize("metric", sorted(READERS))
def test_a_reader_returns_the_hand_sum_over_the_spans(
        recording, monkeypatch, metric):
    """The benchmark's eight readers of the serve path
    (``benchmarks/layer_metrics/<metric>.py``), over the CPU recording."""
    from benchmarks import run
    from benchmarks.lib import host_spans

    with open(os.path.join(run.CHECKOUT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    listed = {m["name"]: m for m in bench["per_layer"]}
    serving = next(m["workloads"] for m in bench["end_to_end"]
                   if m["name"] == "per_token_p50_ms")
    assert listed[metric] == {
        **listed["serve.deliver_ms"], "name": metric,
        "unit": listed[metric]["unit"], "workloads": serving}
    assert listed[metric]["better"] == "lower"
    assert len(_whole(recording["spans"])) == 3
    monkeypatch.setattr(host_spans, "TRACE_ROOT", recording["logdir"])
    got = run.read_layer_metric(metric, None, {})
    assert got == pytest.approx(READERS[metric](recording["spans"]), rel=1e-9)
    assert got > 0 or metric == "serve.submit_lock_ms"


@pytest.mark.parametrize("metric", sorted(READERS))
@pytest.mark.parametrize("trace", ["none", "v5e_1chip_spans.xplane.pb"])
def test_a_reader_finds_nothing_where_the_trace_lacks_the_spans(
        monkeypatch, tmp_path, metric, trace):
    """No trace at all, and a trace of a program from before the spans
    (PR 24's recording on the chip: ``llm.request`` with neither ``req`` nor
    ``lock_wait_ms``, ``engine.finish``, no ``serve.*``): None, and the line
    leaves the metric out."""
    from benchmarks import run
    from benchmarks.lib import host_spans

    path = os.path.join(run.BENCH_DIR, "tests", "data", trace)
    assert os.path.isfile(path) or trace == "none"
    monkeypatch.setattr(host_spans, "TRACE_ROOT",
                        path if trace != "none" else str(tmp_path))
    assert run.read_layer_metric(metric, None, {}) is None


def test_the_table_has_a_row_a_request(recording):
    from benchmarks.lib import serve_spans

    text = serve_spans.table(recording["logdir"]).split("\n")
    assert text[0].split() == list(serve_spans.COLUMNS)
    assert len(text) == 1 + len(ORDER) + 1
    assert [line.split()[1] for line in text[1:-1]] == [
        "200", "503", "200", "200", "404", "200", "200", "404"]
    assert text[-1].startswith("median ms/token over 3 requests: handler ")
