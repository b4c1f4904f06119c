"""OpenAI-compatible serving app over the serve layer.

Reference analog: ``ray.serve.llm build_openai_app`` / ``LLMServer``
(``python/ray/llm/_internal/serve/``): an ingress deployment exposing
/v1/completions and /v1/chat/completions, backed by engine replicas. Here
the engine is the in-framework JAX decode engine; TP passthrough maps to
engine mesh config rather than vLLM kwargs.
"""
from __future__ import annotations

import contextlib
import json
import time
import uuid
from typing import Any, Dict, List, Optional

from ray_tpu.llm.config import LLMConfig
from ray_tpu.llm.engine import DecodeEngine, SamplingParams
from ray_tpu.serve.replica import request_origin


def extract_sampling(payload: dict, config: LLMConfig) -> SamplingParams:
    """OpenAI request fields → SamplingParams (shared by every ingress)."""
    stop = payload.get("stop") or ()
    if isinstance(stop, str):
        stop = (stop,)
    return SamplingParams(
        max_new_tokens=int(
            payload.get("max_tokens", config.max_new_tokens_default)
        ),
        temperature=float(payload.get("temperature", 0.0)),
        top_k=int(payload.get("top_k", 0)),
        top_p=float(payload.get("top_p", 1.0)),
        min_p=float(payload.get("min_p", 0.0)),
        repetition_penalty=float(payload.get("repetition_penalty", 1.0)),
        presence_penalty=float(payload.get("presence_penalty", 0.0)),
        frequency_penalty=float(payload.get("frequency_penalty", 0.0)),
        logprobs=int(payload.get("logprobs") or 0),
        seed=(int(payload["seed"]) if payload.get("seed") is not None
              else None),
        stop=tuple(stop),
        ignore_eos=bool(payload.get("ignore_eos", False)),
    )


def _logprobs_block(completion_ids) -> dict:
    """OpenAI-style logprobs payload from a GenerationResult (token ids
    stand in for token strings — the engine's ids ARE its vocabulary)."""
    entries = getattr(completion_ids, "logprobs", None) or []
    return {
        "tokens": [e["token"] for e in entries],
        "token_logprobs": [e["logprob"] for e in entries],
        "top_logprobs": [
            {str(t): lp for t, lp in e["top_logprobs"]} for e in entries
        ],
    }


def finish_reason(engine_reason: str) -> str:
    """OpenAI's two words for the engine's four: ``length`` where
    ``max_tokens`` or the context ended the answer, ``stop`` where EOS or a
    stop did."""
    return "length" if engine_reason in ("length", "context") else "stop"


def new_request_id(chat: bool = False) -> str:
    """The id an answer carries and the spans of its request share."""
    return f"{'chatcmpl' if chat else 'cmpl'}-{uuid.uuid4().hex[:24]}"


def completion_response(config: LLMConfig, prompt_tokens: int,
                        completion_ids, text: str, *,
                        rid: Optional[str] = None, **extra) -> dict:
    """OpenAI text_completion envelope (shared by every ingress)."""
    choice = {"index": 0, "text": text, "finish_reason": finish_reason(
        getattr(completion_ids, "finish_reason", ""))}
    if getattr(completion_ids, "logprobs", None):
        choice["logprobs"] = _logprobs_block(completion_ids)
    return {
        "id": rid or new_request_id(),
        "object": "text_completion",
        "created": int(time.time()),
        "model": config.model_id,
        "choices": [choice],
        "usage": {
            "prompt_tokens": prompt_tokens,
            "completion_tokens": len(completion_ids),
            "total_tokens": prompt_tokens + len(completion_ids),
        },
        **extra,
    }


@contextlib.contextmanager
def _done(rid: str, stream: bool, tokens: int, finished_at: float):
    """The ``llm.done`` span of an answer the serving thread has whole
    (``finished_at``: ``time.monotonic()`` of its ``engine.finish``), around
    the making of its last piece, which the caller counts into the span's
    ``chunks``. ``after_finish_ms``: from the finish to that piece being
    ready to leave the replica. ``req`` is the ingress's id for the request
    ("" where it came through none): it ties ``rid``, the engine's and the
    answer's, to the ``serve.*`` spans."""
    from jax.profiler import TraceAnnotation

    with TraceAnnotation("llm.done", rid=rid, req=request_origin()[0],
                         stream=int(stream), tokens=tokens) as done:
        yield done
        done.set_metadata(after_finish_ms=round(
            (time.monotonic() - finished_at) * 1e3, 3))


class LLMServer:
    """Serve deployment target wrapping one engine replica."""

    def __init__(self, config_dict: dict, params=None):
        from ray_tpu._private.accelerators.tpu import local_device_info

        self.config = LLMConfig.from_dict(config_dict)
        # a replica granted a chip refuses to serve from another platform
        self._device_info = local_device_info()
        self.engine = DecodeEngine(self.config, params=params)

    # serve ingress entry: HTTP payloads from the proxy, or direct dicts
    # from DeploymentHandle calls.
    def __call__(self, request: dict) -> dict:
        if "body" in request:  # HTTP proxy envelope
            path = request.get("path", "")
            try:
                payload = json.loads(request["body"] or b"{}")
            except json.JSONDecodeError:
                return {"error": {"message": "invalid JSON body"}}
            if payload.get("stream") and not payload.get("stop"):
                # OpenAI stream=true -> generator of SSE lines (the serve
                # replica registers it; the HTTP proxy forwards as SSE).
                # String stops need the full output for trimming, so they
                # fall through to the non-streaming path.
                chat = path.endswith("/chat/completions") or (
                    "messages" in payload
                )
                return self.completions_stream(payload, chat=chat)
            if path.endswith("/chat/completions"):
                return self.chat_completions(payload)
            return self.completions(payload)
        if request.get("stream") and not request.get("stop"):
            return self.completions_stream(
                request, chat="messages" in request
            )
        if "messages" in request:
            return self.chat_completions(request)
        return self.completions(request)

    # ----------------------------------------------------------- endpoints

    def _sampling(self, payload: dict) -> SamplingParams:
        return extract_sampling(payload, self.config)

    def _submit(self, prompt: str, payload: dict, rid: str, stream: bool,
                called: float):
        """(prompt ids, the engine's future or token stream): tokenizing and
        the hand-over to the engine, under the request's ``llm.request``
        span on the serving thread. ``called`` is ``time.monotonic()`` of
        the call of the endpoint; ``since_call_ms`` of the span is from
        there to the hand-over: for a stream, whose body runs at the
        proxy's first pull, the reply to the proxy and that pull's way
        back. ``since_received_ms`` is to the same moment from the
        ingress's stamp (``serve/replica.py:request_origin``; with ``req``,
        only where the request came through one); ``lock_wait_ms`` is what
        the hand-over then waited for the engine's lock
        (``DecodeEngine._enqueue``)."""
        from jax.profiler import TraceAnnotation

        params = self._sampling(payload)
        req, received = request_origin()
        with TraceAnnotation(
            "llm.request", rid=rid, req=req,
            max_tokens=params.max_new_tokens, stream=int(stream),
        ) as request:
            ids = self.engine.tokenizer.encode(prompt)
            now = time.monotonic()
            request.set_metadata(
                prompt_tokens=len(ids),
                since_call_ms=round((now - called) * 1e3, 3))
            if received:
                request.set_metadata(
                    since_received_ms=round((now - received) * 1e3, 3))
            send = (self.engine.submit_stream if stream
                    else self.engine.submit)
            out = send(ids, params, rid=rid)
            request.set_metadata(
                lock_wait_ms=round(out.lock_wait_s * 1e3, 3))
            return ids, out

    def _answer(self, prompt: str, payload: dict, rid: str, called: float):
        """(prompt ids, the engine's answer, its text) of a unary request."""
        ids, fut = self._submit(prompt, payload, rid, False, called)
        out = fut.result(600)
        with _done(rid, False, len(out), out.finished_at) as done:
            text = self.engine.tokenizer.decode(out)
            done.set_metadata(chunks=1)
        return ids, out, text

    def completions(self, payload: dict) -> dict:
        called = time.monotonic()
        rid = new_request_id()
        ids, out, text = self._answer(
            payload.get("prompt", ""), payload, rid, called)
        return completion_response(self.config, len(ids), out, text, rid=rid)

    def chat_completions(self, payload: dict) -> dict:
        called = time.monotonic()
        rid = new_request_id(chat=True)
        ids, out, text = self._answer(
            self._chat_prompt(payload.get("messages", [])), payload, rid,
            called)
        choice = {
            "index": 0,
            "message": {"role": "assistant", "content": text},
            "finish_reason": finish_reason(out.finish_reason),
        }
        if getattr(out, "logprobs", None):
            choice["logprobs"] = _logprobs_block(out)
        return {
            "id": rid,
            "object": "chat.completion",
            "created": int(time.time()),
            "model": self.config.model_id,
            "choices": [choice],
            "usage": {
                "prompt_tokens": len(ids),
                "completion_tokens": len(out),
                "total_tokens": len(ids) + len(out),
            },
        }

    def _chat_prompt(self, messages) -> str:
        return "".join(
            f"<{m.get('role', 'user')}>{m.get('content', '')}\n"
            for m in messages
        ) + "<assistant>"

    def completions_stream(self, payload: dict, *, chat: bool = False):
        """Generator of OpenAI SSE chunk lines (stream=true). Deltas are
        detokenized incrementally; the final line is ``data: [DONE]``
        (reference: ray.llm / vLLM streaming responses)."""
        # a generator's body runs at its first ``next`` (the proxy's first
        # pull), so the call is stamped here, outside it
        return self._stream(payload, chat, time.monotonic())

    def _stream(self, payload: dict, chat: bool, called: float):
        if chat:
            prompt = self._chat_prompt(payload.get("messages", []))
        else:
            prompt = payload.get("prompt", "")
        rid = new_request_id(chat)
        _, tokens = self._submit(prompt, payload, rid, True, called)
        created = int(time.time())
        obj = "chat.completion.chunk" if chat else "text_completion"

        def line(choice: dict) -> str:
            return "data: " + json.dumps({
                "id": rid, "object": obj, "created": created,
                "model": self.config.model_id, "choices": [choice],
            }) + "\n\n"

        produced: List[int] = []
        prev_text = ""
        sent = 0
        for tok in tokens:
            produced.append(tok)
            text = self.engine.tokenizer.decode(produced)
            # Hold back trailing replacement chars: a partial multi-byte
            # sequence decodes to U+FFFD that the next byte will fix —
            # emitting it would bake the wrong char into the stream.
            emit = text.rstrip("\ufffd")
            delta, prev_text = emit[len(prev_text):], emit
            if not delta:
                continue  # partial multi-byte/merge: hold until decodable
            if chat:
                choice = {"index": 0, "delta": {"content": delta},
                          "finish_reason": None}
            else:
                choice = {"index": 0, "text": delta, "finish_reason": None}
            sent += 1
            yield line(choice)
        # The whole answer is here: its last lines are made under
        # ``llm.done`` and sent after it (no span stays open across a
        # ``yield``: the next pull may run on another thread).
        with _done(rid, True, len(produced), tokens.finished_at) as done:
            closing = []
            # flush anything held back (a genuinely invalid trailing byte
            # in the final output emits as U+FFFD here, matching
            # non-streaming)
            tail = self.engine.tokenizer.decode(produced)[len(prev_text):]
            if tail:
                closing.append(line(
                    {"index": 0, "delta": {"content": tail},
                     "finish_reason": None} if chat else
                    {"index": 0, "text": tail, "finish_reason": None}))
            ended = finish_reason(tokens.finish_reason)
            closing.append(line(
                {"index": 0, "delta": {}, "finish_reason": ended} if chat
                else {"index": 0, "text": "", "finish_reason": ended}))
            closing.append("data: [DONE]\n\n")
            done.set_metadata(chunks=sent + len(closing))
        yield from closing

    def health_check(self) -> bool:
        return True

    def replica_info(self) -> dict:
        """Where this replica computes (platform, device_kind,
        device_count) and its engine counters."""
        return {**self._device_info, "engine_stats": dict(self.engine.stats)}


def build_openai_app(config: LLMConfig, *, num_replicas: int = 1,
                     params=None):
    """Application for ``serve.run(...)`` exposing the OpenAI surface at
    /v1 (reference: ``ray.serve.llm.build_openai_app``)."""
    from ray_tpu import serve

    deployment = serve.deployment(
        num_replicas=num_replicas,
        max_ongoing_requests=config.max_batch_slots,
        **config.deployment_config,
    )(LLMServer)
    return deployment.bind(config.to_dict(), params)


def serve_llm(config: LLMConfig, *, name: str = "llm", params=None,
              route_prefix: str = "/v1"):
    """Deploy and return (handle, app_name)."""
    from ray_tpu import serve

    app = build_openai_app(config, params=params)
    handle = serve.run(app, name=name, route_prefix=route_prefix)
    return handle
