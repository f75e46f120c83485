"""OpenAI-compatible HTTP server sharing the TGIS gRPC server's engine.

Capability analog of the reference's in-process vLLM FastAPI app
(http.py:41-99): ``/v1/completions`` (unary + SSE streaming),
``/v1/models``, ``/health``, ``/metrics``, and the ``X-Correlation-ID``
middleware behavior (http.py:26-38).  FastAPI/uvicorn are not available in
this environment, so the app runs on a small asyncio + h11 HTTP/1.1 server
(h11 provides the protocol state machine; sockets and concurrency are
asyncio).
"""

from __future__ import annotations

import asyncio
import json
import socket
import ssl as ssl_module
import time
import uuid
from typing import TYPE_CHECKING, Any, AsyncIterator, Callable, Optional

import h11

from vllm_tgis_adapter_tpu import metrics
from vllm_tgis_adapter_tpu.engine.sampling_params import (
    RequestOutputKind,
    SamplingParams,
)
from vllm_tgis_adapter_tpu.frontdoor.errors import (
    AdmissionShedError,
    CapacityError,
    EngineRestartError,
)
from vllm_tgis_adapter_tpu.logging import init_logger
from vllm_tgis_adapter_tpu.tgis_utils import logs
from vllm_tgis_adapter_tpu.utils import close_server

if TYPE_CHECKING:
    import argparse

    from vllm_tgis_adapter_tpu.engine.async_llm import AsyncLLMEngine

logger = init_logger(__name__)

CORRELATION_ID_HEADER = "x-correlation-id"
# x-request-class rides along with the W3C pair: it is consumed by
# telemetry/slo.py class resolution at admission, not by tracing
_TRACE_HEADERS = ("traceparent", "tracestate", "x-request-class")


def _trace_headers(request: "HttpRequest") -> Optional[dict[str, str]]:
    """W3C trace-context headers to forward into the engine (same
    propagation the gRPC server does via its invocation metadata)."""
    headers = {
        k: request.headers[k] for k in _TRACE_HEADERS if k in request.headers
    }
    return headers or None


def _tenant_id(app: App, request: "HttpRequest") -> Optional[str]:
    """Front-door tenant key: the configured header (default
    ``x-tenant-id``), same keying as the gRPC surface."""
    return request.headers.get(app.state.get("tenant_header") or
                               "x-tenant-id")


def _shed_response(exc: BaseException) -> HttpResponse:
    """Admission-shed / capacity errors → deliberate HTTP statuses.

    Type-based mapping shared with the gRPC surface
    (frontdoor.errors.classify): sheds are 429 with ``Retry-After``,
    drain is 503, queue-TTL expiry is 408; returns a generic 500 for
    anything unclassified (callers only pass classified errors).
    """
    from vllm_tgis_adapter_tpu.frontdoor.errors import (
        classify,
        retry_after_seconds,
    )

    disposition = classify(exc)
    if disposition is None:
        return error_response(500, str(exc), "server_error")
    headers = {}
    if disposition.retry_after_s is not None:
        headers["retry-after"] = str(
            retry_after_seconds(disposition.retry_after_s)
        )
    return JsonResponse(
        {
            "error": {
                "message": str(exc),
                "type": disposition.err_type,
                "code": disposition.http_status,
            }
        },
        status=disposition.http_status,
        headers=headers,
    )


# --------------------------------------------------------------------- app


class HttpRequest:
    def __init__(
        self,
        method: str,
        path: str,
        headers: dict[str, str],
        body: bytes,
    ):
        self.method = method
        self.path = path
        self.headers = headers
        self.body = body
        # decoded tail of a prefix route (App.route_prefix), e.g. the
        # {id} of /debug/requests/{id}; set by App.dispatch
        self.path_param: Optional[str] = None

    def json(self) -> Any:
        return json.loads(self.body or b"{}")


class HttpResponse:
    def __init__(
        self,
        status: int = 200,
        body: bytes | str = b"",
        content_type: str = "application/json",
        headers: Optional[dict[str, str]] = None,
    ):
        self.status = status
        self.body = body.encode() if isinstance(body, str) else body
        self.content_type = content_type
        self.headers = headers or {}


class StreamingResponse:
    """Chunked response driven by an async byte-chunk generator."""

    def __init__(
        self,
        chunks: AsyncIterator[bytes],
        content_type: str = "text/event-stream",
        headers: Optional[dict[str, str]] = None,
    ):
        self.chunks = chunks
        self.content_type = content_type
        self.headers = headers or {}
        self.status = 200


class JsonResponse(HttpResponse):
    def __init__(self, obj: Any, status: int = 200, **kwargs):  # noqa: ANN003
        super().__init__(status=status, body=json.dumps(obj), **kwargs)


def error_response(status: int, message: str, err_type: str = "invalid_request_error"):
    return JsonResponse(
        {"error": {"message": message, "type": err_type, "code": status}},
        status=status,
    )


class App:
    """Method+path router with the shared-engine state, FastAPI-app analog."""

    def __init__(self, root_path: str | None = None) -> None:
        self.routes: dict[tuple[str, str], Callable] = {}
        # path-prefix routes ({prefix}{rest}, e.g. /debug/requests/{id});
        # the matched suffix is delivered as request.path_param
        self.prefix_routes: dict[tuple[str, str], Callable] = {}
        self.state: dict[str, Any] = {}
        # --root-path: prefix prepended by a reverse proxy; requests
        # arrive as {root_path}{route} and are matched with it stripped
        self.root_path = (root_path or "").rstrip("/")

    def route(self, method: str, path: str):  # noqa: ANN201
        def register(fn):  # noqa: ANN001, ANN202
            self.routes[(method, path)] = fn
            return fn

        return register

    def route_prefix(self, method: str, prefix: str):  # noqa: ANN201
        """Register ``{prefix}{rest}``; the handler reads the decoded
        ``rest`` from ``request.path_param``."""

        def register(fn):  # noqa: ANN001, ANN202
            self.prefix_routes[(method, prefix)] = fn
            return fn

        return register

    async def dispatch(self, request: HttpRequest):  # noqa: ANN201
        raw_path = request.path.split("?")[0]
        # Proxied requests arrive as {root_path}{route}; direct requests
        # arrive unprefixed.  Like FastAPI's root_path handling, match the
        # stripped form first but fall back to the raw path so a direct
        # request to a native route (e.g. --root-path /v1 + /v1/completions)
        # still resolves.
        candidates = [raw_path]
        if self.root_path and raw_path.startswith(self.root_path):
            candidates.insert(0, raw_path[len(self.root_path):] or "/")
        handler = None
        for path in candidates:
            handler = self.routes.get((request.method, path))
            if handler is not None:
                break
            for (method, prefix), fn in self.prefix_routes.items():
                if method == request.method and path.startswith(prefix):
                    from urllib.parse import unquote

                    request.path_param = unquote(path[len(prefix):])
                    handler = fn
                    break
            if handler is not None:
                break
        if handler is None:
            if any(p in candidates for (_, p) in self.routes):
                return error_response(405, "method not allowed")
            return error_response(404, "not found")
        return await handler(self, request)


# ------------------------------------------------------------- endpoints


def build_http_server(args: "argparse.Namespace", engine: "AsyncLLMEngine") -> App:
    """Assemble the app around the SHARED engine (reference: http.py:41-67)."""
    app = App(root_path=getattr(args, "root_path", None))
    app.state["engine"] = engine
    app.state["args"] = args
    served_names = args.served_model_name or [args.model]
    app.state["model_names"] = served_names
    app.state["api_key"] = args.api_key
    app.state["tenant_header"] = (
        getattr(args, "tenant_header", "x-tenant-id") or "x-tenant-id"
    ).lower()

    app.route("GET", "/health")(_health)
    app.route("GET", "/metrics")(_metrics)
    app.route("GET", "/version")(_version)
    app.route("GET", "/v1/models")(_models)
    app.route("POST", "/v1/load_lora_adapter")(_load_lora_adapter)
    app.route("POST", "/v1/unload_lora_adapter")(_unload_lora_adapter)
    app.route("POST", "/v1/completions")(_completions)
    app.route("POST", "/v1/chat/completions")(_chat_completions)
    # vLLM-app extras the reference exposes by mounting the full OpenAI
    # app (/root/reference/src/vllm_tgis_adapter/http.py:52)
    app.route("POST", "/tokenize")(_tokenize)
    app.route("POST", "/detokenize")(_detokenize)
    # on-demand jax.profiler capture, gated by --profile-dir (vLLM-app
    # analog: start_profile/stop_profile); shared with the gRPC debug
    # service so either front-end can bracket a capture
    from vllm_tgis_adapter_tpu.profiler import get_controller

    app.state["profiler"] = get_controller(
        getattr(args, "profile_dir", None)
    )
    app.route("POST", "/start_profile")(_start_profile)
    app.route("POST", "/stop_profile")(_stop_profile)
    # live engine-state introspection (flight_recorder.py): the same
    # snapshot/timeline serializer the stall watchdog dumps and the gRPC
    # Debug service serves, so all surfaces tell one story
    app.route("GET", "/debug/state")(_debug_state)
    app.route("GET", "/debug/doctor")(_debug_doctor)
    app.route("GET", "/debug/timeline")(_debug_timeline)
    app.route_prefix("GET", "/debug/requests/")(_debug_request)
    return app


async def _health(app: App, request: HttpRequest) -> HttpResponse:
    from vllm_tgis_adapter_tpu.supervisor.lifecycle import (
        LIFECYCLE_RECOVERING,
        engine_lifecycle,
    )

    engine: AsyncLLMEngine = app.state["engine"]
    frontdoor = getattr(engine, "frontdoor", None)
    if frontdoor is not None and frontdoor.draining:
        # drain (frontdoor/drain.py): healthy but refusing new work —
        # 503 pulls this pod out of load-balancer rotation while
        # in-flight generations finish
        return error_response(
            503, "server is draining", "service_unavailable"
        )
    if engine_lifecycle(engine) == LIFECYCLE_RECOVERING:
        # supervised restart in flight (supervisor/): 503 + Retry-After
        # through the SAME classify mapping every other restart surface
        # uses, mirroring the gRPC health NOT_SERVING flip
        return _shed_response(EngineRestartError(
            "engine is restarting after a fault; retry shortly",
            retry_after_s=2.0,
        ))
    try:
        await engine.check_health()
    except Exception as e:  # noqa: BLE001 — cancellation must propagate
        return error_response(500, f"engine dead: {e}", "engine_error")
    return HttpResponse(200, b"")


async def _metrics(app: App, request: HttpRequest) -> HttpResponse:  # noqa: ARG001
    engine: AsyncLLMEngine = app.state["engine"]
    # engine-state gauges (KV usage, queue depth) refresh on scrape so
    # the autoscaler never reads a stats-tick-stale value
    refresh = getattr(engine, "refresh_engine_gauges", None)
    if refresh is not None:
        refresh()
    return HttpResponse(
        200, metrics.render(), content_type="text/plain; version=0.0.4"
    )


async def _start_profile(app: App, request: HttpRequest) -> HttpResponse:  # noqa: ARG001
    from vllm_tgis_adapter_tpu.profiler import ProfilerError

    try:
        return JsonResponse(app.state["profiler"].start())
    except ProfilerError as e:
        return error_response(
            409 if "already active" in str(e) else 400, str(e)
        )


async def _stop_profile(app: App, request: HttpRequest) -> HttpResponse:  # noqa: ARG001
    from vllm_tgis_adapter_tpu.profiler import ProfilerError

    try:
        return JsonResponse(app.state["profiler"].stop())
    except ProfilerError as e:
        return error_response(
            409 if "no profiler capture" in str(e) else 400, str(e)
        )


async def _debug_state(app: App, request: HttpRequest) -> HttpResponse:  # noqa: ARG001
    """Full engine-state snapshot: scheduler queues with ages, KV pool
    stats, in-flight batch plan, compile-tracker + watchdog state, and
    the flight recorder's recent events (AsyncLLMEngine.debug_state).

    ``?section=<key>[,<key>...]`` narrows the payload to the named
    top-level sections — a dashboard polling ``step_timeline`` every
    second must not drag the full event ring along each time."""
    from urllib.parse import parse_qs, urlsplit

    engine: AsyncLLMEngine = app.state["engine"]
    state_fn = getattr(engine, "debug_state", None)
    if state_fn is None:
        return error_response(501, "engine exposes no debug state")
    state = state_fn()
    query = parse_qs(urlsplit(request.path).query)
    sections = [
        key
        for raw in query.get("section", ())
        for key in raw.split(",")
        if key
    ]
    if sections:
        unknown = [k for k in sections if k not in state]
        if unknown:
            return error_response(
                404,
                f"unknown debug-state section(s) {unknown}; "
                f"available: {sorted(state)}",
            )
        state = {k: state[k] for k in sections}
    return JsonResponse(state)


async def _debug_doctor(app: App, request: HttpRequest) -> HttpResponse:  # noqa: ARG001
    """The bottleneck doctor's view alone (telemetry/doctor.py):
    active/recent regime episodes with evidence + the rule thresholds."""
    engine: AsyncLLMEngine = app.state["engine"]
    doctor = getattr(engine, "doctor", None)
    if doctor is None:
        return error_response(501, "engine exposes no doctor state")
    return JsonResponse(doctor.debug_state())


async def _debug_timeline(app: App, request: HttpRequest) -> HttpResponse:  # noqa: ARG001
    """Unified chrome-trace timeline (telemetry/timeline.py): step
    anatomy + flight-recorder events + doctor episodes, loadable
    directly in Perfetto / chrome://tracing.  ``?format=chrome`` is the
    only (and default) format; ``?last_steps=N`` bounds the step rows."""
    from urllib.parse import parse_qs, urlsplit

    from vllm_tgis_adapter_tpu.telemetry.timeline import (
        chrome_trace_from_state,
    )

    engine: AsyncLLMEngine = app.state["engine"]
    state_fn = getattr(engine, "debug_state", None)
    if state_fn is None:
        return error_response(501, "engine exposes no debug state")
    query = parse_qs(urlsplit(request.path).query)
    fmt = query.get("format", ["chrome"])[0]
    if fmt != "chrome":
        return error_response(
            400, f"unknown timeline format {fmt!r}; supported: chrome"
        )
    last_steps = None
    raw_last = query.get("last_steps", [None])[0]
    if raw_last is not None:
        try:
            last_steps = max(1, int(raw_last))
        except ValueError:
            return error_response(400, "last_steps must be an integer")
    return JsonResponse(
        chrome_trace_from_state(state_fn(), last_steps=last_steps)
    )


async def _debug_request(app: App, request: HttpRequest) -> HttpResponse:
    """One request's flight-recorder timeline (+ live state while it is
    still in the engine)."""
    engine: AsyncLLMEngine = app.state["engine"]
    request_id = request.path_param or ""
    if not request_id:
        return error_response(400, "request id required")
    trace_fn = getattr(engine, "request_trace", None)
    if trace_fn is None:
        return error_response(501, "engine exposes no request traces")
    trace = trace_fn(request_id)
    if trace is None:
        return error_response(
            404,
            f"request {request_id!r} is unknown (never admitted, or its "
            "events aged out of the flight recorder)",
        )
    return JsonResponse(trace)


async def _tokenize(app: App, request: HttpRequest) -> HttpResponse:
    """vLLM-style /tokenize: {"prompt": str, "add_special_tokens"?: bool}
    → {"count", "max_model_len", "tokens"?}."""
    engine: AsyncLLMEngine = app.state["engine"]
    try:
        body = request.json()
    except ValueError:
        return error_response(400, "request body must be JSON")
    prompt = body.get("prompt")
    if not isinstance(prompt, str):
        return error_response(400, "prompt must be a string")
    tokenizer = engine.engine.get_tokenizer()
    ids = tokenizer(
        prompt,
        add_special_tokens=bool(body.get("add_special_tokens", True)),
    ).input_ids
    payload = {
        "count": len(ids),
        "max_model_len": engine.engine.config.max_model_len,
    }
    if body.get("return_tokens", True):
        payload["tokens"] = list(ids)
    return JsonResponse(payload)


async def _detokenize(app: App, request: HttpRequest) -> HttpResponse:
    """vLLM-style /detokenize: {"tokens": [int]} → {"prompt": str}."""
    engine: AsyncLLMEngine = app.state["engine"]
    try:
        body = request.json()
    except ValueError:
        return error_response(400, "request body must be JSON")
    tokens = body.get("tokens")
    if not isinstance(tokens, list) or not all(
        isinstance(t, int) for t in tokens
    ):
        return error_response(400, "tokens must be a list of integers")
    tokenizer = engine.engine.get_tokenizer()
    return JsonResponse({"prompt": tokenizer.decode(tokens)})


async def _version(app: App, request: HttpRequest) -> HttpResponse:  # noqa: ARG001
    from vllm_tgis_adapter_tpu import __version__

    return JsonResponse({"version": __version__})


async def _models(app: App, request: HttpRequest) -> HttpResponse:  # noqa: ARG001
    created = int(time.time())
    data = [
        {
            "id": name,
            "object": "model",
            "created": created,
            "owned_by": "vllm-tgis-adapter-tpu",
            "root": name,
        }
        for name in app.state["model_names"]
    ]
    engine: AsyncLLMEngine = app.state["engine"]
    lora_manager = getattr(engine.engine, "lora_manager", None)
    if lora_manager is not None:
        data.extend(
            {
                "id": name,
                "object": "model",
                "created": created,
                "owned_by": "vllm-tgis-adapter-tpu",
                "root": req.lora_path,
                "parent": app.state["model_names"][0],
            }
            for name, req in lora_manager.lora_requests.items()
        )
    return JsonResponse({"object": "list", "data": data})


def _completion_sampling_params(body: dict[str, Any]) -> SamplingParams:
    stop = body.get("stop")
    if isinstance(stop, str):
        stop = [stop]
    temperature = float(body.get("temperature", 1.0))
    params = dict(
        max_tokens=int(body.get("max_tokens", 16)),
        temperature=temperature,
        seed=body.get("seed"),
        stop=stop,
        repetition_penalty=float(body.get("repetition_penalty", 1.0)),
        logprobs=body.get("logprobs"),
        min_tokens=int(body.get("min_tokens", 0)),
        ignore_eos=bool(body.get("ignore_eos", False)),
    )
    if temperature > 0.0:
        params.update(
            top_p=float(body.get("top_p", 1.0)),
            top_k=int(body.get("top_k", -1)),
        )
    return SamplingParams(**params)


def _check_api_key(app: App, request: HttpRequest) -> Optional[HttpResponse]:
    """The one --api-key Bearer check (OpenAI endpoints AND the
    mutating adapter admin endpoints — an auth fix can never land on
    one surface and miss the other)."""
    if (key := app.state.get("api_key")) and request.headers.get(
        "authorization"
    ) != f"Bearer {key}":
        return error_response(
            401, "invalid api key", "authentication_error"
        )
    return None


async def _load_lora_adapter(app: App, request: HttpRequest) -> HttpResponse:
    """vLLM-compatible dynamic adapter registration: ``{"lora_name":
    ..., "lora_path": ...}``.  Load/parse failures (missing
    adapter_config.json, over-rank, unknown target modules, pinned-full
    registry) surface as 400 with the actionable message via the typed
    taxonomy (frontdoor/errors.py classify), never a generic 500."""
    if (err := _check_api_key(app, request)) is not None:
        return err
    engine: AsyncLLMEngine = app.state["engine"]
    lora_manager = getattr(engine.engine, "lora_manager", None)
    if lora_manager is None or not engine.engine.config.lora_config.enabled:
        return error_response(
            400, "LoRA is disabled on this server (--enable-lora)"
        )
    try:
        body = request.json()
    except json.JSONDecodeError as e:
        return error_response(400, f"invalid JSON body: {e}")
    name = body.get("lora_name")
    path = body.get("lora_path")
    if not name or not path:
        return error_response(
            400, "body must carry lora_name and lora_path"
        )
    from vllm_tgis_adapter_tpu.engine.lora import LoRAError

    try:
        await lora_manager.load_lora_adapter(name, path)
    except LoRAError as e:
        return _shed_response(e)
    except OSError as e:
        return error_response(400, f"cannot read adapter {name!r}: {e}")
    return JsonResponse({"status": "ok", "lora_name": name})


async def _unload_lora_adapter(app: App, request: HttpRequest) -> HttpResponse:
    if (err := _check_api_key(app, request)) is not None:
        return err
    engine: AsyncLLMEngine = app.state["engine"]
    lora_manager = getattr(engine.engine, "lora_manager", None)
    if lora_manager is None or not engine.engine.config.lora_config.enabled:
        return error_response(
            400, "LoRA is disabled on this server (--enable-lora)"
        )
    try:
        body = request.json()
    except json.JSONDecodeError as e:
        return error_response(400, f"invalid JSON body: {e}")
    name = body.get("lora_name")
    if not name:
        return error_response(400, "body must carry lora_name")
    from vllm_tgis_adapter_tpu.engine.lora import LoRAError

    try:
        lora_manager.unload_lora_adapter(name)
    except LoRAError as e:
        return _shed_response(e)
    return JsonResponse({"status": "ok", "lora_name": name})


def _openai_preamble(app: App, request: HttpRequest):
    """Auth + body parse + model lookup shared by the OpenAI endpoints.

    Returns (body, model_name, lora_request, None) on success or
    (None, None, None, error response) — one implementation so an auth
    or validation fix can never land on one endpoint and miss the
    other.  ``model`` naming a registered LoRA adapter (the /v1/models
    listing includes them) resolves to that adapter's engine request —
    the OpenAI-compatible multi-LoRA surface vLLM serves.
    """
    if (err := _check_api_key(app, request)) is not None:
        return None, None, None, err
    try:
        body = request.json()
    except json.JSONDecodeError as e:
        return None, None, None, error_response(
            400, f"invalid JSON body: {e}"
        )
    model_name = body.get("model") or app.state["model_names"][0]
    lora_request = None
    if model_name not in app.state["model_names"]:
        engine: AsyncLLMEngine = app.state["engine"]
        lora_manager = getattr(engine.engine, "lora_manager", None)
        if lora_manager is not None:
            lora_request = lora_manager.lora_requests.get(model_name)
        if lora_request is None:
            return None, None, None, error_response(
                404, f"model {model_name!r} does not exist"
            )
    return body, model_name, lora_request, None


def _parse_n(body: dict[str, Any]):
    """OpenAI ``n`` (samples per prompt): strict-integer 1..64, shared by
    the completions and chat endpoints so validation cannot diverge."""
    n = body.get("n", 1)
    if isinstance(n, bool) or not isinstance(n, int) or not 1 <= n <= 64:
        return None, error_response(
            400, "n must be an integer between 1 and 64"
        )
    return n, None


def _sibling_params(sampling_params: "SamplingParams", k: int, n: int,
                    output_kind) -> "SamplingParams":  # noqa: ANN001
    """Per-sample copy of the request params: sibling k of a seeded
    request gets a DISTINCT but reproducible stream (seed+k, wrapped to
    the uint64 domain __post_init__ enforces)."""
    sp = SamplingParams(**{**sampling_params.__dict__})
    if sp.seed is not None and n > 1:
        sp.seed = (sp.seed + k) % (1 << 64)
    sp.output_kind = output_kind
    return sp



async def _stream_head(merged):  # noqa: ANN001, ANN202
    """Await the merged generators' first item before the streaming
    response commits its status line.

    Returns ``((index, result) | None, None)`` on success (None when
    every stream was empty) or ``(None, error_response)`` when the
    first event was a shed/overload/validation failure — those must go
    on the wire as their real statuses (429/503/400), which is only
    possible before any body bytes exist.  A failure arriving later,
    mid-stream, still degrades to an in-band error frame.
    """
    try:
        return await merged.__anext__(), None
    except StopAsyncIteration:
        return None, None
    except (AdmissionShedError, CapacityError, EngineRestartError) as e:
        return None, _shed_response(e)
    except ValueError as e:
        return None, error_response(400, str(e))


async def _completions(app: App, request: HttpRequest):  # noqa: ANN201, C901, PLR0915
    engine: AsyncLLMEngine = app.state["engine"]
    body, model_name, lora_request, err = _openai_preamble(app, request)
    if err is not None:
        return err

    prompt = body.get("prompt", "")
    prompts = prompt if isinstance(prompt, list) else [prompt]
    if not prompts or not all(isinstance(p, str) for p in prompts):
        return error_response(400, "prompt must be a string or list of strings")
    n, err = _parse_n(body)
    if err is not None:
        return err
    try:
        sampling_params = _completion_sampling_params(body)
    except (ValueError, TypeError) as e:
        return error_response(400, str(e))

    stream = bool(body.get("stream", False))
    base_request_id = uuid.uuid4().hex
    created = int(time.time())
    completion_id = f"cmpl-{base_request_id}"
    correlation_id = request.headers.get(CORRELATION_ID_HEADER)

    # OpenAI n: each prompt expands into n independent samples; choices
    # are prompt-major (index = prompt_idx * n + k).  Each sample is its
    # own engine request, so with --enable-prefix-caching the n-1
    # siblings adopt the first sample's prompt pages instead of
    # re-running prefill.
    logs.set_correlation_id(base_request_id, correlation_id)
    out_kind = (
        RequestOutputKind.DELTA if stream else RequestOutputKind.FINAL_ONLY
    )
    generators = []
    for pi, p in enumerate(prompts):
        for k in range(n):
            # id format {method}-{base}-{index} is what
            # logs.get_correlation_id strips back down (reference format,
            # tgis_utils/logs.py:40-44)
            generators.append(engine.generate(
                prompt=p,
                sampling_params=_sibling_params(
                    sampling_params, k, n, out_kind
                ),
                request_id=f"cmpl-{base_request_id}-{pi * n + k}",
                lora_request=lora_request,
                trace_headers=_trace_headers(request),
                tenant_id=_tenant_id(app, request),
            ))

    from vllm_tgis_adapter_tpu.utils import merge_async_iterators

    merged = merge_async_iterators(*generators)

    if stream:
        # pull the first result BEFORE committing the 200 + stream
        # headers: a shed/overload raised on the generators' first
        # iteration must surface as a real 429/503 + Retry-After, not
        # as an error frame inside a 200 stream
        first, err = await _stream_head(merged)
        if err is not None:
            return err

        async def sse() -> AsyncIterator[bytes]:
            def chunk(i: int, out) -> bytes:  # noqa: ANN001
                payload = {
                    "id": completion_id,
                    "object": "text_completion",
                    "created": created,
                    "model": model_name,
                    "choices": [
                        {
                            "index": i,
                            "text": out.text,
                            "logprobs": None,
                            "finish_reason": out.finish_reason,
                        }
                    ],
                }
                return f"data: {json.dumps(payload)}\n\n".encode()

            try:
                if first is not None:
                    yield chunk(first[0], first[1].outputs[0])
                async for i, res in merged:
                    yield chunk(i, res.outputs[0])
            except Exception as e:  # noqa: BLE001 — cancellation must propagate
                err_frame = {
                    "error": {"message": str(e), "type": "server_error"}
                }
                yield f"data: {json.dumps(err_frame)}\n\n".encode()
            yield b"data: [DONE]\n\n"

        return StreamingResponse(sse())

    results: list = [None] * (len(prompts) * n)
    try:
        async for i, res in merged:
            results[i] = res
    except (AdmissionShedError, CapacityError, EngineRestartError) as e:
        # overload: 429 + Retry-After (shed) or 503 (exhaustion); any
        # sibling streams already admitted are reaped on cancellation
        return _shed_response(e)
    except ValueError as e:
        return error_response(400, str(e))

    # usage counts each prompt's tokens ONCE (OpenAI convention) even
    # though n siblings each carry it
    prompt_tokens = sum(
        len(results[pi * n].prompt_token_ids) for pi in range(len(prompts))
    )
    completion_tokens = sum(len(r.outputs[0].token_ids) for r in results)
    choices = []
    for i, res in enumerate(results):
        out = res.outputs[0]
        text = out.text
        if body.get("echo"):
            text = prompts[i // n] + text
        choices.append(
            {
                "index": i,
                "text": text,
                "logprobs": _convert_http_logprobs(out, engine)
                if sampling_params.logprobs is not None
                else None,
                "finish_reason": out.finish_reason,
                "stop_reason": out.stop_reason,
            }
        )
    return JsonResponse(
        {
            "id": completion_id,
            "object": "text_completion",
            "created": created,
            "model": model_name,
            "choices": choices,
            "usage": {
                "prompt_tokens": prompt_tokens,
                "completion_tokens": completion_tokens,
                "total_tokens": prompt_tokens + completion_tokens,
            },
        }
    )


def _render_chat_prompt(tokenizer, messages: list[dict]) -> str:  # noqa: ANN001
    """messages → prompt text via the model's chat template.

    Models without a bundled template get a minimal role-prefixed layout
    (same fallback stance as serving stacks that accept template-less
    models rather than rejecting chat outright).
    """
    if getattr(tokenizer, "chat_template", None):
        return tokenizer.apply_chat_template(
            messages, tokenize=False, add_generation_prompt=True
        )
    lines = [f"{m['role']}: {m['content']}" for m in messages]
    return "\n".join(lines) + "\nassistant:"


async def _chat_completions(app: App, request: HttpRequest):  # noqa: ANN201, C901
    """OpenAI chat API over the shared engine (reference parity: the
    embedded vLLM app serves chat from the same engine as completions)."""
    engine: AsyncLLMEngine = app.state["engine"]
    body, model_name, lora_request, err = _openai_preamble(app, request)
    if err is not None:
        return err

    messages = body.get("messages")
    if (
        not isinstance(messages, list)
        or not messages
        or not all(
            isinstance(m, dict) and isinstance(m.get("content"), str)
            and m.get("role")
            for m in messages
        )
    ):
        return error_response(
            400, "messages must be a non-empty list of {role, content} "
                 "objects"
        )
    n, err = _parse_n(body)
    if err is not None:
        return err
    if body.get("logprobs"):
        return error_response(
            400, "logprobs is not supported on the chat endpoint"
        )

    tokenizer = engine.engine.get_tokenizer()
    try:
        prompt = _render_chat_prompt(tokenizer, messages)
    except Exception as e:  # noqa: BLE001 — template errors are client input
        return error_response(400, f"chat template rejected messages: {e}")

    if "max_tokens" not in body and "max_completion_tokens" in body:
        body = {**body, "max_tokens": body["max_completion_tokens"]}
    if "max_tokens" not in body:
        # chat clients rarely set a budget; default to the remaining
        # context (the vLLM chat server's behavior) instead of the
        # completions endpoint's OpenAI-compat default of 16
        config = await engine.get_model_config()
        n_prompt = len(tokenizer(prompt).input_ids)
        body = {
            **body,
            "max_tokens": max(1, config.max_model_len - n_prompt - 1),
        }
    try:
        sampling_params = _completion_sampling_params(body)
    except (ValueError, TypeError) as e:
        return error_response(400, str(e))

    stream = bool(body.get("stream", False))
    base_request_id = uuid.uuid4().hex
    created = int(time.time())
    chat_id = f"chatcmpl-{base_request_id}"
    logs.set_correlation_id(
        base_request_id, request.headers.get(CORRELATION_ID_HEADER)
    )
    out_kind = (
        RequestOutputKind.DELTA if stream else RequestOutputKind.FINAL_ONLY
    )
    # n independent samples of the same rendered prompt (prefix caching
    # lets siblings adopt the first sample's prompt pages)
    generators = [
        engine.generate(
            prompt=prompt,
            sampling_params=_sibling_params(sampling_params, k, n, out_kind),
            request_id=f"chat-{base_request_id}-{k}",
            lora_request=lora_request,
            trace_headers=_trace_headers(request),
            tenant_id=_tenant_id(app, request),
        )
        for k in range(n)
    ]

    from vllm_tgis_adapter_tpu.utils import merge_async_iterators

    merged = merge_async_iterators(*generators)

    if stream:
        # same head-await as _completions: sheds on the first iteration
        # become real 429/503 responses, not error frames inside a 200
        first, head_err = await _stream_head(merged)
        if head_err is not None:
            return head_err

        async def sse() -> AsyncIterator[bytes]:
            def chunk(idx: int, delta: dict,
                      finish: Optional[str]) -> bytes:
                payload = {
                    "id": chat_id,
                    "object": "chat.completion.chunk",
                    "created": created,
                    "model": model_name,
                    "choices": [{
                        "index": idx,
                        "delta": delta,
                        "finish_reason": finish,
                    }],
                }
                return f"data: {json.dumps(payload)}\n\n".encode()

            def content_chunks(k: int, res) -> list[bytes]:  # noqa: ANN001
                out = res.outputs[0]
                frames = []
                if out.text:
                    frames.append(chunk(k, {"content": out.text}, None))
                if out.finish_reason:
                    frames.append(chunk(k, {}, out.finish_reason))
                return frames

            for k in range(n):
                yield chunk(k, {"role": "assistant", "content": ""}, None)
            try:
                if first is not None:
                    for frame in content_chunks(first[0], first[1]):
                        yield frame
                async for k, res in merged:
                    for frame in content_chunks(k, res):
                        yield frame
            except Exception as e:  # noqa: BLE001 — cancellation propagates
                err = {"error": {"message": str(e), "type": "server_error"}}
                yield f"data: {json.dumps(err)}\n\n".encode()
            yield b"data: [DONE]\n\n"

        return StreamingResponse(sse())

    finals: list = [None] * n
    try:
        async for k, res in merged:
            finals[k] = res
    except (AdmissionShedError, CapacityError, EngineRestartError) as e:
        return _shed_response(e)
    except ValueError as e:
        return error_response(400, str(e))
    n_prompt = len(finals[0].prompt_token_ids or ())
    n_out = sum(len(f.outputs[0].token_ids) for f in finals)
    return JsonResponse({
        "id": chat_id,
        "object": "chat.completion",
        "created": created,
        "model": model_name,
        "choices": [{
            "index": k,
            "message": {
                "role": "assistant", "content": f.outputs[0].text
            },
            "finish_reason": f.outputs[0].finish_reason,
            "stop_reason": f.outputs[0].stop_reason,
        } for k, f in enumerate(finals)],
        "usage": {
            "prompt_tokens": n_prompt,
            "completion_tokens": n_out,
            "total_tokens": n_prompt + n_out,
        },
    })


def _convert_http_logprobs(out, engine) -> Optional[dict]:  # noqa: ANN001
    if out.logprobs is None:
        return None
    tokenizer = engine.engine.get_tokenizer()
    token_logprobs: list[Optional[float]] = []
    tokens: list[str] = []
    top_logprobs: list[Optional[dict[str, float]]] = []
    for tid, entry in zip(out.token_ids, out.logprobs):
        if entry is None:
            token_logprobs.append(None)
            tokens.append(tokenizer.convert_ids_to_tokens(tid))
            top_logprobs.append(None)
            continue
        lp = entry.get(tid)
        tokens.append(tokenizer.convert_ids_to_tokens(tid))
        token_logprobs.append(lp.logprob if lp else None)
        top_logprobs.append(
            {
                tokenizer.convert_ids_to_tokens(t): v.logprob
                for t, v in entry.items()
            }
        )
    return {
        "tokens": tokens,
        "token_logprobs": token_logprobs,
        "top_logprobs": top_logprobs,
        "text_offset": [],
    }


# ------------------------------------------------------- h11 server plumbing


_MAX_BODY = 32 * 1024 * 1024


async def _handle_connection(  # noqa: C901, PLR0915
    app: App,
    reader: asyncio.StreamReader,
    writer: asyncio.StreamWriter,
) -> None:
    conn = h11.Connection(h11.SERVER)

    async def send(event) -> None:  # noqa: ANN001
        data = conn.send(event)
        if data:
            writer.write(data)
            await writer.drain()

    try:
        while True:
            # -------- read one request (headers + full body)
            request_ev = None
            body = b""
            while True:
                event = conn.next_event()
                if event is h11.NEED_DATA:
                    data = await reader.read(65536)
                    conn.receive_data(data)
                    if data == b"" and request_ev is None:
                        return  # client closed between requests
                    continue
                if isinstance(event, h11.Request):
                    request_ev = event
                elif isinstance(event, h11.Data):
                    body += event.data
                    if len(body) > _MAX_BODY:
                        return
                elif isinstance(event, h11.EndOfMessage):
                    break
                elif isinstance(event, (h11.ConnectionClosed,)):
                    return

            headers = {
                k.decode("latin1").lower(): v.decode("latin1")
                for k, v in request_ev.headers
            }
            request = HttpRequest(
                method=request_ev.method.decode(),
                path=request_ev.target.decode(),
                headers=headers,
                body=body,
            )

            # correlation-ID middleware behavior (reference: http.py:26-38)
            correlation_id = headers.get(CORRELATION_ID_HEADER)

            try:
                response = await app.dispatch(request)
            except Exception as e:  # noqa: BLE001
                logger.exception("HTTP handler failed")
                response = error_response(500, str(e), "server_error")

            common_headers = [
                ("server", "vllm-tgis-adapter-tpu"),
                ("date", _http_date()),
            ]
            if correlation_id:
                common_headers.append((CORRELATION_ID_HEADER, correlation_id))
            for k, v in response.headers.items():
                common_headers.append((k.lower(), v))

            if isinstance(response, StreamingResponse):
                await send(
                    h11.Response(
                        status_code=response.status,
                        headers=[
                            *common_headers,
                            ("content-type", response.content_type),
                            ("transfer-encoding", "chunked"),
                        ],
                    )
                )
                async for chunk in response.chunks:
                    await send(h11.Data(data=chunk))
                await send(h11.EndOfMessage())
            else:
                await send(
                    h11.Response(
                        status_code=response.status,
                        headers=[
                            *common_headers,
                            ("content-type", response.content_type),
                            ("content-length", str(len(response.body))),
                        ],
                    )
                )
                await send(h11.Data(data=response.body))
                await send(h11.EndOfMessage())

            # -------- keep-alive / close
            if conn.our_state is h11.MUST_CLOSE or conn.their_state in (
                h11.MUST_CLOSE,
                h11.CLOSED,
            ):
                return
            try:
                conn.start_next_cycle()
            except h11.ProtocolError:
                return
    except (
        ConnectionResetError,
        BrokenPipeError,
        asyncio.IncompleteReadError,
        h11.RemoteProtocolError,
    ):
        pass
    finally:
        try:
            writer.close()
            await writer.wait_closed()
        except Exception:  # noqa: BLE001, S110
            pass


def _http_date() -> str:
    from email.utils import formatdate

    return formatdate(time.time(), usegmt=True)


def _build_ssl_context(
    args: "argparse.Namespace",
) -> Optional[ssl_module.SSLContext]:
    """Blocking half of TLS setup (cert/key/CA file reads); callers on
    the event loop dispatch it through ``asyncio.to_thread``."""
    if not (args.ssl_keyfile and args.ssl_certfile):
        return None
    ssl_context = ssl_module.SSLContext(ssl_module.PROTOCOL_TLS_SERVER)
    ssl_context.load_cert_chain(args.ssl_certfile, args.ssl_keyfile)
    if args.ssl_ca_certs:
        ssl_context.load_verify_locations(args.ssl_ca_certs)
        ssl_context.verify_mode = ssl_module.CERT_REQUIRED
    return ssl_context


async def run_http_server(
    args: "argparse.Namespace",
    engine: "AsyncLLMEngine",
    app: App,
    sock: Optional[socket.socket] = None,
) -> None:
    """Serve the app forever on ``sock`` (pre-bound by the entrypoint)."""
    # cert files load off the loop (tpulint TPL302): the gRPC server and
    # engine step loop are already live when the HTTP tier boots
    ssl_context = await asyncio.to_thread(_build_ssl_context, args)

    writers: set[asyncio.StreamWriter] = set()

    async def client_connected(
        reader: asyncio.StreamReader, writer: asyncio.StreamWriter
    ) -> None:
        writers.add(writer)
        try:
            await _handle_connection(app, reader, writer)
        finally:
            writers.discard(writer)

    if sock is not None:
        server = await asyncio.start_server(
            client_connected, sock=sock, ssl=ssl_context
        )
    else:
        server = await asyncio.start_server(
            client_connected,
            host=args.host or "0.0.0.0",  # noqa: S104
            port=args.port,
            ssl=ssl_context,
        )
    addr = args.host or "0.0.0.0"  # noqa: S104
    logger.info("HTTP Server started at %s:%s", addr, args.port)
    try:
        # start_server is already accepting; serve until cancelled
        await asyncio.get_running_loop().create_future()
    finally:
        # not serve_forever(): its cancel path waits on idle clients
        await close_server(server, writers, "HTTP server")
