"""Full-stack gRPC integration tests against the real dual-server stack.

Mirrors the reference's integration coverage (tests/test_grpc_server.py):
generation, tokenization, streaming framing (N tokens → N+1 messages),
batching, validation errors, model info, token detail options, stop
sequences, and time limits — all through real RPCs against a real engine
running the tiny fixture model on the JAX CPU backend.
"""

from __future__ import annotations

import grpc
import pytest

try:  # pragma: no cover - environment probe
    from vllm_tgis_adapter_tpu.grpc.pb import generation_pb2 as pb2
except ImportError as _e:  # protoc missing in this environment
    pytest.skip(
        f"protoc-generated gRPC bindings unavailable ({_e}); install "
        "protoc (or a wheel with prebuilt pb2 modules) to run this suite",
        allow_module_level=True,
    )


def test_generation_request(grpc_client):
    response = grpc_client.make_request("The answer to life the universe")
    assert response.text
    assert response.generated_token_count == 10
    assert response.stop_reason == pb2.StopReason.MAX_TOKENS
    assert response.input_token_count > 0


def test_generation_request_stop_reason_eos_or_max(grpc_client):
    params = pb2.Parameters(
        method=pb2.DecodingMethod.GREEDY,
        stopping=pb2.StoppingCriteria(max_new_tokens=64),
    )
    response = grpc_client.make_request("this is a test", params=params)
    assert response.stop_reason in (
        pb2.StopReason.MAX_TOKENS,
        pb2.StopReason.EOS_TOKEN,
    )


def test_batched_generation_request(grpc_client):
    responses = grpc_client.make_request(
        ["The answer to life", "Medicine is", "The capital of France is"]
    )
    assert len(responses) == 3
    for response in responses:
        assert response.generated_token_count == 10
        assert response.input_token_count > 0


def test_generation_request_stream(grpc_client):
    streaming_response = grpc_client.make_request_stream(
        "The answer to life the universe",
        max_new_tokens=10,
    )
    # input-details frame + one frame per generated token
    assert len(streaming_response) == 11
    first = streaming_response[0]
    assert first.input_token_count > 0
    assert first.generated_token_count == 0
    text = "".join(r.text for r in streaming_response)
    assert text
    assert streaming_response[-1].stop_reason == pb2.StopReason.MAX_TOKENS
    total_tokens = streaming_response[-1].generated_token_count
    assert total_tokens == 10


def test_stream_matches_unary(grpc_client):
    prompt = "The weather today is"
    unary = grpc_client.make_request(prompt, max_new_tokens=12)
    stream = grpc_client.make_request_stream(prompt, max_new_tokens=12)
    assert "".join(r.text for r in stream) == unary.text


def test_tokenize_request(grpc_client):
    response = grpc_client.make_request_tokenize("The answer to life")
    assert response.token_count > 0
    assert not response.tokens


def test_tokenize_with_tokens_and_offsets(grpc_client):
    response = grpc_client.make_request_tokenize(
        "Hello world, how are you?", return_tokens=True, return_offsets=True
    )
    assert response.token_count > 0
    assert len(response.tokens) == response.token_count
    assert len(response.offsets) == response.token_count


def test_tokenize_truncation(grpc_client):
    full = grpc_client.make_request_tokenize("one two three four five six seven")
    truncated = grpc_client.make_request_tokenize(
        "one two three four five six seven",
        return_tokens=True,
        truncate_input_tokens=3,
    )
    assert full.token_count > 3
    assert truncated.token_count == 3
    assert len(truncated.tokens) == 3


def test_model_info(grpc_client):
    info = grpc_client.model_info()
    assert info.model_kind == pb2.ModelInfoResponse.ModelKind.DECODER_ONLY
    assert info.max_sequence_length == 512
    assert info.max_new_tokens == 1024


def test_generation_with_token_details(grpc_client):
    params = pb2.Parameters(
        method=pb2.DecodingMethod.GREEDY,
        stopping=pb2.StoppingCriteria(max_new_tokens=5),
        response=pb2.ResponseOptions(
            generated_tokens=True,
            token_logprobs=True,
            token_ranks=True,
            top_n_tokens=2,
        ),
    )
    response = grpc_client.make_request("The answer to life", params=params)
    assert len(response.tokens) == 5
    for token in response.tokens:
        assert token.text
        assert token.logprob <= 0.0
        assert token.rank >= 1
        assert len(token.top_tokens) == 2


def test_generation_with_input_tokens(grpc_client):
    params = pb2.Parameters(
        method=pb2.DecodingMethod.GREEDY,
        stopping=pb2.StoppingCriteria(max_new_tokens=5),
        response=pb2.ResponseOptions(
            input_tokens=True,
            generated_tokens=True,
            token_logprobs=True,
        ),
    )
    response = grpc_client.make_request("The answer to life", params=params)
    assert len(response.input_tokens) == response.input_token_count
    # first prompt token has no logprob entry
    assert response.input_tokens[0].logprob == 0.0


def test_generation_with_stop_sequence(grpc_client):
    params = pb2.Parameters(
        method=pb2.DecodingMethod.GREEDY,
        stopping=pb2.StoppingCriteria(
            max_new_tokens=64,
            stop_sequences=["e"],
        ),
    )
    response = grpc_client.make_request("The answer to life", params=params)
    if response.stop_reason == pb2.StopReason.STOP_SEQUENCE:
        assert response.stop_sequence == "e"
        # server default is --default-include-stop-seqs=true
        assert response.text.endswith("e")


def test_generation_with_stop_sequence_excluded(grpc_client):
    params = pb2.Parameters(
        method=pb2.DecodingMethod.GREEDY,
        stopping=pb2.StoppingCriteria(
            max_new_tokens=64,
            stop_sequences=["e"],
            include_stop_sequence=False,
        ),
    )
    response = grpc_client.make_request("The answer to life", params=params)
    if response.stop_reason == pb2.StopReason.STOP_SEQUENCE:
        assert "e" not in response.text


def test_generation_seeded_sampling_reproducible(grpc_client):
    params = pb2.Parameters(
        method=pb2.DecodingMethod.SAMPLE,
        sampling=pb2.SamplingParameters(temperature=0.9, seed=42),
        stopping=pb2.StoppingCriteria(max_new_tokens=8),
    )
    r1 = grpc_client.make_request("Once upon a time", params=params)
    r2 = grpc_client.make_request("Once upon a time", params=params)
    assert r1.text == r2.text
    assert r1.seed == 42


def test_generation_input_text_echo(grpc_client):
    params = pb2.Parameters(
        method=pb2.DecodingMethod.GREEDY,
        stopping=pb2.StoppingCriteria(max_new_tokens=4),
        response=pb2.ResponseOptions(input_text=True),
    )
    prompt = "The answer to life"
    response = grpc_client.make_request(prompt, params=params)
    assert response.text.startswith(prompt)


def test_time_limit(grpc_client):
    params = pb2.Parameters(
        method=pb2.DecodingMethod.GREEDY,
        stopping=pb2.StoppingCriteria(
            max_new_tokens=1024, time_limit_millis=300
        ),
    )
    response = grpc_client.make_request("Count to one thousand:", params=params)
    assert response.stop_reason in (
        pb2.StopReason.TIME_LIMIT,
        # fast machines may legitimately finish first — including by
        # running the tiny fixture model out to its max_model_len
        pb2.StopReason.EOS_TOKEN,
        pb2.StopReason.MAX_TOKENS,
        pb2.StopReason.TOKEN_LIMIT,
    )


@pytest.mark.parametrize(
    ("params", "error_fragment"),
    [
        (
            pb2.Parameters(
                response=pb2.ResponseOptions(
                    generated_tokens=True, top_n_tokens=11
                )
            ),
            "top_n_tokens",
        ),
        (
            pb2.Parameters(
                stopping=pb2.StoppingCriteria(max_new_tokens=2048)
            ),
            "max_new_tokens must be <= 1024",
        ),
        (
            pb2.Parameters(
                stopping=pb2.StoppingCriteria(
                    max_new_tokens=10, min_new_tokens=20
                )
            ),
            "min_new_tokens must be <= max_new_tokens",
        ),
        (
            pb2.Parameters(
                stopping=pb2.StoppingCriteria(
                    stop_sequences=["a"] * 7, max_new_tokens=10
                )
            ),
            "stop sequences",
        ),
        (
            pb2.Parameters(
                method=pb2.DecodingMethod.SAMPLE,
                sampling=pb2.SamplingParameters(top_p=1.5),
                stopping=pb2.StoppingCriteria(max_new_tokens=10),
            ),
            "top_p",
        ),
        (
            pb2.Parameters(
                response=pb2.ResponseOptions(token_logprobs=True),
                stopping=pb2.StoppingCriteria(max_new_tokens=10),
            ),
            "must request input and/or generated tokens",
        ),
    ],
)
def test_invalid_params_rejected(grpc_client, params, error_fragment):
    with pytest.raises(grpc.RpcError) as excinfo:
        grpc_client.make_request("test", params=params)
    assert excinfo.value.code() == grpc.StatusCode.INVALID_ARGUMENT
    assert error_fragment in excinfo.value.details()


def test_input_too_long_rejected(grpc_client):
    with pytest.raises(grpc.RpcError) as excinfo:
        grpc_client.make_request("word " * 600, max_new_tokens=5)
    assert excinfo.value.code() == grpc.StatusCode.INVALID_ARGUMENT
    assert "input tokens" in excinfo.value.details()


def test_truncate_input_tokens(grpc_client):
    params = pb2.Parameters(
        method=pb2.DecodingMethod.GREEDY,
        stopping=pb2.StoppingCriteria(max_new_tokens=5),
        truncate_input_tokens=3,
    )
    response = grpc_client.make_request("word " * 600, params=params)
    assert response.input_token_count <= 3


def test_request_id_from_correlation_id_header(grpc_client):
    response = grpc_client.make_request(
        "The answer to life",
        metadata=[("x-correlation-id", "test-correlation-id")],
    )
    assert response.text


def test_unknown_adapter_rejected(grpc_client):
    with pytest.raises(grpc.RpcError) as excinfo:
        grpc_client.make_request(
            "test", adapter_id="this-adapter-does-not-exist"
        )
    assert excinfo.value.code() == grpc.StatusCode.INVALID_ARGUMENT
    assert "can't retrieve adapter" in excinfo.value.details()


@pytest.mark.parametrize(
    "guided",
    ["json_format", "json_schema", "regex", "choice"],
)
def test_guided_decoding_over_grpc(grpc_client, guided):
    """Constrained generation over the wire (reference test matrix:
    tests/test_grpc_server.py guided parametrization)."""
    import json as json_mod

    from vllm_tgis_adapter_tpu.grpc.pb import generation_pb2 as pb2

    decoding = pb2.DecodingParameters()
    if guided == "json_format":
        decoding.format = pb2.DecodingParameters.JSON
    elif guided == "json_schema":
        decoding.json_schema = json_mod.dumps({
            "type": "object",
            "properties": {"n": {"type": "integer"}},
            "required": ["n"],
        })
    elif guided == "regex":
        decoding.regex = "[0-9]{2}-[0-9]{2}"
    elif guided == "choice":
        decoding.choice.choices.extend(["alpha", "beta"])

    params = pb2.Parameters(
        method=pb2.SAMPLE,
        sampling=pb2.SamplingParameters(seed=11),
        stopping=pb2.StoppingCriteria(max_new_tokens=48),
        decoding=decoding,
    )
    response = grpc_client.make_request("generate: ", params=params)
    text = response.text
    if guided == "json_format":
        # every emitted token obeyed the JSON FSM; if the budget ran out
        # mid-document the stream is a valid prefix truncated by length
        # (same semantics as the reference's guided backends)
        if response.stop_reason == pb2.MAX_TOKENS:
            assert text.startswith("{")
        else:
            assert json_mod.loads(text) is not None
    elif guided == "json_schema":
        assert isinstance(json_mod.loads(text)["n"], int)
    elif guided == "regex":
        import re

        assert re.fullmatch(r"[0-9]{2}-[0-9]{2}", text), text
    elif guided == "choice":
        assert text in ("alpha", "beta")


def test_guided_grammar_generation(grpc_client):
    """Grammar-constrained generation over the wire: the reply must be a
    sentence of the grammar (reference parity: guided_decoding_grammar,
    /root/reference/tests/test_grpc_server.py:189-196)."""
    from vllm_tgis_adapter_tpu.grpc.pb import generation_pb2 as pb2

    grammar = 'root ::= "yes " ("please" | "thanks")'
    params = pb2.Parameters(
        stopping=pb2.StoppingCriteria(max_new_tokens=32),
        decoding=pb2.DecodingParameters(grammar=grammar),
    )
    response = grpc_client.make_request("answer: ", params=params)
    assert response.text in ("yes please", "yes thanks")


def test_guided_grammar_malformed_rejected(grpc_client):
    """A malformed grammar fails request validation, not the stream."""
    from vllm_tgis_adapter_tpu.grpc.pb import generation_pb2 as pb2

    params = pb2.Parameters(
        stopping=pb2.StoppingCriteria(max_new_tokens=4),
        decoding=pb2.DecodingParameters(grammar='root ::= "unterminated'),
    )
    with pytest.raises(grpc.RpcError) as excinfo:
        grpc_client.make_request("test", params=params)
    assert excinfo.value.code() == grpc.StatusCode.INVALID_ARGUMENT


def test_server_reflection(_servers):
    """v1alpha reflection: list services + fetch the fmaas descriptor set
    (what `grpcurl list` / `describe` do under the hood)."""
    import grpc
    from google.protobuf import descriptor_pb2

    from vllm_tgis_adapter_tpu.grpc.pb import reflection_pb2

    def ask(channel, **kwargs):
        call = channel.stream_stream(
            "/grpc.reflection.v1alpha.ServerReflection/ServerReflectionInfo",
            request_serializer=(
                reflection_pb2.ServerReflectionRequest.SerializeToString
            ),
            response_deserializer=(
                reflection_pb2.ServerReflectionResponse.FromString
            ),
        )
        req = reflection_pb2.ServerReflectionRequest(**kwargs)
        return next(iter(call(iter([req]))))

    with grpc.insecure_channel(f"localhost:{_servers.grpc_port}") as ch:
        listing = ask(ch, list_services="*")
        names = {s.name for s in listing.list_services_response.service}
        assert "fmaas.GenerationService" in names
        assert "grpc.health.v1.Health" in names
        assert "grpc.reflection.v1alpha.ServerReflection" in names

        symbol = ask(ch, file_containing_symbol="fmaas.GenerationService")
        blobs = symbol.file_descriptor_response.file_descriptor_proto
        assert blobs
        fdp = descriptor_pb2.FileDescriptorProto.FromString(blobs[-1])
        assert fdp.package == "fmaas"
        assert any(s.name == "GenerationService" for s in fdp.service)

        missing = ask(ch, file_containing_symbol="no.such.Service")
        assert missing.error_response.error_message


def test_debug_dump_state_rpc(grpc_client, _servers):
    """tgis_tpu.debug.v1.Debug/DumpState serves the same snapshot as
    GET /debug/state (acceptance: queues + KV occupancy + events live
    over gRPC)."""
    import json as _json

    import grpc as _grpc

    from vllm_tgis_adapter_tpu.grpc.debug import DebugStub
    from vllm_tgis_adapter_tpu.grpc.pb import debug_pb2

    grpc_client.make_request("dump state probe", max_new_tokens=3)
    with _grpc.insecure_channel(f"localhost:{_servers.grpc_port}") as ch:
        stub = DebugStub(ch)
        resp = stub.DumpState(debug_pb2.StateRequest())
        state = _json.loads(resp.state_json)
        assert state["engine"]["running"] is True
        replica = state["replicas"][0]
        assert replica["kv_cache"]["num_blocks"] > 0
        assert "waiting" in replica["scheduler"]
        assert {"admit", "finish"} <= {e["kind"] for e in state["events"]}

        # last_events caps the tail the snapshot carries
        capped = _json.loads(
            stub.DumpState(
                debug_pb2.StateRequest(last_events=2)
            ).state_json
        )
        assert len(capped["events"]) <= 2


def test_debug_request_trace_rpc(grpc_client, _servers):
    import json as _json

    import grpc as _grpc

    from vllm_tgis_adapter_tpu.grpc.debug import DebugStub
    from vllm_tgis_adapter_tpu.grpc.pb import debug_pb2

    grpc_client.make_request("trace probe", max_new_tokens=3)
    with _grpc.insecure_channel(f"localhost:{_servers.grpc_port}") as ch:
        stub = DebugStub(ch)
        state = _json.loads(
            stub.DumpState(debug_pb2.StateRequest()).state_json
        )
        finished = [
            e["request_id"]
            for e in state["events"]
            if e["kind"] == "finish" and "request_id" in e
        ]
        assert finished
        resp = stub.GetRequestTrace(
            debug_pb2.RequestTraceRequest(request_id=finished[-1])
        )
        trace = _json.loads(resp.trace_json)
        assert trace["request_id"] == finished[-1]
        kinds = [e["kind"] for e in trace["events"]]
        # the cost ledger's closing event follows the finish (PR 16,
        # docs/OBSERVABILITY.md event table); the HTTP twin of this
        # test (test_http_server.py) reads the same grammar
        assert kinds[0] == "admit" and "finish" in kinds[-2:]
        assert kinds[-1] in ("finish", "ledger")

        with pytest.raises(_grpc.RpcError) as excinfo:
            stub.GetRequestTrace(
                debug_pb2.RequestTraceRequest(request_id="no-such-request")
            )
        assert excinfo.value.code() == _grpc.StatusCode.NOT_FOUND

        with pytest.raises(_grpc.RpcError) as excinfo:
            stub.GetRequestTrace(debug_pb2.RequestTraceRequest())
        assert excinfo.value.code() == _grpc.StatusCode.INVALID_ARGUMENT


def test_debug_timeline_rpc(grpc_client, _servers):
    """tgis_tpu.debug.v1.Debug/GetTimeline serves the same chrome-trace
    JSON as GET /debug/timeline (telemetry/timeline.py), so offline
    tooling can pull a Perfetto-loadable artifact over gRPC."""
    import json as _json

    import grpc as _grpc

    from vllm_tgis_adapter_tpu.grpc.debug import DebugStub
    from vllm_tgis_adapter_tpu.grpc.pb import debug_pb2

    grpc_client.make_request("timeline probe", max_new_tokens=3)
    with _grpc.insecure_channel(f"localhost:{_servers.grpc_port}") as ch:
        stub = DebugStub(ch)
        resp = stub.GetTimeline(debug_pb2.TimelineRequest(format="chrome"))
        trace = _json.loads(resp.timeline_json)
        events = trace["traceEvents"]
        assert any(e["ph"] == "M" for e in events)
        assert any(
            e["ph"] == "X" and e.get("cat") == "step" for e in events
        )

        # empty format defaults to chrome; last_steps bounds step rows
        bounded = _json.loads(
            stub.GetTimeline(
                debug_pb2.TimelineRequest(last_steps=1)
            ).timeline_json
        )
        steps = {
            e["args"]["step"]
            for e in bounded["traceEvents"]
            if e["ph"] == "X" and e.get("cat") == "step"
        }
        assert len(steps) <= 1

        with pytest.raises(_grpc.RpcError) as excinfo:
            stub.GetTimeline(debug_pb2.TimelineRequest(format="xml"))
        assert excinfo.value.code() == _grpc.StatusCode.INVALID_ARGUMENT
