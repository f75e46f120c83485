"""CLI argument system: engine flags + TGIS compatibility flags + env fallback.

Capability parity with the reference's ``tgis_utils/args.py``:

* every flag can be supplied via an environment variable named after it
  (``--grpc-port`` <-> ``GRPC_PORT``), including boolean actions, with the
  ``[env: NAME]`` annotation in ``--help`` (reference: args.py:30-98);
* the TGIS-legacy flag set (``--model-name``, ``--max-sequence-length``,
  ``--num-gpus``/``--num-shard``, ``--quantize``, TLS paths, speculator
  args, ...) is accepted and mapped onto the engine's native namespace with
  conflict errors (reference: args.py:101-258).

Where the reference wraps vLLM's ``make_arg_parser``, we define the engine
argument set ourselves (`add_engine_args`): the engine here is this package's
own JAX/TPU engine, and ``--tensor-parallel-size`` selects the size of the
SPMD mesh axis over ICI rather than a NCCL world size.
"""

from __future__ import annotations

import argparse
import os

from vllm_tgis_adapter_tpu.logging import init_logger

logger = init_logger(__name__)

MAX_TOP_N_TOKENS = 10  # shared limit, see grpc/validation.py


def _to_env_var(arg_name: str) -> str:
    return arg_name.upper().replace("-", "_")


def _bool_from_string(val: str) -> bool:
    return val.lower().strip() == "true" or val == "1"


class StoreBoolean(argparse.Action):
    """``--flag true|false`` style boolean action."""

    def __call__(self, parser, namespace, values, option_string=None):  # noqa: ANN001
        lowered = values.lower()
        if lowered not in ("true", "false"):
            raise ValueError(
                f"Invalid boolean value: {values}. Expected 'true' or 'false'."
            )
        setattr(namespace, self.dest, lowered == "true")


class FlexibleArgumentParser(argparse.ArgumentParser):
    """ArgumentParser accepting both ``--foo-bar`` and ``--foo_bar`` spellings."""

    def parse_args(self, args=None, namespace=None):  # noqa: ANN001
        import sys

        if args is None:
            args = sys.argv[1:]
        processed = []
        for arg in args:
            if arg.startswith("--") and "_" in arg:
                if "=" in arg:
                    key, _, value = arg.partition("=")
                    processed.append(f"{key.replace('_', '-')}={value}")
                else:
                    processed.append(arg.replace("_", "-"))
            else:
                processed.append(arg)
        return super().parse_args(processed, namespace)


_BOOLEAN_ACTIONS = (
    argparse._StoreTrueAction,  # noqa: SLF001
    argparse._StoreFalseAction,  # noqa: SLF001
    argparse.BooleanOptionalAction,
    StoreBoolean,
)


def _apply_env_fallback(action: argparse.Action) -> None:
    """Replace an action's default with the value of its env var, if set."""
    env_val = os.environ.get(_to_env_var(action.dest))
    if not env_val:
        return

    val: bool | str
    if action.type is bool or isinstance(action, _BOOLEAN_ACTIONS):
        # bool("false") == True, so parse the string ourselves
        val = _bool_from_string(env_val)
    else:
        # non-string types get converted by argparse when the default is used
        val = env_val

    if action.nargs in ("+", "*"):
        action.default = [val]
    else:
        action.default = val


class EnvVarArgumentParser(FlexibleArgumentParser):
    """Parser where every argument falls back to an env var of the same name."""

    class _EnvVarHelpFormatter(argparse.ArgumentDefaultsHelpFormatter):
        def _get_help_string(self, action: argparse.Action) -> str:
            help_ = super()._get_help_string(action)
            assert help_ is not None
            if action.dest != "help":
                help_ += f" [env: {_to_env_var(action.dest)}]"
            return help_

    def __init__(
        self,
        parser: argparse.ArgumentParser | None = None,
        *,
        formatter_class=_EnvVarHelpFormatter,
        **kwargs,
    ):
        parents = []
        if parser:
            parents.append(parser)
            for action in parser._actions:  # noqa: SLF001
                if isinstance(action, argparse._HelpAction):  # noqa: SLF001
                    continue
                _apply_env_fallback(action)
        super().__init__(
            formatter_class=formatter_class, parents=parents, add_help=False, **kwargs
        )

    def _add_action(self, action: argparse.Action) -> argparse.Action:
        _apply_env_fallback(action)
        return super()._add_action(action)


def add_engine_args(parser: argparse.ArgumentParser) -> argparse.ArgumentParser:
    """Add the TPU engine's native argument set.

    This is the analog of the vLLM engine arg surface the reference exposes
    through ``make_arg_parser`` (reference: __main__.py:118-120); names are
    kept compatible where the concept carries over so existing deployments'
    flags keep working.
    """
    g = parser.add_argument_group("model")
    g.add_argument("--model", type=str, default=None,
                   help="name or local path of the model to serve")
    g.add_argument("--tokenizer", type=str, default=None,
                   help="tokenizer path override; defaults to --model")
    g.add_argument("--served-model-name", type=str, nargs="*", default=None,
                   help="model name(s) reported by the APIs; defaults to --model")
    g.add_argument("--revision", type=str, default=None,
                   help="model revision: selects the HF-cache snapshot for "
                        "weights/config and the tokenizer revision")
    g.add_argument("--trust-remote-code", action="store_true",
                   help="allow custom code from the model repo when loading "
                        "tokenizer/config")
    g.add_argument("--dtype", type=str, default="auto",
                   choices=["auto", "bfloat16", "float16", "float32"],
                   help="activation/weight dtype; 'auto' picks bfloat16 on TPU")
    g.add_argument("--moe-dispatch", type=str, default="dense",
                   choices=["dense", "capacity"],
                   help="MoE expert dispatch: 'dense' runs every expert "
                        "on every token (exact); 'capacity' routes into "
                        "static per-expert buffers so FLOPs scale with "
                        "top-k (assignments past capacity are dropped)")
    g.add_argument("--moe-capacity-factor", type=float, default=1.25,
                   help="per-expert buffer headroom for --moe-dispatch "
                        "capacity: capacity = ceil(T*k/E * factor)")
    g.add_argument("--kv-cache-dtype", type=str, default="auto",
                   choices=["auto", "bfloat16", "float16", "float32",
                            "float8_e4m3", "fp8", "int8"],
                   help="KV-cache storage dtype.  Quantized spellings "
                        "(fp8/int8/float8_e4m3) are subsumed by "
                        "--kv-quantization: they serve the scaled "
                        "quantized-page path, never a raw cast "
                        "(docs/QUANTIZATION.md)")
    g.add_argument("--kv-quantization", type=str, default="none",
                   choices=["none", "int8", "fp8"],
                   help="store KV pages quantized with per-page-per-"
                        "head scales, dequantized inside the ragged "
                        "attention kernel — ~2x KV capacity at equal "
                        "HBM, quality-gated per scenario "
                        "(docs/QUANTIZATION.md).  'none' (default) is "
                        "byte-identical to the unquantized engine")
    g.add_argument("--quantization", type=str, default=None,
                   choices=["int8", "awq", "gptq", "squeezellm"],
                   help="weight quantization scheme: int8 is native "
                        "(weight-only, per-channel, quantized on load); "
                        "awq/gptq int4 checkpoints dequantize group-wise "
                        "at load (the checkpoint's quantization_config is "
                        "authoritative — the flag just validates it); "
                        "squeezellm is accepted for CLI compat but "
                        "rejected at engine boot")
    g.add_argument("--max-model-len", type=int, default=None,
                   help="model context length; derived from the model config "
                        "if unset")
    g.add_argument("--seed", type=int, default=0, help="engine-level RNG seed")
    g.add_argument("--max-logprobs", type=int, default=20,
                   help="max number of logprobs returnable per position")

    g = parser.add_argument_group("engine")
    g.add_argument("--max-num-seqs", type=int, default=64,
                   help="max sequences resident in the decode batch")
    g.add_argument("--max-num-batched-tokens", type=int, default=None,
                   help="cap on tokens processed per engine step (prefill "
                        "chunking budget)")
    g.add_argument("--num-scheduler-steps", type=int, default=8,
                   help="decode steps fused into one device dispatch "
                        "(tokens sampled per sequence between host "
                        "round-trips); 1 disables multi-step decode")
    g.add_argument("--block-size", type=int, default=None,
                   help="KV-cache page size in tokens; unset, 16, and "
                        "128 (one run of the attention kernel's work "
                        "item) for a model that keeps its window layers' "
                        "and its full layers' pages in two groups "
                        "(docs/ATTENTION.md)")
    g.add_argument("--attention-backend", type=str, default="ragged",
                   choices=["bucketed", "ragged"],
                   help="serving data path (docs/ATTENTION.md): "
                        "'ragged' (default, the only backend) merges "
                        "mixed prefill+decode token streams — "
                        "speculative verify spans included — into one "
                        "ragged-paged-attention dispatch with a single "
                        "flat-length bucket and no per-prompt padding; "
                        "'bucketed' is RETIRED and fails boot with a "
                        "migration pointer")
    g.add_argument("--hbm-memory-utilization", "--gpu-memory-utilization",
                   dest="hbm_memory_utilization", type=float, default=0.90,
                   help="fraction of device memory budgeted for weights + KV "
                        "cache (accepts --gpu-memory-utilization for "
                        "compatibility)")
    g.add_argument("--swap-space", type=float, default=0,
                   help="GiB of host memory for preempted sequences' KV: "
                        "a preempted decode's pages swap to host and "
                        "restore on re-admission instead of recomputing "
                        "the whole prefill (0 = recompute only)")
    g.add_argument("--kv-host-cache-gb", type=float, default=4.0,
                   help="GiB of host RAM for the tiered KV store "
                        "(docs/KV_TIERING.md): full prompt pages demote "
                        "to a hash-addressed host cache when they are "
                        "registered or evicted, and prefix misses the "
                        "tier can cover promote back asynchronously — "
                        "cross-request AND cross-restart prefix reuse "
                        "beyond HBM.  The served default is on; library "
                        "constructions default off")
    g.add_argument("--no-kv-host-cache", action="store_true",
                   help="disable the host KV tier entirely "
                        "(pre-tier engine behavior, byte-identical; "
                        "also disables the disk tier beneath it)")
    g.add_argument("--kv-disk-cache-gb", type=float, default=0.0,
                   help="GiB of local disk beneath the host KV tier "
                        "(docs/MEMORY.md): host-tier LRU victims — "
                        "cold KV prefix pages and cold adapters "
                        "spilled from the host registry — land in "
                        "mmap-read, checksum-validated files and "
                        "promote disk-to-host-to-device through the "
                        "existing park/promote gates (0 = off; "
                        "requires --kv-host-cache-gb > 0)")
    g.add_argument("--kv-disk-cache-dir", type=str, default=None,
                   help="directory for disk-tier entries (default: a "
                        "stable path under the system tempdir); "
                        "content-addressed and validated on read, so "
                        "it may survive restarts for cross-restart "
                        "reuse")
    g.add_argument("--kvnet-listen", type=str, default=None,
                   help="host:port for the networked KV tier's RPC "
                        "service (docs/CROSS_HOST.md): cross-host "
                        "prefix sharing, remote handoffs, and "
                        "machine-loss resume over the disk-entry "
                        "wire format (default: kvnet off; port 0 "
                        "binds an ephemeral port)")
    g.add_argument("--kvnet-peers", type=str, default=None,
                   help="comma-separated host:port addresses of the "
                        "other kvnet hosts; each peer's digest "
                        "mirror extends prefix coverage fleet-wide "
                        "and can accept cross-host handoffs")
    g.add_argument("--kvnet-node-id", type=str, default=None,
                   help="stable node identity in kvnet peer HELLOs "
                        "(machine-loss adoption keys staged handoffs "
                        "by it; default: derived from --kvnet-listen)")
    g.add_argument("--kvnet-timeout", type=float, default=5.0,
                   help="per-request deadline against a kvnet peer, "
                        "seconds; bounded retry with backoff inside "
                        "it, then graceful degradation to the local "
                        "tiers")
    g.add_argument("--unified-arena",
                   action=argparse.BooleanOptionalAction, default=True,
                   help="one paged HBM arena for KV pages + adapter "
                        "shards (docs/MEMORY.md): unified LRU + "
                        "pinning over a single block budget, adapter "
                        "residency charged at TRUE rank; "
                        "--no-unified-arena restores separately-"
                        "budgeted pools")
    g.add_argument("--enforce-eager", action="store_true",
                   help="accepted for compatibility; the TPU engine always "
                        "compiles with XLA")
    g.add_argument("--disable-log-stats", action="store_true",
                   help="disable periodic engine stats logging")
    g.add_argument("--enable-prefix-caching", action="store_true",
                   help="content-addressed reuse of full prompt KV pages "
                        "across requests sharing a prefix (matched pages "
                        "skip prefill; engine/kv_cache.py)")
    g.add_argument("--precompile", type=str, default=None,
                   choices=["all", "max"],
                   help="warm every serving shape at boot (TPU compiles "
                        "run 20-40s; the compilation cache persists them "
                        "across restarts): 'all' compiles every decode "
                        "batch-width bucket x prefill bucket, 'max' only "
                        "the widest batch (faster boot, fill-in compiles "
                        "as load ramps)")

    g = parser.add_argument_group("parallelism")
    g.add_argument("--tensor-parallel-size", "-tp", type=int, default=None,
                   help="SPMD tensor-parallel mesh size over ICI")
    g.add_argument("--sequence-parallel-size", "-sp", type=int, default=1,
                   help="sequence-parallel mesh axis for long-context "
                        "prefill (total chips = sp * tp)")
    g.add_argument("--sequence-parallel-mode", type=str, default="ring",
                   choices=["ring", "ulysses"],
                   help="sp>1 attention style: 'ring' rotates K/V chunks "
                        "via ppermute; 'ulysses' all-to-alls to full-"
                        "sequence head slices (sp must divide the per-tp "
                        "head counts)")
    g.add_argument("--pipeline-parallel-size", "-pp", type=int, default=1,
                   help="pipeline stages across the mesh")
    g.add_argument("--data-parallel-size", "-dp", type=int, default=1,
                   help="in-process engine replicas, each owning a "
                        "disjoint sp*tp device slice with its own "
                        "scheduler and KV pool; the front door's "
                        "placement router scores replicas by prefix/"
                        "tenant affinity and load (total chips = "
                        "dp*sp*tp)")
    g.add_argument("--dp-replicas", type=int, default=1,
                   help="replica count like --data-parallel-size, but "
                        "tolerant of hosts with fewer than N*pp*sp*tp "
                        "devices: replicas then share the visible "
                        "device set (CPU-proxy / dev mode; each still "
                        "owns its own scheduler, KV pool, and step "
                        "loop).  docs/SCALING.md; mutually exclusive "
                        "with --data-parallel-size > 1")
    g.add_argument("--replica-role", type=str, default="mixed",
                   choices=("prefill", "decode", "mixed"),
                   help="prefill/decode disaggregation (docs/SCALING.md "
                        "'Disaggregated roles'): the role every replica "
                        "serves when --dp-replica-roles is not given.  "
                        "'prefill' replicas run full-bucket prefill and "
                        "hand finished prompts to decode-capable "
                        "replicas through the host KV tier; 'decode' "
                        "replicas admit those handoffs and run decode; "
                        "'mixed' (default) is the pre-disaggregation "
                        "behavior.  Non-mixed roles require the KV tier "
                        "and at least one prefill-capable AND one "
                        "decode-capable replica (validated at boot)")
    g.add_argument("--dp-replica-roles", type=str, default=None,
                   help="comma-separated per-replica role list, e.g. "
                        "'prefill,decode,decode,mixed' — length must "
                        "equal the replica count; overrides "
                        "--replica-role")

    g = parser.add_argument_group("front door (admission control)")
    g.add_argument("--max-waiting-requests", type=int, default=0,
                   help="bound on requests waiting for admission "
                        "(front-door queue + engine waiting queues); "
                        "past it new requests shed with "
                        "RESOURCE_EXHAUSTED/429 + Retry-After instead "
                        "of queuing unboundedly (0 = unbounded)")
    g.add_argument("--admission-deadline", type=float, default=0.0,
                   help="shed a new request when the estimated "
                        "queue-drain time (observed token throughput, "
                        "seeded from KV-pool capacity) already exceeds "
                        "this many seconds (0 disables)")
    g.add_argument("--queue-ttl", type=float, default=0.0,
                   help="early-abort requests still waiting for "
                        "prefill this many seconds after arrival; "
                        "request-level deadlines (time_limit_millis) "
                        "tighten it per request (0 disables)")
    g.add_argument("--drain-grace", type=float, default=30.0,
                   help="on SIGTERM, seconds in-flight generations may "
                        "finish before the process exits anyway "
                        "(health flips to DRAINING/503 immediately)")
    g.add_argument("--tenant-weights", type=str, default=None,
                   help="weighted-fair-queue tenant weights as "
                        "name=weight[,name=weight...]; unlisted "
                        "tenants weigh 1.0")
    g.add_argument("--tenant-rate-limit", type=float, default=0.0,
                   help="per-tenant sustained token budget "
                        "(tokens/second, prompt + max new tokens) "
                        "enforced by a token bucket; 0 disables")
    g.add_argument("--tenant-burst", type=float, default=0.0,
                   help="per-tenant token-bucket burst capacity; 0 "
                        "defaults to 10s of --tenant-rate-limit")
    g.add_argument("--tenant-header", type=str, default="x-tenant-id",
                   help="HTTP header / gRPC metadata key carrying the "
                        "tenant id for fair queuing and rate limits "
                        "(falls back to the adapter id, then "
                        "'default')")
    g.add_argument("--disable-frontdoor", action="store_true",
                   help="bypass the front door entirely: unbounded "
                        "FIFO hand-off straight to the scheduler "
                        "(pre-PR4 behavior; escape hatch)")

    g = parser.add_argument_group("lora")
    g.add_argument("--enable-lora", action="store_true",
                   help="enable LoRA adapter support")
    g.add_argument("--max-loras", type=int, default=4,
                   help="max distinct LoRA adapters resident per batch")
    g.add_argument("--max-lora-rank", type=int, default=64,
                   help="max supported LoRA rank")
    g.add_argument("--lora-modules", type=str, nargs="*", default=None,
                   help="static LoRA modules to register: name=path ...")
    g.add_argument("--max-cpu-loras", type=int, default=0,
                   help="host-RAM adapter registry capacity for the "
                        "paged pool (0 = auto: max(64, 4*max-loras)); "
                        "device residency stays bounded by --max-loras")
    g.add_argument("--lora-pool", action=argparse.BooleanOptionalAction,
                   default=True,
                   help="paged device adapter pool with async "
                        "host-to-device streaming (docs/LORA.md); "
                        "--no-lora-pool restores the legacy full-stack "
                        "rebuild slow path")
    g.add_argument("--lora-prefetch-concurrency", type=int, default=2,
                   help="concurrent host-to-device adapter streams per "
                        "replica pool")
    g.add_argument("--lora-gathered",
                   action=argparse.BooleanOptionalAction, default=True,
                   help="heterogeneous-rank gathered LoRA matmul "
                        "(docs/LORA.md): each row computes its delta "
                        "at its adapter's TRUE pow2 rank bucket "
                        "instead of padding to --max-lora-rank; "
                        "--no-lora-gathered restores the padded "
                        "matmuls")

    g = parser.add_argument_group("speculative decoding")
    g.add_argument("--speculative-model", type=str, default=None,
                   help="draft model for speculative decoding")
    g.add_argument("--num-speculative-tokens", type=int, default=None,
                   help="tokens proposed per speculation round")
    g.add_argument("--use-v2-block-manager", action="store_true",
                   help="accepted for compatibility; this engine has a single "
                        "block manager")

    g = parser.add_argument_group("http server")
    g.add_argument("--host", type=str, default=None, help="bind address")
    g.add_argument("--port", type=int, default=8000, help="HTTP port")
    g.add_argument("--uvicorn-log-level", type=str, default="info",
                   choices=["debug", "info", "warning", "error", "critical",
                            "trace"],
                   help="HTTP server log level (flag name kept for "
                        "compatibility)")
    g.add_argument("--ssl-keyfile", type=str, default=None)
    g.add_argument("--ssl-certfile", type=str, default=None)
    g.add_argument("--ssl-ca-certs", type=str, default=None)
    g.add_argument("--ssl-cert-reqs", type=int, default=None,
                   choices=[0, 1, 2],
                   help="ssl.CERT_* constant for client cert verification "
                        "(0 never, 1 optional, 2 required); default: "
                        "required exactly when --ssl-ca-certs is given")
    g.add_argument("--root-path", type=str, default=None,
                   help="HTTP root path prefix when behind a proxy")
    g.add_argument("--api-key", type=str, default=None,
                   help="require this bearer token on the HTTP API")

    g = parser.add_argument_group("observability")
    g.add_argument("--otlp-traces-endpoint", type=str, default=None,
                   help="OTLP endpoint; enables trace-context propagation")
    g.add_argument("--slo-config", type=str, default=None,
                   help="per-class SLO objectives (telemetry/slo.py): "
                        "inline JSON object or a path to one, keyed by "
                        "request class (chat|rag|batch) with "
                        "ttft_p99_s / itl_p99_s / availability; unset "
                        "uses built-in defaults")
    g.add_argument("--ledger-log", type=str, default=None,
                   help="JSONL sink for closed request cost-ledger "
                        "records (telemetry/ledger.py): one line per "
                        "terminal request with wall-time splits, token "
                        "counts, KV page-seconds, tier bytes, and "
                        "recovery counts")
    g.add_argument("--jax-profiler-port", type=int, default=None,
                   help="start a jax.profiler server on this port "
                        "(connect with TensorBoard/XProf to capture "
                        "device traces)")
    g.add_argument("--profile-dir", type=str, default=None,
                   help="enable on-demand jax.profiler captures written "
                        "to this directory: POST /start_profile and "
                        "/stop_profile on the HTTP server (and the gRPC "
                        "debug service) bracket a capture; view with "
                        "TensorBoard/XProf")
    g.add_argument("--disable-log-requests", action="store_true",
                   help="disable engine-level per-request logs")
    g.add_argument("--dump-dir", type=str, default=None,
                   help="directory for stall-watchdog diagnostic "
                        "snapshots (one timestamped JSON file per "
                        "detected step-loop stall); unset keeps dumps "
                        "in the log and termination log only")
    g.add_argument("--watchdog-deadline", type=float, default=120.0,
                   help="seconds the engine step loop may go without a "
                        "heartbeat (while work is in flight) before the "
                        "stall watchdog dumps engine state; suspended "
                        "during in-flight XLA/Mosaic compiles; 0 "
                        "disables the watchdog")

    g = parser.add_argument_group("self-healing (docs/RECOVERY.md)")
    g.add_argument("--max-engine-restarts", type=int, default=3,
                   help="supervised engine restarts allowed within "
                        "--engine-restart-window before the crash-loop "
                        "circuit breaker escalates to clean process "
                        "death (restart history lands in the "
                        "termination log); 0 disables supervision "
                        "entirely — any engine death kills the process "
                        "(pre-restart behavior)")
    g.add_argument("--engine-restart-window", type=float, default=300.0,
                   help="sliding window (seconds) the crash-loop "
                        "circuit breaker counts restarts over")
    g.add_argument("--engine-restart-backoff", type=float, default=0.5,
                   help="base of the exponential backoff between "
                        "restart attempts (base * 2^(n-1), capped at "
                        "30s)")
    g.add_argument("--no-decode-resume", action="store_true",
                   help="disable mid-decode checkpoint/resume at "
                        "supervised restart: mid-decode requests fail "
                        "retryable (UNAVAILABLE + Retry-After) instead "
                        "of checkpointing into the host KV tier and "
                        "resuming token-identically (docs/RECOVERY.md; "
                        "resume is on by default whenever supervision "
                        "and --kv-host-cache-gb are both active)")
    g.add_argument("--watchdog-action", type=str, default="snapshot",
                   choices=["snapshot", "restart"],
                   help="what a watchdog-declared stall triggers: "
                        "'snapshot' diagnoses only (default); "
                        "'restart' additionally hands the stalled "
                        "engine to the supervisor — the diagnostic "
                        "snapshot is still written first")
    g.add_argument("--failpoints", type=str,
                   default=os.getenv("TGIS_TPU_FAILPOINTS"),
                   help="DELIBERATE fault injection for chaos testing "
                        "(never in production): comma-separated "
                        "site=action[:count] entries, e.g. "
                        "'core.plan_step=raise:1,core.wait_step=oom'; "
                        "actions: raise, oom, hang; also read from "
                        "TGIS_TPU_FAILPOINTS "
                        "(supervisor/failpoints.py)")

    return parser


def add_tgis_args(parser: argparse.ArgumentParser) -> argparse.ArgumentParser:
    """Add TGIS-compatibility flags (reference: args.py:101-181)."""
    # maps to model
    parser.add_argument("--model-name", type=str,
                        help="name or path of the huggingface model to use")
    # maps to max_model_len
    parser.add_argument("--max-sequence-length", type=int,
                        help="model context length. If unspecified, will be "
                             "automatically derived from the model.")
    parser.add_argument("--max-new-tokens", type=int, default=1024,
                        help="maximum allowed new (generated) tokens per "
                             "request")
    # maps to max_num_seqs (advisory)
    parser.add_argument("--max-batch-size", type=int)
    # legacy arg no longer supported
    parser.add_argument("--max-concurrent-requests", type=int)
    # maps to dtype
    parser.add_argument("--dtype-str", type=str, help="deprecated, use dtype")
    # maps to quantization
    parser.add_argument("--quantize", type=str,
                        choices=["awq", "gptq", "squeezellm", None],
                        help="method used to quantize the weights")
    # both map to tensor_parallel_size (mesh size over ICI)
    parser.add_argument("--num-gpus", type=int)
    parser.add_argument("--num-shard", type=int)
    parser.add_argument("--output-special-tokens", type=_bool_from_string,
                        default=False)
    parser.add_argument("--default-include-stop-seqs", type=_bool_from_string,
                        default=True)
    parser.add_argument("--grpc-port", type=int, default=8033)
    # map to ssl_certfile / ssl_keyfile / ssl_ca_certs
    parser.add_argument("--tls-cert-path", type=str)
    parser.add_argument("--tls-key-path", type=str)
    parser.add_argument("--tls-client-ca-cert-path", type=str)
    # path PEFT adapters are loaded from
    parser.add_argument("--adapter-cache", type=str)
    # backwards-compatibility support for tgis prompt tuning
    parser.add_argument("--prefix-store-path", type=str,
                        help="deprecated, use --adapter-cache")
    # spec decode
    parser.add_argument("--speculator-name", type=str)
    parser.add_argument("--speculator-n-candidates", type=int)
    parser.add_argument("--speculator-max-batch-size", type=int)
    # re-enable engine-native per-request logging
    parser.add_argument("--enable-vllm-log-requests", type=_bool_from_string,
                        default=False)
    parser.add_argument("--disable-prompt-logprobs", type=_bool_from_string,
                        default=False)
    return parser


def postprocess_tgis_args(args: argparse.Namespace) -> argparse.Namespace:  # noqa: C901, PLR0912
    """Resolve TGIS-legacy flags onto the engine namespace.

    Same mapping and conflict semantics as the reference
    (args.py:184-258); raises ValueError on inconsistent values.
    """
    if args.model_name:
        args.model = args.model_name
    if args.max_sequence_length is not None:
        if args.max_model_len not in (None, args.max_sequence_length):
            raise ValueError(
                "Inconsistent max_model_len and max_sequence_length arg values"
            )
        args.max_model_len = args.max_sequence_length
    if args.dtype_str is not None:
        if args.dtype not in (None, "auto", args.dtype_str):
            raise ValueError("Inconsistent dtype and dtype_str arg values")
        args.dtype = args.dtype_str
    if args.quantize:
        if args.quantization and args.quantization != args.quantize:
            raise ValueError("Inconsistent quantize and quantization arg values")
        args.quantization = args.quantize
    if args.num_gpus is not None or args.num_shard is not None:
        if (
            args.num_gpus is not None
            and args.num_shard is not None
            and args.num_gpus != args.num_shard
        ):
            raise ValueError("Inconsistent num_gpus and num_shard arg values")
        num_chips = args.num_gpus if args.num_gpus is not None else args.num_shard
        if args.tensor_parallel_size not in [None, 1, num_chips]:
            raise ValueError(
                "Inconsistent tensor_parallel_size and num_gpus/num_shard arg values"
            )
        args.tensor_parallel_size = num_chips
    if args.max_logprobs < MAX_TOP_N_TOKENS + 1:
        logger.info("Setting max_logprobs to %d", MAX_TOP_N_TOKENS + 1)
        args.max_logprobs = MAX_TOP_N_TOKENS + 1

    # The TGIS-style wrapper logs every request; keep the engine quiet unless
    # explicitly re-enabled.
    args.disable_log_requests = not args.enable_vllm_log_requests

    if args.speculator_name:
        if args.speculative_model and args.speculative_model != args.speculator_name:
            raise ValueError(
                "Inconsistent speculator_name and speculative_model arg values"
            )
        args.speculative_model = args.speculator_name

    if args.speculator_n_candidates or args.speculator_max_batch_size:
        logger.warning(
            "speculator_n_candidates and speculator_max_batch_size args are "
            "not yet supported"
        )

    if args.max_batch_size is not None:
        logger.warning(
            "max_batch_size is set to %d but will be ignored for now. "
            "max_num_seqs can be used if this is still needed.",
            args.max_batch_size,
        )
    if args.max_concurrent_requests is not None:
        logger.warning(
            "max_concurrent_requests is not supported and will be ignored."
        )

    if args.tls_cert_path:
        args.ssl_certfile = args.tls_cert_path
    if args.tls_key_path:
        args.ssl_keyfile = args.tls_key_path
    if args.tls_client_ca_cert_path:
        args.ssl_ca_certs = args.tls_client_ca_cert_path

    return args


def make_parser() -> EnvVarArgumentParser:
    """Build the complete CLI parser used by ``python -m vllm_tgis_adapter_tpu``."""
    base = FlexibleArgumentParser(
        description="TPU-native TGIS gRPC + OpenAI REST api server"
    )
    base = add_engine_args(base)
    parser = EnvVarArgumentParser(parser=base)
    return add_tgis_args(parser)
