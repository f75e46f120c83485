"""Perf regression gate: ``nox -s perf_check`` (ROADMAP item 5, minimal core).

Runs the closed-loop mini-bench (bench.py machinery, CPU proxy,
BENCH_TINY-sized) once per serving data path and diffs the results
against the checked-in ``PERF_BASELINE.json``:

* ``aggregate_output_tok_per_s`` — fails on > ``tolerance`` (default
  20%) regression against the baseline, using the BEST of ``runs``
  short passes per backend to damp scheduler/load jitter (the r05
  lesson: a single 0.5s timed pass swings 3x run-to-run, which is how
  the 1847 → 466 CPU-run drop went unattributed for a round);
* ``padding_waste_frac`` — fails when the padding fraction grows more
  than ``waste_slack`` absolute over the baseline (the ragged backend's
  whole claim is waste ≈ 0; a silent return of bucket padding is a
  regression even if tok/s survives);
* speculative decoding (docs/ATTENTION.md "Speculative decoding"): the
  decode-heavy chat scenario under concurrent RAG prefill load run with
  and without a same-weights draft — spec chat ITL p50 must beat plain
  ragged by ≥ ``spec.min_itl_speedup`` (ISSUE 12 acceptance: 1.5×) at
  acceptance ≥ ``spec.min_acceptance`` with identical greedy outputs;
* dp scaling (docs/SCALING.md): aggregate tok/s across the baseline's
  ``dp.points`` replica counts (ragged backend, BENCH_ARCH=small +
  BENCH_SYNC_DISPATCH=1 — see bench.py's docstring for why the dp gate
  needs both), gated on absolute floors AND the dp=N / dp=1 scaling
  ratios in ``dp.min_scaling`` (ISSUE 7 acceptance: dp=2 ≥ 1.6x,
  dp=4 ≥ 2.8x).  Ratio gates are robust to shared-runner load jitter
  (both points see the same load); the floors catch a uniformly slow
  fleet.

* prefill/decode disaggregation (docs/SCALING.md "Disaggregated
  roles"): the BENCH_ROLES chat+RAG scenario run disaggregated AND
  all-mixed at equal replica count — disaggregated chat ITL p99 must
  stay ≤ ``disagg.max_itl_ratio`` (default 0.5×, i.e. ≥ 2× better) of
  the mixed fleet's, handoff streams token-identical (outputs digest),
  every handoff taken with zero fallbacks.

Exit codes follow obs_check: 0 green, 1 regression, 2 tool error.
Update the baseline deliberately with ``--write`` after a reviewed
perf-relevant change; the JSON records the config knobs it was
measured under.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parent.parent
BASELINE_PATH = REPO_ROOT / "PERF_BASELINE.json"


def run_bench(backend: str, env_overrides: dict) -> dict:
    env = dict(os.environ)
    env.update(env_overrides)
    env["JAX_PLATFORMS"] = "cpu"
    env["BENCH_FORCE_CPU"] = "1"
    env["BENCH_TINY"] = "1"
    env["BENCH_ATTENTION_BACKEND"] = backend
    proc = subprocess.run(
        [sys.executable, str(REPO_ROOT / "bench.py")],
        capture_output=True, text=True, timeout=600, env=env,
    )
    line = None
    for candidate in reversed(proc.stdout.strip().splitlines()):
        try:
            parsed = json.loads(candidate)
        except ValueError:
            continue
        if isinstance(parsed, dict):
            line = parsed
            break
    if proc.returncode != 0 or line is None:
        raise RuntimeError(
            f"bench.py ({backend}) failed rc={proc.returncode}: "
            f"{proc.stderr[-400:]}"
        )
    if "error" in line:
        raise RuntimeError(f"bench.py ({backend}) errored: {line['error']}")
    return line


def measure(backend: str, runs: int, env_overrides: dict) -> dict:
    best = None
    for _ in range(runs):
        line = run_bench(backend, env_overrides)
        if best is None or line["value"] > best["value"]:
            best = line
    return {
        "aggregate_output_tok_per_s": best["value"],
        "padding_waste_frac": best["padding_waste_frac"],
        "compiled_shapes": best["compiled_shapes"],
        "weight_resident_bytes": best.get("weight_resident_bytes"),
    }


def measure_dp(dp_cfg: dict, runs: int) -> dict[str, dict]:
    """Best-of-``runs`` bench line per replica count in ``dp_cfg``."""
    backend = dp_cfg.get("backend", "ragged")
    results: dict[str, dict] = {}
    for point in dp_cfg.get("points", []):
        env = dict(dp_cfg.get("env", {}))
        env["BENCH_DP"] = str(point)
        best = None
        for _ in range(runs):
            line = run_bench(backend, env)
            if best is None or line["value"] > best["value"]:
                best = line
        results[str(point)] = best
        print(
            f"perf_check: dp={point}     "
            f"tok/s={best['value']:8.1f} "
            f"per_replica={best.get('per_replica_committed_tok_per_s')} "
            f"affinity_hits={best.get('placement_affinity_hit_rate')}"
        )
    return results


def measure_lora(lora_cfg: dict, runs: int) -> tuple[dict, dict]:
    """Best-of-``runs`` adapter-churn line + single-adapter line
    (docs/LORA.md; the churn line is the acceptance demo: 128
    registered / 16 resident / churning tail).  Best = lowest ITL p50
    — the gate is a latency ratio, so 'best' must mean least load
    noise on BOTH sides."""
    backend = lora_cfg.get("backend", "ragged")

    def best_of(env: dict) -> dict:
        best = None
        for _ in range(runs):
            line = run_bench(backend, dict(env))
            itl = line.get("itl_ms_p50")
            if itl is None:
                raise RuntimeError("bench emitted no itl_ms_p50")
            if best is None or itl < best["itl_ms_p50"]:
                best = line
        return best

    churn = best_of(lora_cfg.get("env", {}))
    single = best_of(lora_cfg.get("single_env", {}))
    print(
        f"perf_check: lora     churn itl_p50={churn['itl_ms_p50']}ms "
        f"single itl_p50={single['itl_ms_p50']}ms "
        f"resident_hw={churn.get('lora_resident_high_water')} "
        f"swaps_in={churn.get('lora_swaps_in')} "
        f"registered={churn.get('lora_adapters')}"
    )
    return churn, single


def measure_kv_tier(kv_cfg: dict, runs: int) -> dict:
    """Best-of-``runs`` prefix-reuse line (docs/KV_TIERING.md; the
    acceptance demo: device pool capped below the reusable working set,
    warm pass served through the host tier).  Best = lowest warm/cold
    TTFT ratio — the gate is a latency ratio, so 'best' must mean the
    least load-noise-polluted run."""
    backend = kv_cfg.get("backend", "ragged")
    best = None
    for _ in range(runs):
        line = run_bench(backend, dict(kv_cfg.get("env", {})))
        kv = line.get("kv_tier")
        if kv is None or kv.get("warm_cold_ttft_ratio") is None:
            raise RuntimeError("bench emitted no kv_tier stamps")
        if (
            best is None
            or kv["warm_cold_ttft_ratio"]
            < best["kv_tier"]["warm_cold_ttft_ratio"]
        ):
            best = line
    kv = best["kv_tier"]
    print(
        f"perf_check: kv_tier  warm/cold ttft "
        f"{kv['ttft_warm_ms_p50']}/{kv['ttft_cold_ms_p50']}ms "
        f"(ratio {kv['warm_cold_ttft_ratio']}) "
        f"hit_rate={kv['combined_hit_rate']} "
        f"host_tokens={kv['host_promoted_tokens']} "
        f"identical={kv['token_identical']}"
    )
    return best


def measure_disagg(dis_cfg: dict, runs: int) -> tuple[dict, dict]:
    """ISSUE 11 gate driver: the BENCH_ROLES chat+RAG scenario run
    twice — a disaggregated (prefill+decode) fleet and an all-mixed
    fleet at EQUAL replica count (docs/SCALING.md "Disaggregated
    roles").  Best of ``runs`` per mode = lowest chat ITL p99: the
    gate is a latency ratio, so 'best' must mean the least
    load-noise-polluted run on BOTH sides."""
    backend = dis_cfg.get("backend", "ragged")

    def best_of(mode: str) -> dict:
        best = None
        for _ in range(runs):
            env = dict(dis_cfg.get("env", {}))
            env["BENCH_ROLES"] = mode
            line = run_bench(backend, env)
            roles = line.get("roles")
            if not roles or roles.get("chat_itl_ms_p99") is None:
                raise RuntimeError(
                    f"bench ({mode}) emitted no roles stamps"
                )
            if (
                best is None
                or roles["chat_itl_ms_p99"]
                < best["roles"]["chat_itl_ms_p99"]
            ):
                best = line
        return best

    disagg = best_of("disagg")
    mixed = best_of("mixed")
    d, m = disagg["roles"], mixed["roles"]
    print(
        f"perf_check: disagg   chat itl_p99 {d['chat_itl_ms_p99']}ms "
        f"vs mixed {m['chat_itl_ms_p99']}ms at dp={d['dp']} "
        f"(handoffs {d['handoffs_completed']}/"
        f"{d['handoffs_fallback']} fallback) "
        f"identical={d['outputs_digest'] == m['outputs_digest']}"
    )
    return disagg, mixed


def measure_spec(spec_cfg: dict, runs: int) -> tuple[dict, dict]:
    """ISSUE 12 gate driver: the decode-heavy chat scenario under
    concurrent RAG prefill load (the BENCH_ROLES=mixed chat+RAG fleet),
    run with BENCH_SPEC=1 (same-weights draft — ragged verify spans)
    and BENCH_SPEC=0.  Best of ``runs`` per mode = lowest chat ITL p50:
    a latency-ratio gate, so 'best' must mean the least
    load-noise-polluted run on BOTH sides."""
    backend = spec_cfg.get("backend", "ragged")

    def best_of(spec_on: bool) -> dict:
        best = None
        for _ in range(runs):
            env = dict(spec_cfg.get("env", {}))
            env["BENCH_SPEC"] = "1" if spec_on else "0"
            env["BENCH_SPEC_GAMMA"] = str(spec_cfg.get("gamma", 4))
            line = run_bench(backend, env)
            roles = line.get("roles")
            if not roles or roles.get("chat_itl_ms_p50") is None:
                raise RuntimeError(
                    f"bench (spec={spec_on}) emitted no chat ITL stamps"
                )
            if (
                best is None
                or roles["chat_itl_ms_p50"]
                < best["roles"]["chat_itl_ms_p50"]
            ):
                best = line
        return best

    spec = best_of(True)
    plain = best_of(False)
    s = spec["roles"]
    print(
        f"perf_check: spec     chat itl_p50 {s['chat_itl_ms_p50']}ms vs "
        f"plain {plain['roles']['chat_itl_ms_p50']}ms "
        f"(acceptance {spec['spec']['acceptance_rate']}, "
        f"verify dispatches {spec['spec']['verify_dispatches']}) "
        f"identical="
        f"{s['outputs_digest'] == plain['roles']['outputs_digest']}"
    )
    return spec, plain


def measure_recovery(rec_cfg: dict, runs: int) -> dict:
    """ISSUE 10 gate driver: ``tools/chaos_soak.py --recovery-bench``
    in a subprocess (own engines, shared persistent XLA cache — see
    its docstring for the cold-vs-cold measurement discipline).  Best
    of ``runs`` = lowest ratio: a latency-ratio gate, so 'best' must
    mean the least load-noise-polluted run."""
    best = None
    for _ in range(max(1, runs)):
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        proc = subprocess.run(
            [
                sys.executable,
                str(REPO_ROOT / "tools" / "chaos_soak.py"),
                "--recovery-bench",
            ],
            capture_output=True, text=True, timeout=600, env=env,
        )
        line = None
        for candidate in reversed(proc.stdout.strip().splitlines()):
            try:
                parsed = json.loads(candidate)
            except ValueError:
                continue
            if isinstance(parsed, dict) and parsed.get("kind") == "recovery":
                line = parsed
                break
        if proc.returncode != 0 or line is None:
            raise RuntimeError(
                f"chaos_soak --recovery-bench failed "
                f"rc={proc.returncode}: {proc.stderr[-400:]}"
            )
        if best is None or line["ratio"] < best["ratio"]:
            best = line
    print(
        f"perf_check: recovery  resumed {best['resumed_s']}s vs "
        f"uncrashed {best['base_s']}s (ratio {best['ratio']}) "
        f"identical={best['token_identical']} resumed={best['resumed']}"
    )
    return best


def measure_quant(q_cfg: dict, runs: int) -> tuple[dict, dict | None]:
    """ISSUE 13 gate driver (docs/QUANTIZATION.md): the steady-state
    scenario suites (tools/scenarios.py --quant-gate) run bf16-KV vs
    --kv-quantization at an EQUAL synthetic HBM budget — per-scenario
    tok/s + logprob deltas + the analytic page-capacity ratio — plus
    the weight-only BENCH_QUANTIZATION bench line.  Best of ``runs`` =
    highest chat tok/s ratio (a ratio gate; the quality deltas are
    near-deterministic, so the same run serves them)."""
    scheme = q_cfg.get("scheme", "int8")
    best = None
    for _ in range(max(1, runs)):
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        proc = subprocess.run(
            [
                sys.executable,
                str(REPO_ROOT / "tools" / "scenarios.py"),
                "--quant-gate", "--scheme", scheme,
            ],
            capture_output=True, text=True, timeout=900, env=env,
        )
        line = None
        for candidate in reversed(proc.stdout.strip().splitlines()):
            try:
                parsed = json.loads(candidate)
            except ValueError:
                continue
            if isinstance(parsed, dict) and parsed.get("kind") == "quant":
                line = parsed
                break
        if proc.returncode != 0 or line is None:
            raise RuntimeError(
                f"scenarios --quant-gate failed rc={proc.returncode}: "
                f"{proc.stderr[-400:]}"
            )
        if (
            best is None
            or line["scenarios"]["chat"]["tok_per_s_ratio"]
            > best["scenarios"]["chat"]["tok_per_s_ratio"]
        ):
            best = line
    weight_line = None
    weights_env = q_cfg.get("weights_env")
    if weights_env:
        for _ in range(max(1, runs)):
            line = run_bench(
                q_cfg.get("backend", "ragged"), dict(weights_env)
            )
            if weight_line is None or line["value"] > weight_line["value"]:
                weight_line = line
    cap = best["capacity"]
    chat = best["scenarios"]["chat"]
    print(
        f"perf_check: quant    {scheme} capacity "
        f"{cap['bf16_blocks']}→{cap['quant_blocks']} pages "
        f"({cap['ratio']}x), chat tok/s "
        f"{chat['bf16_tok_per_s']}→{chat['quant_tok_per_s']} "
        f"({chat['tok_per_s_ratio']}x), logprob deltas "
        + ", ".join(
            f"{s}={line['mean_abs_logprob_delta']}"
            for s, line in best["scenarios"].items()
        )
        + (
            f", weights {weight_line['value']:.1f} tok/s "
            f"@ {weight_line['weight_resident_bytes']}B"
            if weight_line is not None
            else ""
        )
    )
    return best, weight_line


def measure_cross_host(x_cfg: dict, runs: int) -> dict:
    """ISSUE 19 gate driver: ``tools/scenarios.py --cross-host-gate``
    in a subprocess — the same prefill→decode request over the
    in-process dp=2 handoff vs a loopback-TCP kvnet handoff, plus the
    remote-prefix-fetch leg (docs/CROSS_HOST.md).  Best of ``runs`` =
    lowest overhead ratio: a latency-ratio gate, so 'best' must mean
    the least load-noise-polluted run."""
    best = None
    for _ in range(max(1, runs)):
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        proc = subprocess.run(
            [
                sys.executable,
                str(REPO_ROOT / "tools" / "scenarios.py"),
                "--cross-host-gate",
            ],
            capture_output=True, text=True, timeout=600, env=env,
        )
        line = None
        for candidate in reversed(proc.stdout.strip().splitlines()):
            try:
                parsed = json.loads(candidate)
            except ValueError:
                continue
            if (
                isinstance(parsed, dict)
                and parsed.get("kind") == "cross_host"
            ):
                line = parsed
                break
        if proc.returncode != 0 or line is None:
            raise RuntimeError(
                f"scenarios --cross-host-gate failed "
                f"rc={proc.returncode}: {proc.stderr[-400:]}"
            )
        if best is None or line["overhead_ratio"] < best["overhead_ratio"]:
            best = line
    print(
        f"perf_check: cross_host remote handoff "
        f"{best['remote']['wall_s']}s vs local "
        f"{best['local']['wall_s']}s (ratio {best['overhead_ratio']}) "
        f"prefix_hits={best['remote_prefix']['hits']} "
        f"identical={best['token_identical']}"
    )
    return best


def measure_unified(u_cfg: dict, runs: int) -> dict:
    """ISSUE 14 gate driver (docs/MEMORY.md): the unified-arena tiered
    memory measurement (tools/scenarios.py --unified-gate) — a mixed
    RAG + adapter-churn working set >= 4x the device pool served
    through arena + host tier + disk tier; cold pass populates, warm
    pass must hit.  Best of ``runs`` = lowest warm/cold TTFT ratio."""
    best = None
    for _ in range(max(1, runs)):
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        proc = subprocess.run(
            [
                sys.executable,
                str(REPO_ROOT / "tools" / "scenarios.py"),
                "--unified-gate",
            ],
            capture_output=True, text=True, timeout=900, env=env,
        )
        line = None
        for candidate in reversed(proc.stdout.strip().splitlines()):
            try:
                parsed = json.loads(candidate)
            except ValueError:
                continue
            if isinstance(parsed, dict) and parsed.get("kind") == "unified":
                line = parsed
                break
        if proc.returncode != 0 or line is None:
            raise RuntimeError(
                f"scenarios --unified-gate failed rc={proc.returncode}: "
                f"{proc.stderr[-400:]}"
            )
        if (
            best is None
            or line["warm_cold_ratio"] < best["warm_cold_ratio"]
        ):
            best = line
    print(
        f"perf_check: unified  working set "
        f"{best['working_set_ratio']}x HBM, warm TTFT "
        f"{best['ttft_ms_p50_warm']}ms vs cold "
        f"{best['ttft_ms_p50_cold']}ms ({best['warm_cold_ratio']}x), "
        f"{best['completed']}/{best['offered']} completed, disk "
        f"{best['tier']['disk']['stored_pages']} stored / "
        f"{best['tier']['disk']['loaded_pages']} loaded, arena "
        f"charges {best['arena']['adapter_charges']}"
    )
    return best


def main(argv: list[str] | None = None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    write = "--write" in argv
    try:
        baseline = json.loads(BASELINE_PATH.read_text())
    except FileNotFoundError:
        if not write:
            print(f"perf_check: {BASELINE_PATH} missing — run --write first")
            return 2
        baseline = {"backends": {}}
    runs = int(baseline.get("runs", 2))
    tolerance = float(baseline.get("tolerance", 0.20))
    waste_slack = float(baseline.get("waste_slack", 0.05))
    env_overrides = dict(baseline.get("env", {}))

    measured: dict[str, dict] = {}
    for backend in ("ragged",):
        try:
            measured[backend] = measure(backend, runs, env_overrides)
        except Exception as exc:  # noqa: BLE001 — tool boundary
            print(f"perf_check: measurement failed for {backend}: {exc}")
            return 2
        m = measured[backend]
        print(
            f"perf_check: {backend:8s} "
            f"tok/s={m['aggregate_output_tok_per_s']:8.1f} "
            f"waste={m['padding_waste_frac']:.4f} "
            f"shapes={m['compiled_shapes']}"
        )

    dp_cfg = baseline.get("dp")
    dp_measured: dict[str, dict] = {}
    if dp_cfg:
        try:
            dp_measured = measure_dp(dp_cfg, int(dp_cfg.get("runs", runs)))
        except Exception as exc:  # noqa: BLE001 — tool boundary
            print(f"perf_check: dp measurement failed: {exc}")
            return 2

    lora_cfg = baseline.get("lora")
    lora_churn: dict | None = None
    lora_single: dict | None = None
    if lora_cfg:
        try:
            lora_churn, lora_single = measure_lora(
                lora_cfg, int(lora_cfg.get("runs", runs))
            )
        except Exception as exc:  # noqa: BLE001 — tool boundary
            print(f"perf_check: lora measurement failed: {exc}")
            return 2

    kv_cfg = baseline.get("kv_tier")
    kv_line: dict | None = None
    if kv_cfg:
        try:
            kv_line = measure_kv_tier(kv_cfg, int(kv_cfg.get("runs", runs)))
        except Exception as exc:  # noqa: BLE001 — tool boundary
            print(f"perf_check: kv_tier measurement failed: {exc}")
            return 2

    dis_cfg = baseline.get("disagg")
    dis_line: dict | None = None
    mixed_line: dict | None = None
    if dis_cfg:
        try:
            dis_line, mixed_line = measure_disagg(
                dis_cfg, int(dis_cfg.get("runs", runs))
            )
        except Exception as exc:  # noqa: BLE001 — tool boundary
            print(f"perf_check: disagg measurement failed: {exc}")
            return 2

    spec_cfg = baseline.get("spec")
    spec_line: dict | None = None
    plain_line: dict | None = None
    if spec_cfg:
        try:
            spec_line, plain_line = measure_spec(
                spec_cfg, int(spec_cfg.get("runs", runs))
            )
        except Exception as exc:  # noqa: BLE001 — tool boundary
            print(f"perf_check: spec measurement failed: {exc}")
            return 2

    rec_cfg = baseline.get("recovery")
    rec_line: dict | None = None
    if rec_cfg:
        try:
            rec_line = measure_recovery(
                rec_cfg, int(rec_cfg.get("runs", 1))
            )
        except Exception as exc:  # noqa: BLE001 — tool boundary
            print(f"perf_check: recovery measurement failed: {exc}")
            return 2

    q_cfg = baseline.get("quant")
    q_line: dict | None = None
    q_weight_line: dict | None = None
    if q_cfg:
        try:
            q_line, q_weight_line = measure_quant(
                q_cfg, int(q_cfg.get("runs", 1))
            )
        except Exception as exc:  # noqa: BLE001 — tool boundary
            print(f"perf_check: quant measurement failed: {exc}")
            return 2

    u_cfg = baseline.get("unified")
    u_line: dict | None = None
    if u_cfg:
        try:
            u_line = measure_unified(u_cfg, int(u_cfg.get("runs", 1)))
        except Exception as exc:  # noqa: BLE001 — tool boundary
            print(f"perf_check: unified measurement failed: {exc}")
            return 2

    x_cfg = baseline.get("cross_host")
    x_line: dict | None = None
    if x_cfg:
        try:
            x_line = measure_cross_host(x_cfg, int(x_cfg.get("runs", 1)))
        except Exception as exc:  # noqa: BLE001 — tool boundary
            print(f"perf_check: cross_host measurement failed: {exc}")
            return 2

    if write:
        out = {
            "_comment": (
                "CPU-proxy perf floors for nox -s perf_check (best of "
                "`runs` BENCH_TINY passes per backend; see "
                "tools/perf_check.py).  Update with `python tools/perf_check.py "
                "--write` after a reviewed perf-relevant change."
            ),
            "runs": runs,
            "tolerance": tolerance,
            "waste_slack": waste_slack,
            "env": env_overrides,
            "backends": {
                name: {
                    "aggregate_output_tok_per_s": round(
                        m["aggregate_output_tok_per_s"], 1
                    ),
                    "padding_waste_frac": round(
                        m["padding_waste_frac"], 4
                    ),
                }
                for name, m in measured.items()
            },
        }
        if lora_cfg:
            # the lora section is declarative (ratio + structural
            # demands, not measured floors) — carried through, with the
            # tok/s floor refreshed at the documented ~70% haircut
            out["lora"] = {
                **lora_cfg,
                **(
                    {"min_tok_per_s": round(lora_churn["value"] * 0.7, 1)}
                    if lora_churn is not None
                    else {}
                ),
            }
        if kv_cfg:
            # declarative section (ratio + structural demands): carried
            # through unchanged — there is no measured floor to refresh
            out["kv_tier"] = dict(kv_cfg)
        if rec_cfg:
            # declarative too: the ≤2x resumed/uncrashed ratio is the
            # ISSUE 10 acceptance bound, not a measured floor
            out["recovery"] = dict(rec_cfg)
        if dis_cfg:
            # declarative (ratio + structural demands): the ≤0.5x
            # disagg/mixed chat-ITL bound is the ISSUE 11 acceptance
            # criterion, not a measured floor
            out["disagg"] = dict(dis_cfg)
        if spec_cfg:
            # declarative: the ≥1.5x spec/plain chat-ITL speedup and
            # ≥0.6 acceptance are the ISSUE 12 acceptance criteria
            out["spec"] = dict(spec_cfg)
        if q_cfg:
            # declarative (capacity/speedup/quality bounds are the
            # ISSUE 13 acceptance criteria); only the weight-path
            # tok/s floor is measured, refreshed at the ~70% haircut
            out["quant"] = {
                **q_cfg,
                **(
                    {"min_weight_tok_per_s": round(
                        q_weight_line["value"] * 0.7, 1
                    )}
                    if q_weight_line is not None
                    else {}
                ),
            }
        if u_cfg:
            # declarative: the <=0.5x warm/cold bound, the >=4x working
            # set, and the zero-deadlock completion demand are the
            # ISSUE 14 acceptance criteria, not measured floors
            out["unified"] = dict(u_cfg)
        if x_cfg:
            # declarative: the remote-vs-local handoff overhead bound
            # and the structural remote-hit/handoff demands are the
            # ISSUE 19 acceptance criteria, not measured floors
            out["cross_host"] = dict(x_cfg)
        if dp_cfg:
            out["dp"] = {
                **dp_cfg,
                # the dp gate compares floors with NO additional
                # tolerance (unlike the main tok/s gate), so the ~70%
                # haircut the checked-in style documents is applied at
                # write time — a freshly written baseline must not fail
                # the very next run on ordinary best-of-N jitter
                "floors_tok_per_s": {
                    point: round(line["value"] * 0.7, 1)
                    for point, line in dp_measured.items()
                },
            }
        BASELINE_PATH.write_text(json.dumps(out, indent=2) + "\n")
        print(f"perf_check: baseline written to {BASELINE_PATH}")
        return 0

    failures = []
    for backend, base in baseline.get("backends", {}).items():
        m = measured.get(backend)
        if m is None:
            failures.append(f"{backend}: no measurement")
            continue
        floor = base["aggregate_output_tok_per_s"] * (1.0 - tolerance)
        if m["aggregate_output_tok_per_s"] < floor:
            failures.append(
                f"{backend}: {m['aggregate_output_tok_per_s']:.1f} tok/s "
                f"< floor {floor:.1f} (baseline "
                f"{base['aggregate_output_tok_per_s']:.1f} - {tolerance:.0%})"
            )
        waste_ceiling = base["padding_waste_frac"] + waste_slack
        if m["padding_waste_frac"] > waste_ceiling:
            failures.append(
                f"{backend}: padding waste {m['padding_waste_frac']:.4f} "
                f"> ceiling {waste_ceiling:.4f} (baseline "
                f"{base['padding_waste_frac']:.4f} + {waste_slack})"
            )

    if dp_cfg:
        # absolute floors (already hand-haircut in the checked-in file,
        # so compared directly — no extra tolerance)
        for point, floor in dp_cfg.get("floors_tok_per_s", {}).items():
            line = dp_measured.get(str(point))
            if line is None:
                failures.append(f"dp={point}: no measurement")
            elif line["value"] < floor:
                failures.append(
                    f"dp={point}: {line['value']:.1f} tok/s < floor "
                    f"{floor:.1f}"
                )
        # near-linear scaling vs the SAME session's dp=1 measurement
        base_line = dp_measured.get("1")
        for point, min_ratio in dp_cfg.get("min_scaling", {}).items():
            line = dp_measured.get(str(point))
            if line is None or base_line is None:
                failures.append(f"dp={point}: scaling unmeasurable")
                continue
            ratio = line["value"] / max(base_line["value"], 1e-9)
            if ratio < min_ratio:
                failures.append(
                    f"dp={point}: {ratio:.2f}x dp=1 < required "
                    f"{min_ratio}x ({line['value']:.1f} vs "
                    f"{base_line['value']:.1f} tok/s)"
                )

    if lora_cfg and lora_churn is not None and lora_single is not None:
        # ISSUE 8 acceptance: adapter-churn ITL within max_itl_ratio of
        # the single-adapter run (same session, so load jitter cancels),
        # the demo residency/churn shape actually achieved, and a
        # conservative absolute tok/s floor
        ratio = lora_churn["itl_ms_p50"] / max(
            lora_single["itl_ms_p50"], 1e-9
        )
        max_ratio = float(lora_cfg.get("max_itl_ratio", 1.5))
        if ratio > max_ratio:
            failures.append(
                f"lora: churn ITL p50 {lora_churn['itl_ms_p50']}ms is "
                f"{ratio:.2f}x the single-adapter run "
                f"({lora_single['itl_ms_p50']}ms) > allowed {max_ratio}x"
            )
        min_resident = int(lora_cfg.get("min_resident", 0))
        if lora_churn.get("lora_resident_high_water", 0) < min_resident:
            failures.append(
                f"lora: resident high-water "
                f"{lora_churn.get('lora_resident_high_water')} < "
                f"required {min_resident} (pool not actually exercised)"
            )
        min_swaps = int(lora_cfg.get("min_swaps_in", 0))
        if lora_churn.get("lora_swaps_in", 0) < min_swaps:
            failures.append(
                f"lora: swaps_in {lora_churn.get('lora_swaps_in')} < "
                f"required {min_swaps} (no churn happened)"
            )
        floor = float(lora_cfg.get("min_tok_per_s", 0.0))
        if lora_churn["value"] < floor:
            failures.append(
                f"lora: {lora_churn['value']:.1f} tok/s < floor {floor:.1f}"
            )

    if kv_cfg and kv_line is not None:
        # ISSUE 9 acceptance: with the device prefix pool capped below
        # the reusable working set, warm TTFT p50 ≤ max_warm_ttft_ratio
        # of cold, combined hit rate ≥ min_hit_rate, the host tier
        # actually served tokens, and cold↔warm outputs token-identical
        kv = kv_line["kv_tier"]
        max_ratio = float(kv_cfg.get("max_warm_ttft_ratio", 0.6))
        if kv["warm_cold_ttft_ratio"] > max_ratio:
            failures.append(
                f"kv_tier: warm TTFT p50 {kv['ttft_warm_ms_p50']}ms is "
                f"{kv['warm_cold_ttft_ratio']}x cold "
                f"({kv['ttft_cold_ms_p50']}ms) > allowed {max_ratio}x"
            )
        min_hit = float(kv_cfg.get("min_hit_rate", 0.5))
        if kv["combined_hit_rate"] < min_hit:
            failures.append(
                f"kv_tier: combined hit rate {kv['combined_hit_rate']} "
                f"< required {min_hit}"
            )
        min_host = int(kv_cfg.get("min_host_promoted_tokens", 0))
        if kv.get("host_promoted_tokens", 0) < min_host:
            failures.append(
                f"kv_tier: host_promoted_tokens "
                f"{kv.get('host_promoted_tokens')} < required {min_host} "
                "(reuse never flowed through the host tier)"
            )
        if not kv.get("token_identical"):
            failures.append(
                "kv_tier: warm-pass outputs diverged from the cold pass "
                "(promoted KV must be byte-equivalent to recompute)"
            )

    if dis_cfg and dis_line is not None and mixed_line is not None:
        # ISSUE 11 acceptance: chat ITL p99 under concurrent RAG load
        # ≥ 2x better disaggregated than all-mixed at equal replica
        # count, handoff streams token-identical (same greedy outputs
        # digest), every handoff actually taken (none fell back)
        d, m = dis_line["roles"], mixed_line["roles"]
        max_ratio = float(dis_cfg.get("max_itl_ratio", 0.5))
        ratio = d["chat_itl_ms_p99"] / max(m["chat_itl_ms_p99"], 1e-9)
        if ratio > max_ratio:
            failures.append(
                f"disagg: chat ITL p99 {d['chat_itl_ms_p99']}ms is "
                f"{ratio:.2f}x the mixed fleet's "
                f"({m['chat_itl_ms_p99']}ms) > allowed {max_ratio}x — "
                "disaggregation stopped isolating chat from RAG "
                "prefill"
            )
        if d["outputs_digest"] != m["outputs_digest"]:
            failures.append(
                "disagg: outputs digest diverged from the mixed fleet "
                "(handoff must be token-identical)"
            )
        min_handoffs = int(dis_cfg.get("min_handoffs", 1))
        if d.get("handoffs_completed", 0) < min_handoffs:
            failures.append(
                f"disagg: {d.get('handoffs_completed')} handoffs "
                f"completed < required {min_handoffs} (the split fleet "
                "did not actually hand off)"
            )
        if d.get("handoffs_fallback", 0) > 0:
            failures.append(
                f"disagg: {d['handoffs_fallback']} handoff(s) fell "
                "back to retryable failure under a healthy fleet"
            )
        if m.get("handoffs_completed", 0) != 0:
            failures.append(
                "disagg: the mixed-mode control run handed off "
                f"{m['handoffs_completed']} request(s) — control is "
                "contaminated"
            )

    if spec_cfg and spec_line is not None and plain_line is not None:
        # ISSUE 12 acceptance: ragged+spec beats plain ragged by >=
        # min_itl_speedup on decode-heavy chat ITL at acceptance >=
        # min_acceptance, token-identical under greedy, with verify
        # dispatches actually taken
        s, pl = spec_line["roles"], plain_line["roles"]
        st = spec_line.get("spec", {})
        min_speedup = float(spec_cfg.get("min_itl_speedup", 1.5))
        speedup = pl["chat_itl_ms_p50"] / max(s["chat_itl_ms_p50"], 1e-9)
        if speedup < min_speedup:
            failures.append(
                f"spec: chat ITL p50 {s['chat_itl_ms_p50']}ms is only "
                f"{speedup:.2f}x better than plain ragged "
                f"({pl['chat_itl_ms_p50']}ms) < required {min_speedup}x"
            )
        min_accept = float(spec_cfg.get("min_acceptance", 0.6))
        if st.get("acceptance_rate", 0.0) < min_accept:
            failures.append(
                f"spec: acceptance {st.get('acceptance_rate')} < "
                f"required {min_accept} (draft/verify machinery broken "
                "— the same-weights draft should accept ~everything)"
            )
        min_vd = int(spec_cfg.get("min_verify_dispatches", 1))
        if st.get("verify_dispatches", 0) < min_vd:
            failures.append(
                f"spec: {st.get('verify_dispatches')} verify dispatches "
                f"< required {min_vd} (speculation never actually ran)"
            )
        if s["outputs_digest"] != pl["outputs_digest"]:
            failures.append(
                "spec: outputs digest diverged from the plain ragged "
                "run (verify spans must be token-identical under "
                "greedy sampling)"
            )

    if rec_cfg and rec_line is not None:
        # ISSUE 10 acceptance: a request killed mid-decode completes
        # RESUMED within max_ratio x its uncrashed wall time, with the
        # resumed stream token-identical and the resume actually taken
        # (not the fallback ladder)
        max_ratio = float(rec_cfg.get("max_ratio", 2.0))
        if rec_line["ratio"] > max_ratio:
            failures.append(
                f"recovery: resumed completion {rec_line['resumed_s']}s "
                f"is {rec_line['ratio']}x the uncrashed baseline "
                f"({rec_line['base_s']}s) > allowed {max_ratio}x"
            )
        if not rec_line.get("token_identical"):
            failures.append(
                "recovery: resumed stream diverged from the uncrashed "
                "baseline (checkpoint/resume must be token-identical)"
            )
        if rec_line.get("resumed", 0) < 1:
            failures.append(
                "recovery: the mid-decode request was not resumed "
                "(fallback ladder taken — gate measured nothing)"
            )

    if q_cfg and q_line is not None:
        # ISSUE 13 acceptance (docs/QUANTIZATION.md): KV-page capacity
        # ≥ min_capacity_ratio x bf16 at equal HBM, per-scenario
        # logprob deltas bounded (token quality IS the gate — greedy
        # identity cannot police a numerics-changing optimization),
        # chat-suite tok/s ≥ min_chat_speedup with the device pool
        # capped below the working set, and the weight-only int8 path
        # floored with its resident-bytes saving demonstrated
        cap_ratio = q_line["capacity"]["ratio"]
        min_cap = float(q_cfg.get("min_capacity_ratio", 1.9))
        if cap_ratio < min_cap:
            failures.append(
                f"quant: KV-page capacity {cap_ratio}x bf16 at equal "
                f"HBM < required {min_cap}x "
                f"({q_line['capacity']['bf16_blocks']} → "
                f"{q_line['capacity']['quant_blocks']} pages)"
            )
        max_deltas = q_cfg.get("max_logprob_delta", {})
        min_match = q_cfg.get("min_token_match", {})
        for suite, line in q_line["scenarios"].items():
            bound = float(max_deltas.get(suite, 0.05))
            delta = line.get("mean_abs_logprob_delta")
            if delta is None:
                failures.append(
                    f"quant/{suite}: no logprob deltas measured "
                    "(quality gate measured nothing)"
                )
            elif delta > bound:
                failures.append(
                    f"quant/{suite}: mean |Δlogprob| {delta} > bound "
                    f"{bound} (quantized KV is perturbing token "
                    "quality beyond the per-scenario budget)"
                )
            floor = float(
                min_match.get(suite, 0.3)
                if isinstance(min_match, dict)
                else min_match
            )
            if line.get("token_match_frac", 0.0) < floor:
                failures.append(
                    f"quant/{suite}: token_match_frac "
                    f"{line.get('token_match_frac')} < required {floor}"
                )
        chat = q_line["scenarios"]["chat"]
        min_speed = float(q_cfg.get("min_chat_speedup", 1.3))
        if chat["tok_per_s_ratio"] < min_speed:
            failures.append(
                f"quant: chat-suite tok/s ratio "
                f"{chat['tok_per_s_ratio']}x bf16 < required "
                f"{min_speed}x at equal HBM "
                f"({chat['bf16_tok_per_s']} vs "
                f"{chat['quant_tok_per_s']} tok/s — the 2x page pool "
                "stopped buying batch occupancy)"
            )
        if q_weight_line is not None:
            floor = float(q_cfg.get("min_weight_tok_per_s", 0.0))
            if q_weight_line["value"] < floor:
                failures.append(
                    f"quant/weights: {q_weight_line['value']:.1f} "
                    f"tok/s < floor {floor:.1f}"
                )
            base_bytes = (
                measured.get("ragged", {}).get("weight_resident_bytes")
            )
            max_ratio = float(q_cfg.get("max_weight_bytes_ratio", 0.75))
            if base_bytes:
                ratio = (
                    q_weight_line["weight_resident_bytes"] / base_bytes
                )
                if ratio > max_ratio:
                    failures.append(
                        f"quant/weights: resident bytes "
                        f"{q_weight_line['weight_resident_bytes']} are "
                        f"{ratio:.2f}x the full-precision run "
                        f"({base_bytes}) > allowed {max_ratio}x — "
                        "int8 weight quantization stopped saving HBM"
                    )

    if u_cfg and u_line is not None:
        # ISSUE 14 acceptance: mixed RAG + adapter-churn working set
        # >= min_working_set_ratio x the device pool sustains warm-hit
        # TTFT <= max_warm_cold_ratio x cold, with zero allocation
        # deadlocks (every offered request completed) and the full
        # hierarchy demonstrably exercised (host evictions cascaded to
        # the disk tier, disk promotions served, arena charges flowed)
        max_ratio = float(u_cfg.get("max_warm_cold_ratio", 0.5))
        if u_line["warm_cold_ratio"] > max_ratio:
            failures.append(
                f"unified: warm TTFT p50 {u_line['ttft_ms_p50_warm']}ms "
                f"is {u_line['warm_cold_ratio']}x cold "
                f"({u_line['ttft_ms_p50_cold']}ms) > allowed {max_ratio}x"
            )
        min_ws = float(u_cfg.get("min_working_set_ratio", 4.0))
        if u_line["working_set_ratio"] < min_ws:
            failures.append(
                f"unified: working set {u_line['working_set_ratio']}x "
                f"the device pool < required {min_ws}x (the gate "
                "stopped oversubscribing HBM)"
            )
        if u_line["completed"] != u_line["offered"]:
            failures.append(
                f"unified: {u_line['completed']}/{u_line['offered']} "
                "requests completed — an allocation deadlock (or shed) "
                "under arena pressure"
            )
        disk = u_line["tier"]["disk"] or {}
        if not disk.get("stored_pages"):
            failures.append(
                "unified: the disk tier stored nothing — host "
                "evictions stopped cascading down the hierarchy"
            )
        if not disk.get("loaded_pages"):
            failures.append(
                "unified: the disk tier served nothing — promotions "
                "never walked disk→host→device"
            )
        if not (u_line.get("arena") or {}).get("adapter_charges"):
            failures.append(
                "unified: the arena charged no adapters — the unified "
                "budget was not exercised"
            )

    if x_cfg and x_line is not None:
        # ISSUE 19 acceptance: a loopback-TCP kvnet handoff completes
        # within max_overhead_ratio x the in-process dp=2 handoff,
        # token-identical across all three legs, with the remote path
        # actually taken (kvnet handoffs counted) and the
        # remote-prefix leg actually served over the wire
        max_ratio = float(x_cfg.get("max_overhead_ratio", 2.5))
        if x_line["overhead_ratio"] > max_ratio:
            failures.append(
                f"cross_host: remote handoff {x_line['remote']['wall_s']}s "
                f"is {x_line['overhead_ratio']}x the local fleet's "
                f"({x_line['local']['wall_s']}s) > allowed {max_ratio}x"
            )
        if not x_line.get("token_identical"):
            failures.append(
                "cross_host: remote handoff or remote-prefix outputs "
                "diverged (a remote hit must behave exactly like a "
                "local one)"
            )
        min_handoffs = int(x_cfg.get("min_remote_handoffs", 1))
        if x_line["remote"].get("handoffs_remote", 0) < min_handoffs:
            failures.append(
                f"cross_host: {x_line['remote'].get('handoffs_remote')} "
                f"kvnet handoffs < required {min_handoffs} (the remote "
                "path was not actually taken)"
            )
        min_hits = int(x_cfg.get("min_remote_prefix_hits", 1))
        if x_line["remote_prefix"].get("hits", 0) < min_hits:
            failures.append(
                f"cross_host: {x_line['remote_prefix'].get('hits')} "
                f"remote prefix pages served < required {min_hits} "
                "(the prefix-sharing path was not actually exercised)"
            )

    if failures:
        print("perf_check: REGRESSION")
        for f in failures:
            print(f"  - {f}")
        return 1
    print("perf_check: green")
    return 0


if __name__ == "__main__":
    sys.exit(main())
