"""Pipeline-parallel model runner: layer stages over disjoint device groups.

The reference stack's engine tier supports ``--pipeline-parallel-size``
(vLLM arg surface consumed via the adapter's parser, SURVEY.md §2.3/§2.4);
this is the TPU-native equivalent.  The model's layers are split into S
contiguous stages, each owning a disjoint ``tp``-sized device slice with
its own layer-sliced KV cache and jitted stage program; activations hop
stage to stage with ``jax.device_put`` (ICI transfers on real hardware).
PP's primary inference value is CAPACITY — serving a model S× bigger than
one device group's HBM.  Decode additionally overlaps the stages: the
batch splits into up to S microbatches whose chains are issued with no
host synchronisation (sampled tokens feed back to stage 0 as device
arrays), so JAX's async dispatch keeps every stage busy on a different
microbatch.  Prefill chains remain sequential per prompt (single-request
latency pays the stage bubble there).

Scope (fail-fast otherwise, engine/config.py validation): composes with
TP (stage meshes), DP (one pipeline per replica), LoRA (stage-sliced
adapter stacks), and everything sampler-side (guided decoding, seeded
sampling, penalties, stop strings, chunked prefill, prefix caching);
NOT with speculative decoding or sequence parallelism yet.

Decode under PP runs one step per stage chain (the single-jit fused
K-step scan cannot span device groups); the scheduler's
``num_decode_steps`` still batches K steps per plan, paid as K chained
dispatches.
"""

from __future__ import annotations

import dataclasses
import functools
import re
from typing import TYPE_CHECKING

import jax
import jax.numpy as jnp
import numpy as np

from vllm_tgis_adapter_tpu.compile_tracker import track_jit
from vllm_tgis_adapter_tpu.engine import sampler as sampler_mod
from vllm_tgis_adapter_tpu.engine.runner import (
    ModelRunner,
    PromptLogprobInfo,
    SampledToken,
    _HostSamplerOutput,
)
from vllm_tgis_adapter_tpu.logging import init_logger

if TYPE_CHECKING:
    from vllm_tgis_adapter_tpu.engine.config import EngineConfig

logger = init_logger(__name__)


def _stage_meshes(config: "EngineConfig", devices=None) -> list:
    """The deterministic stage → device-slice mapping (shared by the
    weight-loading place fn and the runner so both land tensors on the
    same devices)."""
    from vllm_tgis_adapter_tpu.parallel import build_mesh

    pcfg = config.parallel_config
    pp, tp = pcfg.pipeline_parallel_size, pcfg.tensor_parallel_size
    devices = list(devices if devices is not None else jax.devices())
    if pp * tp > len(devices):
        raise ValueError(
            f"pipeline_parallel_size={pp} × tensor_parallel_size={tp} "
            f"needs {pp * tp} devices but only {len(devices)} are visible"
        )
    return [
        build_mesh(tensor_parallel_size=tp,
                   devices=devices[s * tp:(s + 1) * tp])
        for s in range(pp)
    ]


def make_pp_place_fn(config: "EngineConfig", devices=None):
    """Shard-on-load placement routed by pipeline stage: each layer's
    tensors go straight to their stage's device group (with the usual
    Megatron tp spec within it), embeddings to stage 0, head/final norm
    to the last — so no device group ever materialises another stage's
    weights."""
    from jax.sharding import NamedSharding

    from vllm_tgis_adapter_tpu.parallel.sharding import hf_name_spec

    meshes = _stage_meshes(config, devices)
    ranges = split_layer_ranges(
        config.model_config.num_layers, len(meshes)
    )

    def stage_of_layer(j: int) -> int:
        for s, (lo, hi) in enumerate(ranges):
            if lo <= j < hi:
                return s
        raise ValueError(f"layer index {j} out of range {ranges}")

    def place(name: str, x: jax.Array) -> jax.Array:
        # llama/opt/neox spell layers "…layers.N."; bloom uses "h.N."
        m = re.search(r"(?:^|\.)(?:layers|h)\.(\d+)\.", name)
        if m is not None:
            mesh = meshes[stage_of_layer(int(m.group(1)))]
        elif any(k in name for k in
                 ("embed_tokens", "embed_in", "embed_positions",
                  "word_embeddings", "wte", "wpe")):
            # word_embeddings also catches bloom's
            # word_embeddings_layernorm; wte/wpe are gpt2's token and
            # learned-position embeddings — all live on stage 0
            mesh = meshes[0]
        else:  # lm_head / embed_out / decoder-level final norm
            mesh = meshes[-1]
        return jax.device_put(x, NamedSharding(mesh, hf_name_spec(name)))

    return place


def split_layer_ranges(num_layers: int, stages: int) -> list[tuple[int, int]]:
    """Contiguous near-even layer ranges, earlier stages taking the
    remainder (they also hold the embedding)."""
    base, rem = divmod(num_layers, stages)
    ranges = []
    start = 0
    for s in range(stages):
        n = base + (1 if s < rem else 0)
        ranges.append((start, start + n))
        start += n
    return ranges


def split_pipeline_params(params: dict, ranges) -> list[dict]:
    """Stage param dicts (views, no copies): embed(+pos) on stage 0,
    final norm + lm_head on the last, each stage its layer slice."""
    stages = []
    last = len(ranges) - 1
    for s, (lo, hi) in enumerate(ranges):
        p: dict = {"layers": params["layers"][lo:hi]}
        if s == 0:
            p["embed"] = params["embed"]
            for name in ("pos_embed", "embed_norm", "embed_norm_bias"):
                if name in params:
                    p[name] = params[name]
        if s == last:
            # tied lm_head reads params["embed"]; the last stage needs its
            # own reference even when stage 0 also holds it
            if "embed" not in p:
                p["embed"] = params["embed"]
            p["final_norm"] = params["final_norm"]
            if "final_norm_bias" in params:
                p["final_norm_bias"] = params["final_norm_bias"]
            if "lm_head" in params:
                p["lm_head"] = params["lm_head"]
        stages.append(p)
    return stages


def _stage_decode(model, block_size, first, last,
                  params, caches, token_ids, step_ints, block_tables,
                  hidden=None, lora=None, lora_idx=None):
    """Jitted per-stage decode wrapper: the three identical per-step row
    vectors (positions, slot_mapping, context_lens) travel as ONE packed
    [3, B] int32 buffer per stage — each host↔device buffer is its own
    transfer."""
    return model.decode(
        params, caches, token_ids, step_ints[0], step_ints[1],
        block_tables, step_ints[2], block_size, lora, lora_idx,
        hidden=hidden, first_stage=first, last_stage=last,
    )


@dataclasses.dataclass
class _Stage:
    model: object  # layer-sliced model instance (own config/layer_offset)
    params: dict
    caches: tuple
    mesh: object  # this stage's tp mesh (placement + Megatron specs)
    data_sharding: object  # replicated NamedSharding on this stage's mesh
    first: bool
    last: bool
    prefill_fn: object
    chunk_fn: object
    decode_fn: object


class PipelineRunner(ModelRunner):
    """Drop-in ModelRunner with the device tier split into pp stages.

    Reuses the host halves (prepare_prefill / prepare_decode) unchanged;
    only initialisation and the execute halves differ.
    """

    def __init__(self, config: "EngineConfig", model, params, devices=None):
        from vllm_tgis_adapter_tpu.parallel import (
            cache_sharding,
            data_sharding,
            shard_llama_params,
            validate_tp_divisibility,
        )

        pcfg = config.parallel_config
        pp = pcfg.pipeline_parallel_size
        tp = pcfg.tensor_parallel_size
        mcfg = config.model_config
        cache_cfg = config.cache_config

        # same deterministic stage -> device-slice mapping as the weight
        # loader's place fn, so stage programs run where the weights live
        meshes = _stage_meshes(config, devices)
        if pp > mcfg.num_layers:
            raise ValueError(
                f"pipeline_parallel_size={pp} exceeds num_layers="
                f"{mcfg.num_layers}"
            )
        validate_tp_divisibility(mcfg, tp)

        # ---- host-side state the inherited prepare_* halves consume ----
        self.config = config
        self.model = model  # whole-model reference (config introspection)
        # calibrated kv-scale floors are a flat-runner feature
        # (--kv-quantization refuses pp>1); drop the sidecar so stage
        # slicing never sees a non-layer params key
        if isinstance(params, dict):
            params.pop("kv_scale_floors", None)
        self.block_size = cache_cfg.block_size
        self.num_slots = cache_cfg.num_blocks * cache_cfg.block_size
        self.max_blocks_per_seq = -(-mcfg.max_model_len // self.block_size)
        self._rng = np.random.default_rng(config.seed)
        self.lora_stacks = None
        # one pool a stage: boot refuses pp for a model with window and
        # full layers in two groups (engine/config.py)
        self.has_window_group = False
        self._stage_lora = None
        self._lora_version = 0
        self._seen_pad_lens = sorted(
            set(config.scheduler_config.prefill_buckets)
        )
        self.spec = None
        self.mesh = None  # whole-runner mesh is meaningless under pp

        # ---- stage construction ----
        self.ranges = split_layer_ranges(mcfg.num_layers, pp)
        stage_params = split_pipeline_params(params, self.ranges)
        model_cls = type(model)
        donate = (1,)
        self.caches_donated = True  # device_report.py
        self.stages: list[_Stage] = []
        for s, (lo, hi) in enumerate(self.ranges):
            smesh = meshes[s]
            scfg = dataclasses.replace(mcfg, num_layers=hi - lo)
            smodel = model_cls(scfg)
            smodel.mesh = smesh
            smodel.layer_offset = lo
            sparams = shard_llama_params(smesh, stage_params[s])
            sh = cache_sharding(smesh)
            caches = jax.jit(
                lambda m=smodel: m.make_kv_caches(
                    self.num_slots, cache_cfg.cache_dtype
                ),
                out_shardings=(sh, sh),
            )()
            first, last = s == 0, s == pp - 1
            self.stages.append(_Stage(
                model=smodel,
                params=sparams,
                caches=caches,
                mesh=smesh,
                data_sharding=data_sharding(smesh),
                first=first,
                last=last,
                # stage fns are invoked with token_ids as a KEYWORD
                # (execute paths build a kwargs dict), so the shape
                # labels read kwargs, not positional args
                prefill_fn=track_jit(
                    f"pp{s}_prefill",
                    jax.jit(
                        functools.partial(
                            smodel.prefill, first_stage=first,
                            last_stage=last,
                        ),
                        donate_argnums=donate,
                    ),
                    label=lambda args, kwargs:
                        f"tokens={kwargs['token_ids'].shape[0]}",
                ),
                chunk_fn=track_jit(
                    f"pp{s}_prefill_chunk",
                    jax.jit(
                        functools.partial(
                            smodel.prefill_chunk,
                            block_size=self.block_size,
                            first_stage=first, last_stage=last,
                        ),
                        donate_argnums=donate,
                    ),
                    label=lambda args, kwargs:
                        f"tokens={kwargs['token_ids'].shape[0]}",
                ),
                decode_fn=track_jit(
                    f"pp{s}_decode",
                    jax.jit(
                        functools.partial(
                            _stage_decode, smodel, self.block_size,
                            first, last,
                        ),
                        donate_argnums=donate,
                    ),
                    label=lambda args, kwargs:
                        f"batch={kwargs['token_ids'].shape[-1]}",
                ),
            ))
        logger.info(
            "pipeline runner: %d stages × tp=%d, layer ranges %s",
            pp, tp, self.ranges,
        )

        last_stage = self.stages[-1]
        self._data_sharding = last_stage.data_sharding  # sampler inputs
        max_seqs = config.scheduler_config.max_num_seqs
        self.seen = self._put(jnp.zeros((max_seqs, mcfg.vocab_size), bool))

    # ------------------------------------------------------------- helpers

    def _stage_put(self, stage: _Stage, x):
        return jax.device_put(np.asarray(x), stage.data_sharding)

    def _place_lora_stacks(self, stacks):  # noqa: ANN001
        """Per-stage adapter stacks: the [L, ...] target arrays slice on
        the layer axis exactly like the params, so each stage's model
        indexes them with its LOCAL layer number.  Returns a bare truthy
        marker — keeping the full host stacks alive would pin gigabytes
        for big models; the sliced device copies hold the data."""
        self._stage_lora = []
        for stage, (lo, hi) in zip(self.stages, self.ranges):
            sliced = dataclasses.replace(
                stacks,
                a={t: v[lo:hi] for t, v in stacks.a.items()},
                b={t: v[lo:hi] for t, v in stacks.b.items()},
            )
            self._stage_lora.append(jax.tree.map(
                lambda x, st=stage: jax.device_put(
                    np.asarray(x), st.data_sharding
                ),
                sliced,
            ))
        return True

    # ------------------------------------------------------------- prefill

    # staged execution synchronises hidden-state handoffs between stage
    # device groups, so the enqueue-only dispatch/wait split does not
    # apply: dispatch returns the sentinel and wait runs the full staged
    # execution (engine/runner.py SYNC_DISPATCH contract)
    def dispatch_prefill(self, prep):
        from vllm_tgis_adapter_tpu.engine.runner import SYNC_DISPATCH

        return SYNC_DISPATCH

    def wait_prefill(self, prep, handle):
        return self.execute_prefill(prep)

    def dispatch_decode(self, prep):
        from vllm_tgis_adapter_tpu.engine.runner import SYNC_DISPATCH

        return SYNC_DISPATCH

    def wait_decode(self, prep, handle):
        return self.execute_decode(prep)

    def execute_prefill(self, prep):
        """Chain the prompt (chunk) through the stages; sample on the
        last stage's devices."""
        t = prep.t
        hidden = None
        logits = None
        for si, stage in enumerate(self.stages):
            common = dict(
                token_ids=self._stage_put(stage, prep.token_ids),
                positions=self._stage_put(stage, prep.positions),
                slot_mapping=self._stage_put(stage, prep.slot_mapping),
                valid_len=self._stage_put(stage, np.asarray(t, np.int32)),
                logits_indices=self._stage_put(stage, prep.logits_indices),
            )
            if self.lora_stacks is not None:
                common["lora"] = self._stage_lora[si]
                common["lora_slot"] = self._stage_put(
                    stage, np.asarray(prep.lora_slot, np.int32)
                )
            if not stage.first:
                common["hidden"] = jax.device_put(
                    hidden, stage.data_sharding
                )
            if prep.start_pos == 0:
                out, stage.caches = stage.prefill_fn(
                    stage.params, stage.caches, **common
                )
            else:
                out, stage.caches = stage.chunk_fn(
                    stage.params, stage.caches,
                    block_table=self._stage_put(stage, prep.block_table),
                    **common,
                )
            if stage.last:
                logits = out
            else:
                hidden = out
        prompt_info = None
        if prep.want_prompt_lp:
            prompt_info = PromptLogprobInfo.from_packed(
                sampler_mod.pack_prompt_logprob_parts(
                    sampler_mod.prompt_logprob_info(
                        logits, jnp.asarray(prep.lp_targets)
                    )
                ),
                prep.lp_rows,
            )
        if not prep.is_final:
            return None, prompt_info  # lp chunks carry their table rows

        if prep.want_prompt_lp:
            last_logits = logits[t - 1][None]
        else:
            last_logits = logits

        self.seen = sampler_mod.set_seen_row(
            self.seen,
            self._put(np.asarray(prep.row_slot)),
            self._put(prep.seen_tokens),
        )
        allowed_mask = (
            self._put(prep.allowed_row[None, :])
            if prep.allowed_row is not None
            else None
        )
        seen_rows = jnp.take(
            self.seen,
            jnp.clip(jnp.asarray([prep.row_slot]), 0, None),
            axis=0,
        )
        out = sampler_mod.sample(
            last_logits,
            seen_rows,
            jax.tree.map(self._put, prep.tensors),
            allowed_mask=allowed_mask,
        )
        self.seen = sampler_mod.update_seen(
            self.seen, jnp.asarray([prep.row_slot]), out.tokens
        )
        host = _HostSamplerOutput.from_packed(
            sampler_mod.pack_output(out)[None]
        )
        return host.token(0, 0), prompt_info

    # -------------------------------------------------------------- decode

    def execute_decode(self, prep) -> list[list[SampledToken]]:
        """K single-step stage chains per plan (the fused on-device scan
        cannot span device groups); penalties/sampling run on the last
        stage exactly as the fused path does.

        Overlap: the batch splits into up to ``num_stages`` microbatches
        and dispatches are issued STEP-MAJOR (all chains' step k before
        any chain's step k+1) with no host synchronisation — the sampled
        tokens feed back to stage 0 as device arrays.  Per-device queues
        execute FIFO, so step-major order is what lets stage s run
        microbatch m's step while stage s+1 runs m-1's (chain-major
        order would park a feedback-blocked dispatch at the head of the
        queue and serialise everything behind it).  The host blocks only
        once, collecting all K results at the end.  Microbatches touch
        disjoint seen-matrix rows, so their sampler calls' shared
        ordering on the last stage's device is not a correctness
        constraint."""
        b = prep.token_ids.shape[0]
        n_stages = len(self.stages)
        m_count = n_stages if (b % n_stages == 0 and b >= n_stages) else 1
        mb = b // m_count
        active_rows = np.asarray(prep.slots) >= 0
        rows_all = np.clip(np.asarray(prep.slots), 0, None)

        positions0 = np.asarray(prep.positions)
        limits = np.asarray(prep.limits)
        ctx0 = np.asarray(prep.context_lens)
        tables_host = np.asarray(prep.block_tables)

        # per-microbatch issue state; tensors leaves are [B] host numpy
        # (engine/sampler.py SamplingTensors.from_params keeps them on
        # host precisely so callers control the transfer)
        chains = []
        for m in range(m_count):
            lo, hi = m * mb, (m + 1) * mb
            chains.append(dict(
                lo=lo, hi=hi,
                tokens=None,  # device array after step 0
                tensors=jax.tree.map(
                    lambda x, lo=lo, hi=hi: self._put(x[lo:hi]),
                    prep.tensors,
                ),
                allowed=(
                    self._put(prep.allowed_mask[lo:hi])
                    if prep.allowed_mask is not None
                    else None
                ),
                rows=jnp.asarray(rows_all[lo:hi]),
                # stage-constant placements, done once per chain: block
                # tables plus a token placeholder for non-first stages
                # (decode() reads `hidden` there, not token_ids)
                tables=[
                    self._stage_put(stage, prep.block_tables[lo:hi])
                    for stage in self.stages
                ],
                tok_placeholder=[
                    self._stage_put(stage, prep.token_ids[lo:hi])
                    for stage in self.stages
                ],
                lora_idx=(
                    [
                        self._stage_put(stage, prep.lora_idx[lo:hi])
                        for stage in self.stages
                    ]
                    if prep.lora_idx is not None
                    else None
                ),
                outs=[],
            ))

        for k in range(prep.num_steps):
            for chain in chains:
                lo, hi = chain["lo"], chain["hi"]
                positions = positions0[lo:hi] + k
                active = (positions <= limits[lo:hi]) & active_rows[lo:hi]
                blk = np.take_along_axis(
                    tables_host[lo:hi],
                    np.clip(positions // self.block_size, 0,
                            self.max_blocks_per_seq - 1)[:, None],
                    axis=1,
                )[:, 0]
                slot = np.where(
                    active,
                    blk * self.block_size + positions % self.block_size,
                    -1,
                ).astype(np.int32)
                context_lens = (ctx0[lo:hi] + k).astype(np.int32)
                step_ints = np.stack([positions, slot, context_lens])

                hidden = None
                logits = None
                for si, stage in enumerate(self.stages):
                    if stage.first and chain["tokens"] is not None:
                        # sampled on the last stage, consumed on the
                        # first: device-to-device, no host sync
                        tok_in = jax.device_put(
                            chain["tokens"], stage.data_sharding
                        )
                    else:
                        tok_in = chain["tok_placeholder"][si]
                    kwargs = dict(
                        token_ids=tok_in,
                        step_ints=self._stage_put(stage, step_ints),
                        block_tables=chain["tables"][si],
                    )
                    if chain["lora_idx"] is not None:
                        kwargs["lora"] = self._stage_lora[si]
                        kwargs["lora_idx"] = chain["lora_idx"][si]
                    if not stage.first:
                        kwargs["hidden"] = jax.device_put(
                            hidden, stage.data_sharding
                        )
                    out, stage.caches = stage.decode_fn(
                        stage.params, stage.caches, **kwargs
                    )
                    if stage.last:
                        logits = out
                    else:
                        hidden = out

                t_k = dataclasses.replace(
                    chain["tensors"],
                    gen_len=chain["tensors"].gen_len + k,
                )
                seen_rows = jnp.take(self.seen, chain["rows"], axis=0)
                out = sampler_mod.sample(
                    logits, seen_rows, t_k, allowed_mask=chain["allowed"]
                )
                self.seen = sampler_mod.update_seen(
                    self.seen,
                    jnp.asarray(
                        np.where(
                            active, np.asarray(prep.slots)[lo:hi], -1
                        )
                    ),
                    out.tokens,
                )
                chain["outs"].append(out)
                chain["tokens"] = out.tokens  # stays on device

        # pack every chain's K results ON DEVICE into one buffer
        # (sampler.pack_output) and concatenate across chains there
        # too: the host pulls ONE buffer per wave instead of 5 per
        # (chain, step)
        packed_dev = []
        for chain in chains:
            outs = chain["outs"]
            stacked = sampler_mod.SamplerOutput(
                tokens=jnp.stack([o.tokens for o in outs]),
                logprob=jnp.stack([o.logprob for o in outs]),
                rank=jnp.stack([o.rank for o in outs]),
                topn_ids=jnp.stack([o.topn_ids for o in outs]),
                topn_logprobs=jnp.stack([o.topn_logprobs for o in outs]),
            )
            packed_dev.append(sampler_mod.pack_output(stacked))
        host = _HostSamplerOutput.from_packed(
            jnp.concatenate(packed_dev, axis=1)  # [K, B, 3+2W]
        )
        return [
            [host.token(k, i) for k in range(prep.steps_per_seq[i])]
            for i in range(prep.num_seqs)
        ]
