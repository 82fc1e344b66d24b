"""Configuration system: JSON -> typed model parameters + derived named dimensions.

Reproduces the semantics of the reference's ``ModelParameter`` god-object
(/root/reference/src/dataclass.py:34-372) as a plain dataclass-style config with
explicit derivation, without the dict-compat shims.  The whole parallelism
configuration of the reference is two integers (``tpu_size``, ``heads``) that
synthesize a (mesh_shape, layout) pair (dataclass.py:247-252); here the same two
integers synthesize a `jax.sharding.Mesh` axis layout (see parallel/mesh.py),
extended with an optional sequence-parallel axis the reference lacks.
"""
from __future__ import annotations

import dataclasses
import json
import typing

import jax.numpy as jnp

# Canonical logical axis (dimension) names used across the framework.
BATCH = "batch"
SEQUENCE = "sequence"
HEADS = "heads"
KEY = "features_per_head"
INTERMEDIATE = "intermediate"
VOCAB = "vocab"
TOKEN_PATCH = "language_token_patch"
HEIGHT = "height"
WIDTH = "width"
COLOR_CHANNELS = "color_channels"
EXPERTS = "experts"
ROUTED_EXPERTS = "routed_experts"
PKM_AXES = "pkm_axes"
PKM_VALUES = "product_key_value_dim"
# inner geometry of the kda / mla mixers (their heads and head widths differ
# from the stream's), the latent K/V, the low-rank gate pairs, the taps of a
# short convolution and the width of a routed or shared expert
MIXER_HEADS = "mixer_heads"
MIXER_KEY = "mixer_key"
# the K/V heads of grouped-query attention: fewer than its query heads
KV_HEADS = "kv_heads"
# the heads and head width of a sparse attention's indexer (sa_config)
INDEX_HEADS = "index_heads"
INDEX_KEY = "index_key"
LATENT = "latent"
LOW_RANK = "low_rank"
CONV_TAP = "conv_tap"
EXPERT_INTERMEDIATE = "expert_intermediate"
# leading axis of stage-stacked pipeline-parallel body parameters; maps to
# the pipeline mesh axis so each device holds only its stage's weights
PIPE_STAGE = "pipe_stage"

ANON_PREFIX = "_"

# the canonical axis constants above are THE registry the graftcheck
# axis-literal lint validates against (analysis/ast_rules.py); an anonymized
# twin ("_sequence") validates via its base name
from . import nd as _nd  # noqa: E402  (registry import, no cycle: nd is leaf)

_nd.register_axis(BATCH, SEQUENCE, HEADS, KEY, INTERMEDIATE, VOCAB,
                  TOKEN_PATCH, HEIGHT, WIDTH, COLOR_CHANNELS, EXPERTS,
                  ROUTED_EXPERTS, PKM_AXES, PKM_VALUES, PIPE_STAGE,
                  MIXER_HEADS, MIXER_KEY, KV_HEADS, LATENT, LOW_RANK,
                  CONV_TAP, EXPERT_INTERMEDIATE, INDEX_HEADS, INDEX_KEY)


def anonymize_name(name: str) -> str:
    """Leading underscore marks a replicated twin of an axis (reference
    utils_mtf.py:37-54); two tensors may carry both ``sequence`` and
    ``_sequence`` simultaneously (e.g. attention logits)."""
    return name if name.startswith(ANON_PREFIX) else ANON_PREFIX + name


DTYPES = {
    "float32": jnp.float32,
    "bfloat16": jnp.bfloat16,
    "float16": jnp.float16,
    "float64": jnp.float64,
}


# keys of an upstream model's own `config.json` that a configuration file
# carries beside this repo's keys, for the record of what was published:
# nothing reads them (the block DSL says the same), so they are no typo
UPSTREAM_KEYS = frozenset((
    "attention_bias", "decoder_sparse_step", "first_k_dense_replace",
    "gqa_interval", "gqa_layers", "hidden_act", "hidden_size",
    "intermediate_size", "layer_types", "max_position_embeddings",
    "max_window_layers", "mlp_layer_types", "mlp_only_layers",
    "model_max_length", "model_type", "moe_layer_freq", "moe_renormalize",
    "moe_router_activation_func", "n_group", "n_routed_experts",
    "n_shared_experts", "norm_topk_prob", "num_expert_group", "num_experts",
    "num_experts_per_tok", "num_experts_per_token", "num_hidden_layers",
    "num_local_experts", "num_nextn_predict_layers", "num_shared_experts",
    "partial_rotary_factor", "q_lora_rank", "qk_head_dim", "rope_scaling",
    "rope_theta", "scoring_func", "tie_word_embeddings", "topk_group",
    "topk_method", "use_grouped_topk", "use_sliding_window"))


@dataclasses.dataclass
class BlockConfig:
    """One block = list of layer-DSL strings (reference dataclass.py:12-19)."""
    layer: typing.List[str] = dataclasses.field(default_factory=list)
    skip: bool = False
    memory_reduction_strategy: str = "none"

    @classmethod
    def make(cls, conf, strategy: str) -> "BlockConfig":
        if isinstance(conf, BlockConfig):
            return conf
        out = cls(memory_reduction_strategy=strategy)
        for k, v in conf.items():
            setattr(out, k, v)
        return out


@dataclasses.dataclass
class LearningRateConfig:
    start_step: int = 0
    final_step: int = 0
    factor: float = 1.0


_DEFAULTS: typing.Dict[str, typing.Any] = dict(
    # embeddings (reference dataclass.py:38-41)
    position_embedding="absolute",
    token_embedding="absolute",
    empty_frame_embedding="absolute",
    output_embedding="absolute-orthogonal",
    # modes
    use_video=True,
    use_language=True,
    model_mode="jannet",
    contrastive_across_samples=False,
    contrastive_across_token_embeddings=False,
    # io/model shape
    input_dropout=0.0,
    output_offset=1,
    time_patch=1,
    patch_size=16,
    frame_width=320,
    frame_height=176,
    vocab_size=256,
    color_channels=3,
    three_axes=True,
    sequence_length=32,
    heads=8,
    features=None,
    features_per_head=None,
    depth=16,
    token_patch_size=1,
    language_token_per_frame=0,
    padding_token=0,
    concat_token=4,
    # data
    dataset_configs=(),
    data_seed=456772,
    use_random_dataloader=False,
    shuffle_buffer=256,
    interleaved_datasets=256,
    buffer_size=4,
    parallel_interleave=None,
    shuffle_input_filenames=True,
    use_bit_fold_input_pipeline=False,
    bit_fold_value=4,
    color_quantization_value=256,
    # training
    train=True,
    train_batch_size=1,
    grad_accumulation=1,
    macro_batching=1,
    macro_batch_loss_smoothing=False,
    learning_rate=5e-5,
    learning_rate_config=(),
    opt_beta1=0.9,
    opt_beta2=0.999,
    momentum=0.95,
    optimizer="learning_rate",
    weight_decay=0.001,
    weight_centralisation=True,
    weight_standardisation=True,
    rezero_lr_multiplier=0.1,
    train_steps=2 ** 30,
    z_loss=1e-4,
    calc_accuracy=False,
    multi_loss_strategy="linear",
    memory_reduction_strategy="revnet",
    momentumnet_alpha=0.99,
    # jax.checkpoint each reversible block's backward replay: recompute
    # block internals instead of storing residuals — FLOPs for HBM bytes,
    # a win on bandwidth-bound workloads (docs/perf/README.md round 4)
    reversible_remat_blocks=False,
    # fuse the [norm, map-attention, norm, gelu, map-attention] mixer block
    # into one pallas fwd kernel + one full-vjp bwd kernel (the HBM-bytes
    # lever for the bandwidth-bound mixer workloads, ops/pallas_mixer.py).
    # Single-device only: the GSPMD/sharded paths keep the unfused chain.
    fused_mixer_block=False,
    # quantized-compute scope (ops/quant.py, docs/performance.md
    # "Low-precision compute"): layer-scope substrings whose DSL linears run
    # the W8A8 quantized forward (dynamic in-graph scales, f32-accumulated
    # int8/fp8 dot, bf16 backward), e.g. ["bottleneck_group_linear",
    # "/group_linear"].  Empty (default) compiles the exact pre-quant graph
    # — bit-identical loss sequence, parity-tested like telemetry_interval=0.
    # The graftcheck quant-dtype rule pins both directions (a quant op
    # outside the scope, or a declared scope with no quantized dot).
    quant_blocks=(),
    # forward quantization format: "int8" (symmetric, qmax 127) or "fp8"
    # (e4m3, toolchain-gated)
    quant_dtype="int8",
    # recursion depth for the blocked causal map decomposition
    # (models/layers.py::_blocked_map_rows): 0 = plain masked einsum; >0
    # carves the triangle into dense sub-blocks so XLA skips the masked
    # FLOPs — the measured lever for the compute-bound long-context shape
    blocked_causal_map=0,
    debug_train_step=False,
    debug_gradients=False,
    # async-dispatch step loop (main.py, docs/performance.md): up to N
    # dispatched-but-undrained updates may be in flight before the loop
    # blocks on the oldest one's metrics.  0 (or debug_train_step) drains
    # every step synchronously — the parity-reference path.
    async_inflight_steps=2,
    # device-side batch prefetch (data/feed.py::DeviceFeeder): a background
    # thread assembles + H2D-transfers up to N upcoming global batches while
    # the current step runs.  0 assembles inline on the critical path.
    device_prefetch_depth=1,
    # observability (docs/observability.md).  All default-off: disabled runs
    # pay a single ambient-tracer load per instrumented site and the
    # synchronous parity path stays bit-identical.
    # obs_port: >0 serves /metrics (Prometheus text) + /healthz (JSON
    # liveness) on 127.0.0.1:<port> for the run's duration
    obs_port=0,
    # obs_spans: record host spans (step/feed/drain/checkpoint/serve) and
    # export model_path/trace.json (Chrome trace-event JSON, Perfetto-
    # loadable); each span also mirrors into jax.profiler.TraceAnnotation
    obs_spans=False,
    # device telemetry (docs/observability.md "Device telemetry").
    # telemetry_interval: >0 computes in-graph numerics (grad/param/update
    # norms, NaN/Inf sentinels) inside the jitted step EVERY update and
    # writes the norm-class metrics every N updates (sentinels drain every
    # step); 0 = off — the step compiles to the exact pre-telemetry graph,
    # keeping the sync-parity sequence bit-identical.
    telemetry_interval=0,
    # telemetry_groups: param-name substrings; each gets a per-group
    # gradient-norm metric telemetry/grad_norm/<group> (e.g.
    # ["embed", "body", "output"])
    telemetry_groups=(),
    # anomaly_policy: what the NaN/Inf gradient sentinels trigger —
    # "log" (observe only), "skip_step" (mask the optimizer update
    # in-graph and count hbnlp_anomaly_skips_total), "halt" (exit with
    # EXIT_ANOMALY_HALT so a supervisor restarts from the last checkpoint)
    anomaly_policy="log",
    # watchdog_factor: N>0 arms the hang watchdog — when no step completes
    # within N x the EMA step time, thread stacks + device memory stats are
    # dumped to model_path/diagnostics/ (once per stall; never kills the
    # run).  0 disables.
    watchdog_factor=0.0,
    # absolute stall bound BEFORE any step cadence exists (compile /
    # restore / first step): raise it for configs whose cold compile
    # legitimately exceeds 10 minutes, or a /healthz-driven restart loops
    # the compile forever; 0 disables the startup bound entirely
    watchdog_startup_s=600.0,
    # --profile window (main.py): start the jax.profiler trace at update
    # u0+profile_start (must be >= 1: update u0 pays the compile, which
    # would drown steady-state timing) and capture profile_steps updates
    profile_start=3,
    profile_steps=3,
    # fault tolerance (docs/reliability.md).
    # grace_deadline_s: wall budget for the SIGTERM/SIGINT grace shutdown
    # (drain the async loop + cut a final checkpoint); exceeded -> forced
    # exit EXIT_GRACE_TIMEOUT.  0 disables the forced deadline.
    grace_deadline_s=30.0,
    # ckpt_retries: storage retries (exponential backoff) around each
    # checkpoint save/restore/sidecar/manifest operation
    ckpt_retries=2,
    # corrupt_record_budget: >0 skips (and logs + counts) up to N unreadable
    # data records/shards per pipeline instead of dying; 0 = strict fail-fast
    corrupt_record_budget=0,
    # fault_plan: fault-injection spec for chaos tests, e.g.
    # "ckpt_write:fail@2;feeder:die@step10;sigterm@step25"
    # (grammar in reliability/faults.py; HBNLP_FAULT_PLAN env var when empty)
    fault_plan="",
    # elastic multi-host training (docs/reliability.md "Multi-host
    # elasticity"; reliability/dist.py).  All dist_* knobs are overridden by
    # the HBNLP_DIST_COORDINATOR / HBNLP_DIST_NUM_PROCESSES /
    # HBNLP_DIST_PROCESS_ID env vars so one config file serves every host —
    # the per-host supervisor injects the rank into its child's env.
    # dist_coordinator: "host:port" of the jax.distributed coordinator
    # (rank 0's address); "" with dist_num_processes <= 1 = single-host
    dist_coordinator="",
    # dist_num_processes: fleet size; <= 1 disables multi-host init entirely
    dist_num_processes=0,
    # dist_process_id: this host's rank in [0, dist_num_processes)
    dist_process_id=0,
    # dist_init_timeout_s: wall deadline across ALL initialize() retry
    # attempts (coordinator-unreachable backoff); each attempt gets a
    # deadline/(retries+1) slice as its jax initialization_timeout so the
    # retry path engages even against a slow coordinator.  Default matches
    # jax's own 300s join timeout: a fleet whose hosts boot minutes apart
    # must not burn its supervisors' crash-loop budget waiting.
    # 0 = attempts-only budget
    dist_init_timeout_s=300.0,
    # dist_init_retries: retries (exponential backoff) after the first
    # failed jax.distributed.initialize attempt
    dist_init_retries=3,
    # dist_barrier_timeout_s: default bound on reliability.dist.barrier();
    # an absent peer raises PeerLost (exit 87) instead of hanging forever
    dist_barrier_timeout_s=60.0,
    # fleet_dir: SHARED directory for cross-rank fleet observability
    # (docs/observability.md "Fleet observability"): each rank posts
    # per-step dispatch timestamps, /metrics snapshots, and its span trace
    # under <fleet_dir>/obs/ for federation + straggler attribution.
    # Overridden by HBNLP_FLEET_DIR (the supervisor injects its
    # --fleet-dir).  "" = off: single-process runs stay byte-identical.
    fleet_dir="",
    current_step=0,
    steps_per_checkpoint=100_000,
    use_checkpointing=False,
    max_checkpoints_keep=1,
    model_path="runs/default",
    # serving codec for tools/train_tokenizer.py artifacts: when set, the
    # query/REST/sample text paths encode+decode through this tokenizer
    # (serve/interface.py::HbnlpBpeTokenizer) instead of bytes/GPT-2
    tokenizer_path="",
    # None = the reference's rule (only use_random_dataloader repeats,
    # inputs.py:540-541); true forces deterministic epoch wrap-around on
    # the sequential reader, false forces single-epoch
    repeat_dataset=None,
    # dtypes (storage/compute/optimizer policy; reference dataclass.py:82-86)
    storage_dtype="float32",
    slice_dtype="float32",
    calculation_dtype="float32",
    optimizer_slice_dtype="float32",
    optimizer_calculation_dtype="float32",
    # architecture knobs
    group_linear_factor=2,
    intermediate_feed_forward_multiplier=None,
    intermediate_feed_forward_multiplier_multiplier=None,
    embedding_stddev=0.04,
    experts=64,
    moe_balance_weight=0.01,  # routed_moe load-balance aux loss (extension)
    # routed_moe under expert parallelism: `experts` is how many the router
    # scores; this process holds `experts_held` of them (None = all),
    # starting at `expert_offset`, and computes their part of the result
    experts_held=None,
    expert_offset=0,
    moe_intermediate_size=None,  # width of one expert (None = intermediate)
    routed_scaling_factor=1.0,
    rms_norm_eps=1e-5,
    # kda: {"num_heads", "head_dim", "short_conv_kernel_size"} as upstream
    # names them (the two low-rank gate pairs take head_dim as their rank);
    # `kda_allow_neg_eigval`: beta = 2 * sigmoid(.), so that the eigenvalues
    # of I - beta k k^T lie in (-1, 1); `kda_use_full_proj` true (full-rank
    # gates in the low-rank pairs' place) is not written and is refused
    linear_attn_config=None,
    kda_allow_neg_eigval=False,
    kda_use_full_proj=False,
    # mla (latent K/V attention over `num_attention_heads` heads, None =
    # heads): `mla_use_nope` true is the layer without positions (`mla`),
    # false the one whose decoupled query and key parts are turned by the
    # default rotary table at `rope_theta` (`mla-rope`), in pairs
    # (x_2i, x_2i+1) under `rope_interleave`, else rotate-half; upstream's
    # names and defaults (DeepSeek-V3 has no `mla_use_nope` and rotates)
    kv_lora_rank=None,
    qk_nope_head_dim=None,
    qk_rope_head_dim=None,
    v_head_dim=None,
    mla_use_nope=False,
    rope_interleave=True,
    # gqa (softmax attention over grouped K/V heads, rotary positions), as
    # upstream names them: `num_attention_heads` query heads (None = heads)
    # of `head_dim` read `num_key_value_heads` K/V heads; `rope_parameters`
    # maps a layer type ("sliding_attention", "full_attention") to its
    # rotary table's {"rope_type": "default" | "yarn", "rope_theta", ...};
    # a sliding layer sees the last `sliding_window` positions.  `use_rope`
    # false: no rotation at all, and the block part names no table
    # (`gqa-nope`); `use_gqa_gate`: sigmoid(u W_g), one gate a channel of
    # every head, on the attention's result before the output matrix
    # (`gqa-...-gated`).  Where `rope_parameters` is not given and
    # upstream's `rope_scaling` is, `full_attention` takes `rope_theta` and
    # that entry (`mrope_section`: text positions, one table).  `sa_config`:
    # upstream's sparse attention ({"indexer_num_heads", "indexer_head_dim",
    # "indexer_num_kv_heads", "topk", "q_chunk_size", "kv_chunk_size"}),
    # which a `gqa-...-sparse` layer reads
    num_attention_heads=None,
    num_key_value_heads=None,
    head_dim=None,
    rope_parameters=None,
    sliding_window=None,
    use_rope=True,
    use_gqa_gate=False,
    sa_config=None,
    # false: the table holds one stream-wide row a token, no factorisation
    factorized_embedding=True,
    # which block_config entries run at which depth: one list of indices a
    # depth (None = every entry, in order, at every depth)
    block_schedule=None,
    pkm_axes=2,
    convolution_size=16,
    scale_by_depth=True,
    use_initial_position_embedding=False,
    vocab_weight_factorization=0.125,
    masked_attention_dimensions=(0,),
    block_config=(
        {"layer": ["norm-group-shift-scale", "feed_forward-in_relu-group-in_glu_add-in_norm"]},
        {"layer": ["norm-group-std-shift-scale", "attention-in_relu-embedded-relative"]},
    ),
    input_block_config=(),
    output_block_config=(),
    # intended deployment device kind ("v5e", "v4", "v5p", ... — see
    # homebrewnlp_tpu/devices.py) for the static cost model
    # (docs/static_analysis.md "Resource cost model"): when set, the
    # graftcheck resource-budget rule HARD-FAILS any config whose predicted
    # per-device peak HBM exceeds this device's capacity — the OOM surfaces
    # in CI seconds instead of after a ~2-minute TPU compile.  "" (default)
    # skips the capacity gate; predictions and the roofline verdict are
    # still recorded against the default verdict device.
    target_device="",
    # how deep into the mesh searcher's ranked sheet the committed
    # hand-written mesh may sit before graftcheck's mesh-rank rule fails
    # (docs/static_analysis.md "Mesh search"); 1 = the hand mesh must BE
    # the searcher's (possibly tied) top pick
    mesh_search_top_k=3,
    # parallelism (the reference's two knobs, plus TPU-native extensions)
    tpu_size=32,
    sequence_parallel=1,  # extension: size of the sequence-parallel mesh axis
    pipeline_parallel=1,  # extension: pipeline stages over the pipeline axis
    # "gpipe": all-forward scan + autodiff backward (residuals grow with the
    # microbatch count M).  "1f1b": interleaved schedule computing loss and
    # grads in one scan with a 2P-deep input stash, M-independent activation
    # memory (ops/pipeline.py::pipeline_1f1b)
    pipeline_schedule="gpipe",
    # sampling / serving
    initial_autoregressive_position=128,
    use_autoregressive_sampling=False,
    sampling_temperature=0.0,
    # extension: truncated sampling (the reference only has temperature).
    # top_k=0 and top_p=1.0 disable truncation; both knobs are compile-time
    # static (changing them recompiles the sampler).
    sampling_top_k=0,
    sampling_top_p=1.0,
    num_of_sample=10,
    web_workers=1,
    # serving SLO knobs (docs/observability.md "Serving SLOs").
    # serve_queue_deadline_s: a request whose ENGINE-QUEUE wait exceeds this
    # is rejected (REST: 503 + Retry-After) instead of hanging the client
    # behind the serialized engine; 0 = wait forever (the reference's
    # Manager-queue behavior)
    serve_queue_deadline_s=0.0,
    # serve_queue_limit: >0 sheds load at ADMISSION — a completion request
    # arriving with this many requests already queued is rejected
    # immediately (REST: 503 + Retry-After) without waiting out the
    # deadline; 0 = unbounded queue
    serve_queue_limit=0,
    # continuous-batching engine (docs/observability.md "Continuous
    # batching").  serve_max_batch: decode lanes sharing one persistent
    # decode loop — >1 replaces the serialized InterfaceWrapper with the
    # serve/engine.py scheduler (requests admitted BETWEEN decode steps);
    # 1 (default) keeps the reference-shaped serialized path bit-identical
    serve_max_batch=1,
    # serve_block_tokens: tokens per KV-pool block (must be a multiple of
    # token_patch_size so blocks hold whole decode rows); 0 = one
    # whole-sequence block per lane, which makes the pool byte-identical
    # to the monolithic per-lane cache
    serve_block_tokens=0,
    # serve_kv_blocks: total blocks in the fixed-capacity KV pool shared
    # by all lanes — admission takes a request's whole block footprint up
    # front and recycles it on completion; 0 = auto
    # (serve_max_batch x blocks-per-sequence, i.e. the physical pool)
    serve_kv_blocks=0,
    # serve_prefill_chunk_tokens: >0 splits admission prefill into chunks of
    # this many tokens (must be a multiple of the KV-block size, i.e. of
    # serve_block_tokens when paged, else token_patch_size), dispatched
    # asynchronously between decode steps so a long prompt admits over N
    # loop iterations while occupied lanes keep decoding
    # (docs/observability.md "Streaming and inter-token latency");
    # 0 = monolithic admission prefill on the decode thread — byte-identical
    # graphs, census/spmd goldens untouched
    serve_prefill_chunk_tokens=0,
    # serve_aot_cache_dir: directory for serialized prefill/decode
    # executables keyed by config hash + mesh + toolchain — a second
    # server start deserializes instead of re-compiling (cold start in
    # seconds, not minutes); "" = AOT executable serialization off
    serve_aot_cache_dir="",
    # serve_stream: honor `stream: true` on the completion endpoints (SSE
    # token streaming, docs/observability.md "Streaming and inter-token
    # latency"); requests without the flag are byte-identical either way.
    # False keeps the serialized samplers' graphs free of the per-row
    # token callback and buffers every response.
    serve_stream=True,
    # serve_trace_path: Chrome-trace JSON of the serving engine's decode
    # loop (per-phase spans + per-lane occupancy tracks + request phase
    # trails), exported when the engine closes — and, while the flight
    # recorder is on (flight_buffer_spans > 0), ROTATED into rolling
    # <path>.NNN.json segments whenever the span ring fills, so a crash
    # loses at most one ring of spans; "" = serving trace off
    serve_trace_path="",
    # slo_objectives: declared serving SLOs, evaluated per finished
    # request by obs/slo_alerts.py into fast/slow-window burn rates
    # (hbnlp_slo_burn_rate{objective,window} + the /healthz "alerts"
    # block), e.g. {"ttft_p95_s": 2.0, "error_rate": 0.01}.  Keys are
    # "error_rate" (value = the error budget itself) or "<metric>_p<NN>_s"
    # with metric in ttft/e2e/queue_wait (value = the latency threshold;
    # error budget = 1 - NN/100); {} = SLO alerting off
    slo_objectives={},
    # flight_buffer_spans: span capacity of the serving flight recorder's
    # ring (obs/flight.py): recent spans + last-N request trails + metric
    # snapshots held in bounded memory, written as a self-contained
    # incident bundle to <model_path>/diagnostics/ when a trigger fires
    # (flight_dump_triggers); also caps the serve_trace_path tracer and
    # arms its rolling-segment rotation; 0 = flight recorder off
    flight_buffer_spans=4096,
    # flight_dump_triggers: which events write a flight bundle — any
    # subset of ("watchdog", "error", "slo", "manual"): watchdog stall,
    # 5xx response, an SLO burn-rate alert firing, or POST /debugz/dump
    flight_dump_triggers=("watchdog", "error", "slo", "manual"),
    # multi-replica serving (docs/reliability.md "Serving resilience").
    # serve_replicas: engine replica processes tools/graftserve.py spawns
    # behind the health-aware router; 1 = a single replica (the router is
    # still useful for drain/failover semantics, but optional)
    serve_replicas=1,
    # router_port: >0 runs the health-aware replica router
    # (serve/router.py) on this port in front of the replica set;
    # 0 = no router (clients hit a replica directly)
    router_port=0,
    # router_health_interval_s: seconds between the router's /healthz
    # polls of each replica — a replica reporting stalled, draining,
    # firing SLO alerts, or a full KV pool is shed to healthy peers
    router_health_interval_s=1.0,
    # router_health_timeout_s: per-poll HTTP timeout; a wedged healthz
    # endpoint (the replica:wedge_healthz chaos action) reads as
    # unhealthy after this long instead of hanging the health watcher
    router_health_timeout_s=2.0,
    # router_failover_retries: additional replicas tried after a replica
    # death (connection refused, 5xx, or a mid-stream disconnect BEFORE
    # the first SSE token), preserving the client's X-Request-Id; once
    # any response byte has been forwarded, retries are never attempted
    # (at-most-once delivery past the first token)
    router_failover_retries=1,
    # serve_watchdog_min_stall_s: floor of the serving decode-loop
    # watchdog's stall threshold (watchdog_factor x the EMA scheduler
    # iteration time, never below this floor) — the serving twin of the
    # train watchdog; armed only when watchdog_factor > 0
    serve_watchdog_min_stall_s=1.0,
    # per-tenant usage metering (obs/usage.py; docs/observability.md
    # "Usage metering & capacity").  usage_top_k: tenants tracked EXACTLY
    # by the Misra-Gries sketch; the long tail folds into tenant="other"
    # so /metrics cardinality stays bounded at top_k+1 rows no matter how
    # many distinct tenants arrive; 0 = metering off
    usage_top_k=32,
    # usage_tenant_header: the request header carrying the tenant
    # identity; values failing the validation charset (or missing) meter
    # as tenant="anon"
    usage_tenant_header="X-Tenant",
    equal_debugging_items_per_check=16,
    debug_sample=False,
    default_sleep_duration=0.1,
)


class Config:
    """Typed hyperparameter store with validation + derived dimension registry.

    ``dims`` maps logical axis names to sizes — the JAX-side replacement for the
    reference's mtf.Dimension zoo (dataclass.py:273-341)."""

    def __init__(self, config: typing.Optional[dict] = None):
        self.__dict__.update(_DEFAULTS)
        config = dict(config or {})
        for k, v in config.items():
            if (k not in _DEFAULTS and k not in ("mesh_shape", "layout")
                    and k not in UPSTREAM_KEYS):
                print(f"WARNING: Unknown Config parameter {k}={v!r}")
            setattr(self, k, v)
        self._validate_and_derive()

    @classmethod
    def from_json(cls, path: str) -> "Config":
        with open(path) as f:
            return cls(json.load(f))

    # -- derivation ---------------------------------------------------------
    def _validate_and_derive(self) -> None:
        # macro_batching inflates the host batch by M (reference
        # dataloader_placement.py:40-44); grad_accumulation splits each
        # configured batch into G micro-slices.  The train step scans M*G
        # micro-batches per optimizer update (train/state.py).
        assert self.macro_batching > 0
        assert self.grad_accumulation > 0
        if self.async_inflight_steps < 0:
            raise ValueError("async_inflight_steps must be >= 0 "
                             "(0 = synchronous drain every step)")
        if self.device_prefetch_depth < 0:
            raise ValueError("device_prefetch_depth must be >= 0 "
                             "(0 = inline batch assembly)")
        if int(self.obs_port) < 0:
            raise ValueError("obs_port must be >= 0 (0 = exporter disabled)")
        if int(self.telemetry_interval) < 0:
            raise ValueError("telemetry_interval must be >= 0 "
                             "(0 = device telemetry disabled)")
        self.telemetry_interval = int(self.telemetry_interval)
        self.telemetry_groups = [str(g) for g in self.telemetry_groups]
        from .obs.device_telemetry import ANOMALY_POLICIES
        if self.anomaly_policy not in ANOMALY_POLICIES:
            raise ValueError(
                f"unknown anomaly_policy {self.anomaly_policy!r}; expected "
                f"one of {ANOMALY_POLICIES}")
        if isinstance(self.quant_blocks, str):
            # a bare string would iterate per-CHARACTER below and silently
            # quantize nearly every linear via single-letter substrings
            raise ValueError(
                "quant_blocks must be a list of layer-scope substrings, "
                f"not a string (got {self.quant_blocks!r}; write "
                f"[{self.quant_blocks!r}])")
        self.quant_blocks = [str(b) for b in self.quant_blocks]
        if any(not b for b in self.quant_blocks):
            raise ValueError("quant_blocks entries must be non-empty layer-"
                             "scope substrings (e.g. 'bottleneck_group_"
                             "linear'); got an empty string")
        from .ops.quant import QUANT_DTYPES
        if self.quant_dtype not in QUANT_DTYPES:
            raise ValueError(
                f"unknown quant_dtype {self.quant_dtype!r}; this toolchain "
                f"supports {sorted(QUANT_DTYPES)}")
        self.target_device = str(self.target_device or "")
        if self.target_device:
            # a typoed device kind would silently skip the OOM-before-compile
            # gate; surface it at config load (devices.py is a leaf import)
            from .devices import resolve_device
            try:
                resolve_device(self.target_device)
            except ValueError as e:
                raise ValueError(f"target_device: {e} (or \"\" to skip the "
                                 f"HBM capacity gate)") from None
        if int(self.mesh_search_top_k) < 1:
            raise ValueError("mesh_search_top_k must be >= 1 (the rank the "
                             "hand-written mesh must reach in the searcher's "
                             "sheet)")
        self.mesh_search_top_k = int(self.mesh_search_top_k)
        if float(self.serve_queue_deadline_s) < 0:
            raise ValueError("serve_queue_deadline_s must be >= 0 "
                             "(0 = requests wait in the engine queue forever)")
        self.serve_queue_deadline_s = float(self.serve_queue_deadline_s)
        if int(self.serve_queue_limit) < 0:
            raise ValueError("serve_queue_limit must be >= 0 "
                             "(0 = unbounded engine queue)")
        self.serve_queue_limit = int(self.serve_queue_limit)
        if int(self.serve_max_batch) < 1:
            raise ValueError("serve_max_batch must be >= 1 (1 = the "
                             "serialized reference-shaped engine; >1 = the "
                             "continuous-batching scheduler)")
        self.serve_max_batch = int(self.serve_max_batch)
        if int(self.serve_block_tokens) < 0:
            raise ValueError("serve_block_tokens must be >= 0 "
                             "(0 = one whole-sequence block per lane)")
        self.serve_block_tokens = int(self.serve_block_tokens)
        if (self.serve_block_tokens
                and self.serve_block_tokens % self.token_patch_size):
            raise ValueError(
                f"serve_block_tokens={self.serve_block_tokens} must be a "
                f"multiple of token_patch_size={self.token_patch_size} "
                "(KV-pool blocks hold whole decode rows)")
        if int(self.serve_kv_blocks) < 0:
            raise ValueError("serve_kv_blocks must be >= 0 "
                             "(0 = auto: serve_max_batch x blocks per "
                             "sequence)")
        self.serve_kv_blocks = int(self.serve_kv_blocks)
        if self.serve_kv_blocks:
            # the pool must admit at least one full-length request, or every
            # completion sheds at admission forever — surface the dead pool
            # at config load, not in production 503s
            from .infer.kv_cache import blocks_per_sequence
            need = blocks_per_sequence(self)
            if self.serve_kv_blocks < need:
                raise ValueError(
                    f"serve_kv_blocks={self.serve_kv_blocks} cannot hold one "
                    f"full-length sequence ({need} blocks of "
                    f"{self.serve_block_tokens or self.sequence_length} "
                    "tokens); raise serve_kv_blocks or serve_block_tokens")
        if int(self.serve_prefill_chunk_tokens) < 0:
            raise ValueError("serve_prefill_chunk_tokens must be >= 0 "
                             "(0 = monolithic admission prefill)")
        self.serve_prefill_chunk_tokens = int(self.serve_prefill_chunk_tokens)
        if self.serve_prefill_chunk_tokens:
            # chunks scatter-write whole KV-pool blocks at the lane's running
            # position; a chunk that straddles a block boundary would split a
            # block across two asynchronous dispatches
            unit = self.serve_block_tokens or self.token_patch_size
            if self.serve_prefill_chunk_tokens % unit:
                raise ValueError(
                    f"serve_prefill_chunk_tokens="
                    f"{self.serve_prefill_chunk_tokens} must be a multiple of "
                    f"the KV-block size ({unit} = "
                    + ("serve_block_tokens" if self.serve_block_tokens
                       else "token_patch_size")
                    + "); chunks scatter whole blocks")
        self.serve_aot_cache_dir = str(self.serve_aot_cache_dir or "")
        self.serve_stream = bool(self.serve_stream)
        self.serve_trace_path = str(self.serve_trace_path or "")
        if not isinstance(self.slo_objectives, dict):
            raise ValueError(
                "slo_objectives must be a dict of objective -> threshold, "
                'e.g. {"ttft_p95_s": 2.0, "error_rate": 0.01} '
                "({} = SLO alerting off)")
        if self.slo_objectives:
            # surface a typoed objective at config load, not as a silently
            # never-firing alert; validate_objectives raises ValueError
            # naming the bad key/threshold
            from .obs.slo_alerts import validate_objectives
            self.slo_objectives = validate_objectives(self.slo_objectives)
        if int(self.flight_buffer_spans) < 0:
            raise ValueError("flight_buffer_spans must be >= 0 "
                             "(0 = flight recorder off)")
        self.flight_buffer_spans = int(self.flight_buffer_spans)
        if isinstance(self.flight_dump_triggers, str):
            # a bare string would iterate characters and silently disable
            # every real trigger — same guard as quant_blocks
            raise ValueError(
                "flight_dump_triggers must be a sequence of trigger names, "
                "not a string")
        triggers = tuple(str(t) for t in self.flight_dump_triggers)
        from .obs.flight import DUMP_TRIGGERS
        bad = [t for t in triggers if t not in DUMP_TRIGGERS]
        if bad:
            raise ValueError(
                f"flight_dump_triggers has unknown trigger(s) {bad}; "
                f"known: {sorted(DUMP_TRIGGERS)}")
        self.flight_dump_triggers = triggers
        if int(self.serve_replicas) < 1:
            raise ValueError("serve_replicas must be >= 1 "
                             "(the number of engine replica processes)")
        self.serve_replicas = int(self.serve_replicas)
        if int(self.router_port) < 0:
            raise ValueError("router_port must be >= 0 (0 = no router)")
        self.router_port = int(self.router_port)
        if float(self.router_health_interval_s) <= 0:
            raise ValueError("router_health_interval_s must be > 0 "
                             "(seconds between replica /healthz polls)")
        self.router_health_interval_s = float(self.router_health_interval_s)
        if float(self.router_health_timeout_s) <= 0:
            raise ValueError("router_health_timeout_s must be > 0 "
                             "(per-poll HTTP timeout)")
        self.router_health_timeout_s = float(self.router_health_timeout_s)
        if int(self.router_failover_retries) < 0:
            raise ValueError("router_failover_retries must be >= 0 "
                             "(extra replicas tried before giving up)")
        self.router_failover_retries = int(self.router_failover_retries)
        if float(self.serve_watchdog_min_stall_s) <= 0:
            raise ValueError("serve_watchdog_min_stall_s must be > 0 "
                             "(the decode-loop stall threshold floor)")
        self.serve_watchdog_min_stall_s = float(
            self.serve_watchdog_min_stall_s)
        if int(self.usage_top_k) < 0:
            raise ValueError("usage_top_k must be >= 0 "
                             "(0 = usage metering off)")
        self.usage_top_k = int(self.usage_top_k)
        self.usage_tenant_header = str(self.usage_tenant_header
                                       or "X-Tenant")
        if self.watchdog_factor < 0:
            raise ValueError("watchdog_factor must be >= 0 "
                             "(0 = watchdog disabled)")
        if self.watchdog_startup_s < 0:
            raise ValueError("watchdog_startup_s must be >= 0 "
                             "(0 = no startup stall bound)")
        if self.profile_start < 1:
            raise ValueError(
                "profile_start must be >= 1: update 0 pays the XLA compile, "
                "so a window starting there would not capture steady state")
        if self.profile_steps < 1:
            raise ValueError("profile_steps must be >= 1")
        if self.grace_deadline_s < 0:
            raise ValueError("grace_deadline_s must be >= 0 "
                             "(0 = no forced deadline on grace shutdown)")
        if self.ckpt_retries < 0:
            raise ValueError("ckpt_retries must be >= 0 (0 = single attempt)")
        self.dist_coordinator = str(self.dist_coordinator or "")
        self.dist_num_processes = int(self.dist_num_processes)
        self.dist_process_id = int(self.dist_process_id)
        if self.dist_num_processes < 0:
            raise ValueError("dist_num_processes must be >= 0 "
                             "(<= 1 = single-host, no distributed init)")
        if self.dist_process_id < 0:
            raise ValueError("dist_process_id must be >= 0")
        if (self.dist_num_processes > 1
                and self.dist_process_id >= self.dist_num_processes):
            raise ValueError(
                f"dist_process_id={self.dist_process_id} out of range for "
                f"dist_num_processes={self.dist_num_processes}")
        if self.dist_coordinator and self.dist_num_processes == 0:
            # the inverse (world without coordinator) already fails in
            # dist.settings(); a coordinator with no world would silently
            # train N independent models over one model_path instead
            raise ValueError(
                f"dist_coordinator={self.dist_coordinator!r} set but "
                "dist_num_processes is 0: set the fleet size (1 for a "
                "single-process pod slice) or clear the coordinator")
        if float(self.dist_init_timeout_s) < 0:
            raise ValueError("dist_init_timeout_s must be >= 0 "
                             "(0 = no wall deadline on distributed init)")
        self.dist_init_timeout_s = float(self.dist_init_timeout_s)
        if int(self.dist_init_retries) < 0:
            raise ValueError("dist_init_retries must be >= 0 "
                             "(0 = single initialize attempt)")
        self.dist_init_retries = int(self.dist_init_retries)
        if float(self.dist_barrier_timeout_s) < 0:
            raise ValueError("dist_barrier_timeout_s must be >= 0")
        self.dist_barrier_timeout_s = float(self.dist_barrier_timeout_s)
        self.fleet_dir = str(self.fleet_dir or "")
        if self.corrupt_record_budget < 0:
            raise ValueError("corrupt_record_budget must be >= 0 "
                             "(0 = fail fast on any unreadable record)")
        if self.fault_plan:
            # surface a typoed plan at config load, not mid-run; parse_plan
            # raises ValueError naming the bad entry
            from .reliability.faults import parse_plan
            rules = parse_plan(self.fault_plan)
            if (any(r.site == "grads" for r in rules)
                    and self.telemetry_interval <= 0):
                # the grads site is polled by the loop only when device
                # telemetry is on — a silently-inert chaos drill would
                # report success while testing nothing
                raise ValueError(
                    "fault_plan uses the 'grads' site, which requires "
                    "telemetry_interval > 0 (the injection rides the "
                    "telemetry grad_scale input)")

        for attr in ("position_embedding", "token_embedding", "output_embedding",
                     "empty_frame_embedding"):
            v = getattr(self, attr)
            if isinstance(v, str):
                setattr(self, attr, v.split("-"))

        self.learning_rate_config = {
            k: v if isinstance(v, LearningRateConfig) else LearningRateConfig(**v)
            for k, v in dict(self.learning_rate_config).items()}

        for attr in ("storage_dtype", "slice_dtype", "calculation_dtype",
                     "optimizer_slice_dtype", "optimizer_calculation_dtype"):
            v = getattr(self, attr)
            if isinstance(v, str):
                setattr(self, attr, DTYPES[v])

        if self.model_mode == "gpt":
            # text-only path: language on, video off (reference src/main.py:85-92)
            self.use_video = False
            self.use_language = True
        self.multi_loss_strategy = self.multi_loss_strategy.lower()
        if self.multi_loss_strategy not in ("linear", "pcgrad", "mgda"):
            print(f"unknown multi_loss_strategy {self.multi_loss_strategy}; using linear")
            self.multi_loss_strategy = "linear"
        if not self.use_language and not self.use_video:
            raise ValueError("Language and video mode are both disabled")
        if self.sampling_top_k < 0 or self.sampling_top_k > self.vocab_size:
            raise ValueError(
                f"sampling_top_k must be in [0, vocab_size]; got "
                f"{self.sampling_top_k}")
        if not 0.0 < self.sampling_top_p <= 1.0:
            raise ValueError(
                f"sampling_top_p must be in (0, 1]; got {self.sampling_top_p}")
        # GPipe pipeline parallelism (ops/pipeline.py): stages must cut the
        # depth loop evenly and compose with none/checkpoint rematerialization
        # only (reversible chains carry custom_vjp state across stages).
        # The sequence-parallel ring COMPOSES since round 5 — it nests a
        # seq-manual shard_map inside the pipe-manual region (ops/ring.py) —
        # but only under the 1f1b schedule: its per-tick jax.vjp runs the
        # ring's backward immediately, whereas jax.grad THROUGH the gpipe
        # scan delays it, and delayed partial evaluation hoists the ring
        # backward's seq-manual internals across the scan boundary where the
        # partitioner cannot express them (sdy rejects the factor order).
        if self.pipeline_parallel < 1:
            raise ValueError("pipeline_parallel must be a positive integer")
        if self.pipeline_schedule not in ("gpipe", "1f1b"):
            # validated regardless of pipeline_parallel so a typo surfaces
            # before the user scales up
            raise ValueError(
                f"unknown pipeline_schedule {self.pipeline_schedule!r}; "
                "expected 'gpipe' or '1f1b'")
        body_specs = [spec for blk in self.block_config
                      for spec in (blk["layer"] if isinstance(blk, dict)
                                   else blk.layer)]
        if self.pipeline_parallel > 1:
            if self.depth % self.pipeline_parallel:
                raise ValueError("pipeline_parallel must divide depth")
            if self.memory_reduction_strategy not in ("none", "checkpoint"):
                raise ValueError(
                    "pipeline_parallel requires memory_reduction_strategy "
                    "'none' or 'checkpoint'")
            if self.sequence_parallel > 1 and self.pipeline_schedule != "1f1b":
                raise ValueError(
                    "sequence_parallel with pipeline_parallel requires "
                    "pipeline_schedule='1f1b' (gradients through the gpipe "
                    "scan cannot express the nested ring attention's "
                    "backward — see the validation comment above)")
            if self.use_video:
                raise ValueError(
                    "pipeline_parallel supports text (gpt) models only: the "
                    "multi-axis attention rotation depends on the global "
                    "depth index, which is dynamic inside a pipeline stage")
            # cross-depth 'shared' weights compose since round 4: the tensor
            # is replicated per stage and its grad stage-summed
            # (models.sync_shared_pipeline_grads), preserving exact sharing
            # semantics — the flagship's shared mixer maps can pipeline
            if (any(s.split("-")[0] == "routed_moe" for s in body_specs)
                    and self.pipeline_schedule != "1f1b"
                    and self.moe_balance_weight > 0):
                raise ValueError(
                    "pipeline_parallel under the gpipe schedule cannot carry "
                    "the routed_moe balance aux loss across the pipeline "
                    "shard_map boundary; use pipeline_schedule='1f1b' (the "
                    "aux loss rides the schedule's stage stream) or set "
                    "moe_balance_weight=0")
            if self.pipeline_schedule == "1f1b":
                # the loss rides inside the 1F1B schedule (the last stage's
                # tail seeds each microbatch's backward), which constrains
                # what the tail can compute in v1
                if self.multi_loss_strategy != "linear":
                    raise ValueError(
                        "pipeline_schedule='1f1b' supports the linear "
                        "multi-loss strategy only")
                if (self.contrastive_across_samples
                        or self.contrastive_across_token_embeddings):
                    raise ValueError(
                        "pipeline_schedule='1f1b' does not support "
                        "contrastive losses (they need the stashed input "
                        "embedding outside the schedule)")
        # routed_moe's load-balance aux loss cannot cross the reversible
        # custom_vjp boundary (models/__init__.py _body); 'none' collects it
        # directly and 'checkpoint' threads it through jax.checkpoint as a
        # real output, but revnet/momentum would silently drop it — reject
        # rather than train with different semantics than the config names.
        if self.moe_balance_weight > 0 and self.memory_reduction_strategy in (
                "revnet", "momentum"):
            if any(s.split("-")[0] == "routed_moe" for s in body_specs):
                raise ValueError(
                    f"routed_moe with moe_balance_weight > 0 cannot combine "
                    f"with memory_reduction_strategy="
                    f"'{self.memory_reduction_strategy}': the balance aux "
                    f"loss cannot cross the reversible custom_vjp boundary. "
                    f"Use 'none' or 'checkpoint', or set "
                    f"moe_balance_weight=0 to train without the balance term")
        # a sparse gqa's indexer learns from its KL loss alone, which rides
        # ctx.aux_losses as the balance loss does (with the dsa_* counters):
        # the reversible chain and the pipelined body drop it, and the
        # indexer would never learn
        if any(s.split("-")[0] == "gqa" and "sparse" in s.split("-")[1:]
               for s in body_specs) and (
                self.memory_reduction_strategy in ("revnet", "momentum")
                or self.pipeline_parallel > 1):
            raise ValueError(
                f"gqa-...-sparse cannot combine with memory_reduction_strategy="
                f"'{self.memory_reduction_strategy}' or pipeline_parallel="
                f"{self.pipeline_parallel}: the indexer's KL loss, its only "
                f"gradient, cannot cross the reversible custom_vjp boundary "
                f"or the pipeline's stages. Use 'none' or 'checkpoint' "
                f"without pipeline_parallel")
        if self.weight_standardisation and not self.weight_centralisation:
            self.weight_centralisation = True
        if self.features is None and self.features_per_head is None:
            raise ValueError("Either features or features_per_head must be given")
        if self.features is None:
            self.features = self.features_per_head * self.heads
        if self.features_per_head is None:
            self.features_per_head = self.features // self.heads
        if self.use_video and (self.frame_width * self.frame_height // self.patch_size) % self.experts:
            raise ValueError("Frame size must be divisible by expert count")
        if self.use_video and self.use_language and self.three_axes:
            # joint mode concatenates text along the video's "height" axis,
            # which requires the flattened (height*width) video layout — the
            # reference implicitly requires the same (dataclass.py:334 names
            # the token patch-count dim "height"; mtf.concat would reject the
            # extra width axis)
            print("WARNING: three_axes disabled — joint video+language mode "
                  "requires the flattened spatial layout")
            self.three_axes = False
        if self.intermediate_feed_forward_multiplier_multiplier is not None:
            self.intermediate_feed_forward_multiplier = (
                self.group_linear_factor
                * self.intermediate_feed_forward_multiplier_multiplier / self.heads)
        if self.intermediate_feed_forward_multiplier is None:
            self.intermediate_feed_forward_multiplier = self.group_linear_factor / self.heads
        if not self.use_video and self.language_token_per_frame != self.sequence_length:
            self.language_token_per_frame = self.sequence_length

        self.masked_attention_dimensions = list(self.masked_attention_dimensions)
        self.block_config = [BlockConfig.make(c, self.memory_reduction_strategy)
                             for c in self.block_config]
        self.input_block_config = [BlockConfig.make(c, "checkpoint")
                                   for c in self.input_block_config]
        self.output_block_config = [BlockConfig.make(c, "checkpoint")
                                    for c in self.output_block_config]
        every = list(range(len(self.block_config)))
        if self.block_schedule is None:
            self.block_schedule = [every] * self.depth
        self.block_schedule = [[int(c) for c in row]
                               for row in self.block_schedule]
        if len(self.block_schedule) != self.depth or any(
                c not in every for row in self.block_schedule for c in row):
            raise ValueError(
                f"block_schedule needs one list of block_config indices "
                f"(0..{len(every) - 1}) for each of the {self.depth} depths")
        if self.block_schedule != [every] * self.depth and (
                self.pipeline_parallel > 1):
            raise ValueError("pipeline_parallel stacks equal stages: it "
                             "takes no block_schedule")
        if not self.factorized_embedding and self.token_patch_size != 1:
            raise ValueError("factorized_embedding=false holds one stream-"
                             "wide row a token: token_patch_size must be 1")
        if self.experts_held is None:
            self.experts_held = self.experts
        if not (0 < self.experts_held
                and 0 <= self.expert_offset
                and self.expert_offset + self.experts_held <= self.experts):
            raise ValueError(
                f"experts_held={self.experts_held} from expert_offset="
                f"{self.expert_offset} is no share of experts={self.experts}")
        if self.rope_parameters is None and getattr(
                self, "rope_scaling", None) is not None:
            self.rope_parameters = {"full_attention": dict(
                self.rope_scaling, rope_theta=self.rope_theta)}
        if self.kda_use_full_proj:
            raise ValueError(
                "kda_use_full_proj=true asks for full-rank gate maps in kda; "
                "only the two low-rank pairs (decay_down/up, out_down/up) "
                "are written")

        # video patch arithmetic (reference dataclass.py:262-271)
        self.time_patch_size = self.sequence_length // self.time_patch
        self.frame_height_patch = self.frame_height // self.patch_size
        self.frame_width_patch = self.frame_width // self.patch_size
        self.channel_color_size = self.color_channels * self.time_patch * self.patch_size ** 2
        self.fold_count = 32 // self.bit_fold_value
        if self.use_bit_fold_input_pipeline and 2 ** self.bit_fold_value < self.color_quantization_value:
            raise ValueError("bit-fold value too small for color quantization")
        if self.use_bit_fold_input_pipeline:
            self.channel_color_size //= self.fold_count
        self.language_token_patch = self.language_token_per_frame // self.token_patch_size

        self.intermediate_size = int(
            self.heads * self.features_per_head * self.intermediate_feed_forward_multiplier)
        self.product_key_value_vectors = self.features_per_head ** 2
        if self.moe_intermediate_size is None:
            self.moe_intermediate_size = self.intermediate_size

        # dimension registry
        self.dims: typing.Dict[str, int] = {
            BATCH: self.train_batch_size,
            SEQUENCE: self.time_patch_size,
            HEADS: self.heads,
            KEY: self.features_per_head,
            INTERMEDIATE: self.intermediate_size,
            EXPERT_INTERMEDIATE: self.moe_intermediate_size,
            VOCAB: self.vocab_size,
            TOKEN_PATCH: self.token_patch_size,
            EXPERTS: self.experts,
            PKM_AXES: self.pkm_axes,
            PKM_VALUES: self.product_key_value_vectors,
            HEIGHT: self.frame_height_patch,
            WIDTH: self.frame_width_patch,
            COLOR_CHANNELS: self.channel_color_size,
            anonymize_name(KEY): self.features_per_head * self.group_linear_factor,
        }
        self.feature_dims = (HEADS, KEY)

        # parallelism synthesis: reference maps batch->b, heads->h
        # (dataclass.py:247-252); we extend with a sequence-parallel axis.
        self.mesh_data = max(1, self.tpu_size // (
            self.heads * self.sequence_parallel * self.pipeline_parallel))
        self.mesh_model = self.heads if self.heads > 1 else 1

    # -- convenience --------------------------------------------------------
    def dim_size(self, name: str) -> int:
        return self.dims[name]

    def dict(self) -> dict:
        return {k: v for k, v in self.__dict__.items() if not k.startswith("_")}

    def __repr__(self) -> str:
        return f"Config({self.model_mode}, d={self.features}, L={self.depth})"


ModelParameter = Config  # reference-compatible alias
