"""Argument system: reference-compatible flags → config dataclasses.

Parity with /root/reference/megatron/training/arguments.py (2719 LoC, ~28
_add_*_args groups :1059-2656 + validate_args): the flag NAMES follow the
reference so launch scripts translate 1:1; values land in our
TransformerConfig / ParallelConfig / TrainingConfig / OptimizerConfig
dataclasses instead of a global args namespace.
"""

from __future__ import annotations

import argparse
from typing import Optional, Tuple

import jax.numpy as jnp

from megatronapp_tpu.config.parallel_config import ParallelConfig
from megatronapp_tpu.config.training_config import (
    OptimizerConfig, TrainingConfig,
)
from megatronapp_tpu.config.transformer_config import (
    ActivationKind, NormKind, PositionEmbeddingKind, TransformerConfig,
)


_HYBRID_FIELDS = ("attn_layer_period", "attn_layer_offset",
                  "ssm_inner_norms", "ssm_heads", "ssm_head_dim",
                  "ssm_state_dim", "ssm_chunk_size")


def add_hybrid_args(ap: argparse.ArgumentParser):
    """The layer pattern of a hybrid state-space stack
    (TransformerConfig.attn_layer_period; HF `jamba`'s keys) — shared by
    the main parser (the hybrid trains through pretrain_gpt.py) and
    tools/run_text_generation_server.py (it serves through --engine
    dynamic). The mixer's other sizes stay the model's own (a preset's,
    or TransformerConfig's defaults: state 16, conv 4, expand 2, dt rank
    hidden / 16). Which mixer it is, is a fact of the model: --ssm-heads
    makes it Mamba-2 (HF `granitemoehybrid`). Every default is None = the
    model's own."""
    g = ap.add_argument_group("hybrid state-space stack")
    g.add_argument("--attn-layer-period", type=int, default=None,
                   help="layer i attends iff i %% period == offset; every "
                        "other layer is a selective-state-space layer "
                        "(HF attn_layer_period)")
    g.add_argument("--attn-layer-offset", type=int, default=None,
                   help="HF attn_layer_offset")
    g.add_argument("--ssm-inner-norms", action="store_const", const=True,
                   default=None,
                   help="RMS norms on dt, B and C after x_proj (Jamba)")
    g.add_argument("--ssm-heads", type=int, default=None,
                   help="the state-space layers are Mamba-2 mixers of this "
                        "many heads, a matrix state a head (HF "
                        "mamba_n_heads); the inner width is heads x "
                        "--ssm-head-dim")
    g.add_argument("--ssm-head-dim", type=int, default=None,
                   help="columns of a Mamba-2 head (HF mamba_d_head)")
    g.add_argument("--ssm-state-dim", type=int, default=None,
                   help="the state's size N (HF mamba_d_state)")
    g.add_argument("--ssm-chunk-size", type=int, default=None,
                   help="positions a chunk of Mamba-2's prefill and "
                        "training scan (HF mamba_chunk_size)")


def hybrid_fields(args) -> dict:
    """The TransformerConfig fields the add_hybrid_args flags set."""
    return {f: getattr(args, f) for f in _HYBRID_FIELDS
            if getattr(args, f, None) is not None}


_EVA_FIELDS = ("eva_window_size", "eva_chunk_size")


def add_eva_args(ap: argparse.ArgumentParser):
    """EVA attention (TransformerConfig.eva_window_size; transformer/eva.py),
    shared by the main parser and tools/run_text_generation_server.py like
    add_hybrid_args. Every default is None = the model's own (a preset's,
    or TransformerConfig's: 0 = plain attention). What else HF `evabyte`
    adds to a decoder (norm_unit_offset, num_pred_heads) has no flag: the
    preset carries it."""
    g = ap.add_argument_group("EVA attention")
    g.add_argument("--eva-window-size", type=int, default=None,
                   help="a query sees the rows of its own aligned window "
                        "of this many positions exactly (HF window_size); "
                        "0 = plain attention")
    g.add_argument("--eva-chunk-size", type=int, default=None,
                   help="and one pooled key/value row for every this many "
                        "positions of each earlier window (HF chunk_size); "
                        "the paged cache wants it equal to --kv-block-size")


def eva_fields(args) -> dict:
    """The TransformerConfig fields the add_eva_args flags set."""
    return {f: getattr(args, f) for f in _EVA_FIELDS
            if getattr(args, f, None) is not None}


def add_serving_args(ap: argparse.ArgumentParser):
    """Serving / paged-KV flags (ISSUE 3) — single source of truth shared
    by the main parser (so config-YAML runs and --use-checkpoint-args
    carry them) and tools/run_text_generation_server.py, which consumes
    them to assemble the engine."""
    g = ap.add_argument_group("serving")
    g.add_argument("--engine", choices=["static", "dynamic", "mamba"],
                   default="static",
                   help="dynamic = continuous batching over the block-"
                        "pool paged KV cache (connections share one "
                        "decode batch through the server's stepper "
                        "thread, inference/dynamic_engine.py; per-block "
                        "admission, prefix caching, preemption, "
                        "inference/paged_cache.py); "
                        "mamba = recurrent-state decode for pure-M "
                        "presets (reference mamba server tool)")
    g.add_argument("--max-batch", type=int, default=4,
                   help="dynamic engine: concurrent decode slots")
    g.add_argument("--kv-block-size", type=int, default=16,
                   help="tokens per KV block")
    g.add_argument("--num-kv-blocks", type=int, default=None,
                   help="pool size (default: dense capacity max_batch * "
                        "ceil(max_seq_len/block_size); size down to run "
                        "oversubscribed with preemption)")
    g.add_argument("--no-prefix-caching", action="store_false",
                   dest="prefix_caching",
                   help="disable refcounted shared-prefix block reuse")
    # Quantized serving (ISSUE 10 int8, ISSUE 13 fp8). Choices AND help
    # derive from the shared KV_CACHE_DTYPES registry
    # (inference/paged_cache.py) — the flag, the server validation, and
    # the pool check cannot drift apart.
    from megatronapp_tpu.inference.paged_cache import (
        KV_CACHE_DTYPES, kv_cache_dtype_help,
    )
    g.add_argument("--kv-cache-dtype", choices=sorted(KV_CACHE_DTYPES),
                   default="bf16",
                   help="paged KV-pool storage dtype — "
                        + kv_cache_dtype_help()
                        + " (quantized dtypes need --engine dynamic; "
                        "MLA latent/pe pools quantize with per-row "
                        "scalar scales; quantized pools cost "
                        "~(D+4)/2D of the bf16 bytes)")
    g.add_argument("--scan-unroll", type=int, default=1,
                   help="lax.scan unroll factor for the layer stack "
                        "(PERF.md lever #3): unrolls the training "
                        "layer scan AND the serving decode/multi-query "
                        "step scans")
    g.add_argument("--quantized-weights", action="store_true",
                   help="serve from int8 weights kept RESIDENT (per-"
                        "channel dequant fused at matmul entry, param "
                        "HBM ~halved) instead of dequantize-on-load; "
                        "pairs with --load-quantized, otherwise the "
                        "loaded/initialized params are PTQ-quantized at "
                        "startup")
    g.add_argument("--spec-method", default="none",
                   choices=["none", "draft", "mtp", "ngram"],
                   help="speculative decoding over the paged engine "
                        "(inference/speculative.py; needs --engine "
                        "dynamic): draft = small draft "
                        "model (--draft-model), mtp = self-draft through "
                        "the model's MTP heads, ngram = model-free "
                        "prompt lookup. Greedy output is bit-identical "
                        "to plain decode; sampling preserves the target "
                        "distribution exactly")
    g.add_argument("--spec-k", type=int, default=4,
                   help="max draft tokens verified per round (the "
                        "verify step runs K+1 ragged queries through "
                        "the multi-query paged-attention kernel)")
    g.add_argument("--draft-model", default=None,
                   help="models/presets.py preset for --spec-method "
                        "draft (must share the target vocab/tokenizer)")
    g.add_argument("--draft-load-dir", default=None,
                   help="checkpoint dir for the draft model (otherwise "
                        "randomly initialized — only useful for "
                        "plumbing tests)")
    # Disaggregated serving (ISSUE 9, inference/disagg.py).
    g.add_argument("--serve-disagg", action="store_true",
                   help="prefill/decode disaggregation: split the "
                        "devices into a prefill sub-mesh and a decode "
                        "sub-mesh (2*serve_tp devices total) with KV "
                        "handoff through the shared block pool — decode "
                        "token intervals stop being hostage to long "
                        "prefills (needs --engine dynamic)")
    g.add_argument("--serve-tp", type=int, default=1,
                   help="tensor-parallel degree of the serving mesh: "
                        "the ragged paged-attention kernels run "
                        "head-sharded over a tp mesh with per-shard KV "
                        "pools (with --serve-disagg, EACH sub-mesh is "
                        "this wide)")
    g.add_argument("--prefill-chunk", type=int, default=None,
                   help="width of a paged prefill call, in tokens (one "
                        "compiled program; a prompt's last call is padded "
                        "to it). Default: the engine chooses from the "
                        "model's shapes and the device (what the call's "
                        "weight stream pays for: 256 for a dense bf16 "
                        "model on a v5e; at most --max-seq-len; 32 on a "
                        "CPU) and GET /stats "
                        "shows it under prefill.width. With "
                        "--serve-disagg also the prefill-side scheduling "
                        "quantum (chunks defer when the decode SLO is at "
                        "risk), 32 unless given")
    g.add_argument("--disagg-prefill-slots", type=int, default=2,
                   help="staging page-table rows for in-flight/parked "
                        "prefills on the prefill sub-mesh")
    g.add_argument("--decode-slo-ms", type=float, default=None,
                   help="decode token-interval SLO budget: prefill "
                        "chunks are preempted when the next chunk "
                        "would push the interval past this; /stats "
                        "and /healthz report attainment")
    # Fleet serving (ISSUE 14, inference/fleet.py).
    g.add_argument("--serve-fleet", type=int, default=1, metavar="N",
                   help="run N engine replicas behind the KV-affinity "
                        "fleet router (inference/fleet.py): admission "
                        "scores prefix-cache affinity + queue depth + "
                        "pool pressure + SLO attainment per replica; "
                        "replica death fails sessions over losslessly; "
                        "reloads roll one replica at a time. N=1 keeps "
                        "the single-engine path (needs --engine dynamic "
                        "for N>1; with --serve-disagg "
                        "each replica is its own prefill/decode "
                        "sub-mesh pair)")
    g.add_argument("--fleet-migrate", action="store_true",
                   help="live session migration between fleet replicas "
                        "(PagedKVCache.export_slot/import_slot — "
                        "quantized KV rows + scales ship verbatim, "
                        "streams stay token-exact): overloaded replicas "
                        "hand running sessions to underloaded ones, and "
                        "rolling reloads drain by migration instead of "
                        "waiting for completion")
    g.add_argument("--fleet-autoscale", action="store_true",
                   help="EWMA-attainment-driven autoscaling of each "
                        "disagg replica's prefill/decode mesh split "
                        "(fleet.MeshSplitAutoscaler): low decode-SLO "
                        "attainment shrinks the prefill sub-mesh, "
                        "persistent prefill-queue depth grows it; "
                        "applied by drain + rebuild (needs "
                        "--serve-disagg)")
    # Cross-process fleet (ISSUE 18, inference/fleet_rpc.py).
    g.add_argument("--fleet-procs", type=int, default=0, metavar="N",
                   help="promote the fleet to N replica WORKER "
                        "PROCESSES behind the process router "
                        "(inference/fleet_rpc.py): each replica is a "
                        "spawned `python -m megatronapp_tpu.inference"
                        ".fleet_rpc` worker serving its engine over a "
                        "length-prefixed socket RPC; the router keeps "
                        "the same rid space, affinity admission, and "
                        "token-exact migration across the process "
                        "boundary. 0 keeps fleet serving in-process "
                        "(mutually exclusive with --serve-fleet N>1)")
    g.add_argument("--replica-rpc-port", type=int, default=0,
                   metavar="PORT",
                   help="base TCP port for replica workers (replica i "
                        "binds PORT+i on 127.0.0.1); 0 = ephemeral "
                        "ports published via each replica's addr.json")
    g.add_argument("--supervisor", choices=("off", "thread", "process"),
                   default="off",
                   help="replica supervisor mode (inference/"
                        "supervisor.py): 'thread' polls worker "
                        "heartbeats from a router thread, 'process' "
                        "runs `python -m megatronapp_tpu.inference"
                        ".supervisor` as its own OS process — either "
                        "detects a wedged/killed worker, SIGKILLs and "
                        "relaunches it, and the router fails sessions "
                        "over losslessly (needs --fleet-procs)")
    # Multi-tenant batched-LoRA serving (ISSUE 19, inference/lora.py).
    g.add_argument("--lora-dir", type=str, default=None, metavar="DIR",
                   help="serve per-request LoRA adapters from DIR "
                        "(<DIR>/<adapter_id>.npz, LoraAdapter.save "
                        "format): requests submit with an adapter_id, "
                        "the engine pins it into the HBM adapter cache "
                        "(inference/lora.py AdapterCache — refcount/"
                        "LRU-evict, PagedKVCache discipline), and every "
                        "decode step applies the per-row low-rank "
                        "deltas via the segmented batched-LoRA kernel "
                        "(needs --engine dynamic; "
                        "incompatible with --multi-latent-attention: "
                        "MLA has no q/kv projection leaves to adapt)")
    g.add_argument("--lora-rank", type=int, default=8, metavar="R",
                   help="adapter rank the HBM banks are sized for "
                        "(every served adapter must match; DISTINCT "
                        "from the MLA latent dims --q-lora-rank/"
                        "--kv-lora-rank)")
    g.add_argument("--max-resident-adapters", type=int, default=8,
                   metavar="N",
                   help="HBM adapter cache capacity: N adapters resident "
                        "at once (plus the permanent all-zero NULL "
                        "slot); misses load from --lora-dir, evicting "
                        "the LRU unpinned resident — admission waits "
                        "when all N are pinned by in-flight requests")
    # KV capacity tiers (ISSUE 20, inference/paged_cache.py).
    g.add_argument("--kv-spill-host-mb", type=float, default=0.0,
                   metavar="MB",
                   help="host-RAM KV spill tier byte budget (0 = off): "
                        "idle/low-priority sessions PARK — their pool "
                        "blocks export to host memory (export_slot "
                        "payloads, exact serialized bytes) and the "
                        "blocks free — then resume token-exact through "
                        "import_slot on the next token. Under pressure "
                        "the engine prefers parking over preemption "
                        "(a park costs an import, a preemption a "
                        "re-prefill); needs --engine dynamic")
    g.add_argument("--kv-spill-watermark-blocks", type=int, default=0,
                   metavar="N",
                   help="park sessions whenever the pool's free+"
                        "evictable block count drops below N (0 = park "
                        "only under admission/decode pressure); parked "
                        "sessions auto-resume FIFO once capacity "
                        "recovers above the watermark (needs "
                        "--kv-spill-host-mb)")
    g.add_argument("--fleet-prefix-store-mb", type=float, default=0.0,
                   metavar="MB",
                   help="fleet-global prefix store capacity (0 = off): "
                        "prefix blocks inserted by ANY replica are "
                        "exported once into a shared host-RAM store "
                        "(keyed by the same rolling block hashes as "
                        "the prefix cache), and a replica admitting a "
                        "prompt it misses locally imports the blocks "
                        "instead of recomputing the prefill — hot "
                        "prefixes cost once per fleet, not once per "
                        "replica (LRU-bounded; needs --serve-fleet "
                        "N>=2 or --fleet-procs N>=2)")
    # Telemetry spine (ISSUE 12).
    g.add_argument("--serving-metrics", action="store_true",
                   help="enable the telemetry registry "
                        "(utils/metrics.py): counters + log-bucket "
                        "latency histograms from the engines, "
                        "allocator, and driver, exported as Prometheus "
                        "text at GET /metrics (env equivalent: "
                        "MEGATRON_METRICS=1). Off by default — the "
                        "disabled path is one dict check per site")
    g.add_argument("--request-trace", action="store_true",
                   help="enable the always-on bounded request-lifecycle "
                        "tracer (trace/request_trace.py): B/E spans per "
                        "request id (admit/queue/prefill/handoff/adopt/"
                        "decode/retire) in a ring buffer, served as one "
                        "merged Chrome trace at GET /trace (env "
                        "equivalent: MEGATRON_REQUEST_TRACE=1)")
    g.add_argument("--request-trace-capacity", type=int, default=16384,
                   help="ring-buffer record capacity for "
                        "--request-trace (old records fall off; memory "
                        "stays bounded under production load)")
    return g


def validate_serving_args(args, multi_latent_attention: bool = False):
    """Parse-time validation of the serving flag combinations (single
    source of truth for every entry point consuming add_serving_args) —
    reject impossible configs with an actionable message instead of a
    deep stack trace at engine construction."""
    # kv_cache_dtype validation shares the pool's registry messages
    # (inference/paged_cache.py validate_kv_cache_dtype), so the flag
    # help, this parse-time check, and the pool constructor agree by
    # construction (ISSUE 13 satellite).
    from megatronapp_tpu.inference.paged_cache import (
        validate_kv_cache_dtype,
    )
    try:
        spec = validate_kv_cache_dtype(
            getattr(args, "kv_cache_dtype", "bf16"),
            mla=multi_latent_attention)
    except ValueError as e:
        raise SystemExit(str(e))
    if spec.quantized and getattr(args, "engine", "static") != "dynamic":
        raise SystemExit(
            f"--kv-cache-dtype {spec.name} requires --engine dynamic (the "
            "per-block quantization scales live alongside its block pool; "
            "the static engine's dense cache has no block structure)")
    # Fleet serving (ISSUE 14): parse-time validation in the usual
    # first-failed-predicate style — each impossible combination gets
    # its own actionable message.
    fleet = getattr(args, "serve_fleet", 1)
    if fleet < 1:
        raise SystemExit(
            f"--serve-fleet must be >= 1 (got {fleet}); 1 = the "
            "single-engine path, N > 1 = N replicas behind the fleet "
            "router")
    if fleet > 1:
        if getattr(args, "engine", "static") != "dynamic":
            raise SystemExit(
                "--serve-fleet N>1 requires --engine dynamic (the "
                "router drives replica step loops through the "
                "continuous-batching driver)")
    if getattr(args, "fleet_migrate", False) and fleet < 2:
        raise SystemExit(
            "--fleet-migrate needs --serve-fleet >= 2 (live session "
            "migration moves KV between REPLICA pools; with one "
            "replica there is nowhere to migrate to)")
    if getattr(args, "fleet_autoscale", False):
        if not getattr(args, "serve_disagg", False):
            raise SystemExit(
                "--fleet-autoscale needs --serve-disagg (the "
                "autoscaler's knob is each replica's prefill/decode "
                "mesh split — a colocated engine has no split to "
                "resize)")
        if getattr(args, "engine", "static") != "dynamic":
            raise SystemExit(
                "--fleet-autoscale needs --engine dynamic (it is a "
                "fleet-router policy)")
    # Cross-process fleet (ISSUE 18): same first-failed-predicate style.
    procs = getattr(args, "fleet_procs", 0)
    if procs < 0:
        raise SystemExit(
            f"--fleet-procs must be >= 0 (got {procs}); 0 = in-process "
            "serving, N > 0 = N replica worker processes")
    if procs > 0:
        if fleet > 1:
            raise SystemExit(
                "--fleet-procs and --serve-fleet N>1 are mutually "
                "exclusive: the process router OWNS its replica "
                "workers (one fleet, one router — pick in-process OR "
                "cross-process)")
        if getattr(args, "engine", "static") != "dynamic":
            raise SystemExit(
                "--fleet-procs requires --engine dynamic (replica "
                "workers serve DynamicInferenceEngine step loops)")
    port = getattr(args, "replica_rpc_port", 0)
    if port and not procs:
        raise SystemExit(
            "--replica-rpc-port needs --fleet-procs (it is the replica "
            "workers' base port; in-process replicas have no sockets)")
    if port and not (1024 <= port <= 65535 - max(procs, 1)):
        raise SystemExit(
            f"--replica-rpc-port {port} out of range: need 1024 <= "
            f"PORT and PORT+{procs} <= 65535 (replica i binds PORT+i), "
            "or 0 for ephemeral ports")
    if getattr(args, "supervisor", "off") != "off" and not procs:
        raise SystemExit(
            "--supervisor needs --fleet-procs (it watches worker "
            "heartbeats and relaunches worker PROCESSES; the in-process "
            "fleet's kill/revive drills already route through the same "
            "supervisor code path internally)")
    # Multi-tenant LoRA serving (ISSUE 19): same first-failed-predicate
    # style — the adapter banks ride the dynamic paged decode step.
    if getattr(args, "lora_dir", None):
        if getattr(args, "engine", "static") != "dynamic":
            raise SystemExit(
                "--lora-dir requires --engine dynamic (the adapter "
                "banks join the dynamic engine's decode scan; the "
                "static engine has no per-row adapter plumbing)")
        if multi_latent_attention:
            raise SystemExit(
                "--lora-dir is incompatible with "
                "--multi-latent-attention: MLA factors attention "
                "through latent kernels with no q_kernel/kv_kernel "
                "leaves to adapt — serve MLA models without LoRA")
        if getattr(args, "serve_disagg", False):
            raise SystemExit(
                "--lora-dir does not compose with --serve-disagg yet: "
                "the adapter banks join the unified dynamic engine's "
                "decode scan; the disagg facade's split prefill/decode "
                "meshes would need per-mesh bank replicas (serve LoRA "
                "from the colocated dynamic engine or a fleet of them)")
    rank = getattr(args, "lora_rank", 8)
    if rank < 1:
        raise SystemExit(
            f"--lora-rank must be >= 1 (got {rank}); the HBM banks "
            "are sized A[L, slots, din, R] / B[L, slots, R, dout]")
    max_res = getattr(args, "max_resident_adapters", 8)
    if max_res < 1:
        raise SystemExit(
            f"--max-resident-adapters must be >= 1 (got {max_res}); "
            "slot 0 is the reserved NULL adapter, so at least one "
            "managed slot is needed to serve any adapter at all")
    # KV capacity tiers (ISSUE 20): same first-failed-predicate style.
    spill_mb = getattr(args, "kv_spill_host_mb", 0.0)
    if spill_mb < 0:
        raise SystemExit(
            f"--kv-spill-host-mb must be >= 0 (got {spill_mb}); it is "
            "the spill tier's host byte budget (0 disables it)")
    if spill_mb:
        if getattr(args, "engine", "static") != "dynamic":
            raise SystemExit(
                "--kv-spill-host-mb requires --engine dynamic (park/"
                "unpark is the dynamic engine's slot machinery)")
        if getattr(args, "serve_disagg", False):
            raise SystemExit(
                "--kv-spill-host-mb does not compose with "
                "--serve-disagg yet: parking lives in the unified "
                "engine's slot machinery; the disagg facade stages "
                "prefills in a separate pool (serve the spill tier "
                "from colocated dynamic engines or a fleet of them)")
    watermark = getattr(args, "kv_spill_watermark_blocks", 0)
    if watermark < 0:
        raise SystemExit(
            f"--kv-spill-watermark-blocks must be >= 0 (got "
            f"{watermark}); it is a free-block low-water mark")
    if watermark and not spill_mb:
        raise SystemExit(
            "--kv-spill-watermark-blocks needs --kv-spill-host-mb "
            "(the watermark decides WHEN to park; the budget is WHERE "
            "the parked bytes go — without a budget nothing can park)")
    store_mb = getattr(args, "fleet_prefix_store_mb", 0.0)
    if store_mb < 0:
        raise SystemExit(
            f"--fleet-prefix-store-mb must be >= 0 (got {store_mb}); "
            "it is the store's host capacity (0 disables it)")
    if store_mb and fleet < 2 and procs < 2:
        raise SystemExit(
            "--fleet-prefix-store-mb needs a fleet of >= 2 replicas "
            "(--serve-fleet N>=2 or --fleet-procs N>=2): with one "
            "replica the pool's own prefix cache already holds every "
            "inserted block — a fleet-global store would only "
            "duplicate it")
    if (getattr(args, "quantized_weights", False)
            and getattr(args, "engine", "static") == "mamba"):
        raise SystemExit(
            "--quantized-weights supports the gpt engines only: "
            "mamba_forward does not resolve resident int8 kernels "
            "(drop the flag, or serve the artifact without it to "
            "dequantize on load)")


def build_parser(title: str = "megatronapp-tpu") -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(
        description=title, allow_abbrev=False,
        formatter_class=argparse.ArgumentDefaultsHelpFormatter)

    g = ap.add_argument_group("model")  # _add_network_size_args parity
    g.add_argument("--num-layers", type=int, default=12)
    g.add_argument("--hidden-size", type=int, default=768)
    g.add_argument("--num-attention-heads", type=int, default=12)
    g.add_argument("--num-query-groups", type=int, default=None)
    g.add_argument("--ffn-hidden-size", type=int, default=None)
    g.add_argument("--kv-channels", type=int, default=None)
    g.add_argument("--heterogeneous-layers-config-path", type=str,
                   default=None)
    g.add_argument("--heterogeneous-layers-config-encoded-json", type=str,
                   default=None)
    g.add_argument("--vocab-size", type=int, default=50304)
    g.add_argument("--max-position-embeddings", type=int, default=2048)
    g.add_argument("--position-embedding-type", default="rope",
                   choices=[k.value for k in PositionEmbeddingKind])
    g.add_argument("--rotary-base", type=float, default=10000.0)
    g.add_argument("--rotary-percent", type=float, default=1.0)
    g.add_argument("--rope-scaling-factor", type=float, default=1.0,
                   help="YaRN context multiplier (HF rope_scaling.factor; "
                        "with --position-embedding-type yarn)")
    g.add_argument("--yarn-original-max-position", type=int, default=4096,
                   help="HF rope_scaling.original_max_position_embeddings")
    g.add_argument("--yarn-mscale-coeff", type=float, default=0.1,
                   help="logits carry (coeff * ln(factor) + 1)**2; HF "
                        "0.1 * rope_scaling.mscale_all_dim (DeepSeek-V2: "
                        "0.0707), with mscale == mscale_all_dim")
    g.add_argument("--normalization", default="LayerNorm",
                   choices=[k.value for k in NormKind])
    g.add_argument("--swiglu", action="store_true")
    g.add_argument("--squared-relu", action="store_true")
    g.add_argument("--disable-bias-linear", action="store_true")
    g.add_argument("--add-qkv-bias", action="store_true")
    g.add_argument("--qk-layernorm", action="store_true")
    g.add_argument("--untie-embeddings-and-output-weights",
                   action="store_true")
    g.add_argument("--init-method-std", type=float, default=0.02)
    g.add_argument("--preset", default=None,
                   help="named model preset (models/presets.py); flags "
                        "override preset fields they explicitly set")

    g = ap.add_argument_group("mtp")  # multi_token_prediction.py parity
    g.add_argument("--mtp-num-layers", type=int, default=None)
    g.add_argument("--mtp-loss-scaling-factor", type=float, default=0.1)

    g = ap.add_argument_group("mla")  # MLATransformerConfig parity
    g.add_argument("--multi-latent-attention", action="store_true")
    g.add_argument("--q-lora-rank", type=int, default=None)
    g.add_argument("--kv-lora-rank", type=int, default=512)
    g.add_argument("--qk-head-dim", type=int, default=128)
    g.add_argument("--qk-pos-emb-head-dim", type=int, default=64)
    g.add_argument("--v-head-dim", type=int, default=128)

    g = ap.add_argument_group("moe")  # _add_moe_args parity
    g.add_argument("--num-experts", type=int, default=None)
    g.add_argument("--moe-router-topk", type=int, default=2)
    g.add_argument("--moe-ffn-hidden-size", type=int, default=None)
    g.add_argument("--moe-aux-loss-coeff", type=float, default=0.0)
    g.add_argument("--moe-z-loss-coeff", type=float, default=0.0)
    g.add_argument("--moe-expert-capacity-factor", type=float, default=None)
    g.add_argument("--moe-layer-freq", type=int, default=1)
    g.add_argument("--moe-first-k-dense", type=int, default=0,
                   help="the first k layers are dense MLPs of "
                        "--ffn-hidden-size (HF first_k_dense_replace)")
    g.add_argument("--moe-router-no-norm-topk-prob", action="store_false",
                   dest="moe_router_norm_topk_prob",
                   help="keep the top-k softmax probabilities as they are "
                        "(HF norm_topk_prob false: DeepSeek-V2-Lite)")
    g.add_argument("--moe-routed-scaling-factor", type=float, default=1.0,
                   help="HF routed_scaling_factor")
    g.add_argument("--moe-shared-expert-intermediate-size", type=int,
                   default=None)

    g = ap.add_argument_group("distributed")  # _add_distributed_args parity
    g.add_argument("--tensor-model-parallel-size", type=int, default=1)
    g.add_argument("--pipeline-model-parallel-size", type=int, default=1)
    g.add_argument("--context-parallel-size", type=int, default=1)
    g.add_argument("--hierarchical-context-parallel-sizes", nargs=2,
                   type=int, default=None, metavar=("A2A", "RING"),
                   help="inner a2a x outer ring sizes for "
                        "cp-comm-type a2a+p2p (reference flag)")
    g.add_argument("--expert-model-parallel-size", type=int, default=1)
    g.add_argument("--num-layers-per-virtual-pipeline-stage", type=int,
                   default=None)
    g.add_argument("--sequence-parallel", action="store_true")
    g.add_argument("--tp-comm-overlap", action="store_true",
                   help="overlap tensor-parallel collectives with the "
                        "dependent GEMMs via manual ring all-gather / "
                        "reduce-scatter matmuls (parallel/overlap.py)")
    g.add_argument("--no-tp-sharded-stage", action="store_false",
                   dest="tp_sharded_stage",
                   help="disable the tp-SHARDED pipeline stage body "
                        "(parallel/pipeline.py tp_shard) and fall back "
                        "to tp-replicated stage compute — the A/B "
                        "baseline; only meaningful with pp>1 x tp>1")
    g.add_argument("--sharded-init", action="store_true",
                   help="initialize the train state direct-to-shards "
                        "(params never materialize unsharded — for "
                        "giant-model runs whose replicated init would "
                        "OOM a device); the default two-stage "
                        "replicated-then-reshard init is the one whose "
                        "seeded values are mesh-independent "
                        "(training/train_state.py)")
    g.add_argument("--no-cp-comm-overlap", action="store_false",
                   dest="cp_comm_overlap",
                   help="disable the latency-hiding ring-attention path "
                        "(pre-issued KV hops + fused custom_vjp reverse "
                        "ring, ops/context_parallel.py); falls back to "
                        "the plain unrolled ring")
    g.add_argument("--no-moe-comm-overlap", action="store_false",
                   dest="moe_comm_overlap",
                   help="disable the chunked latency-hiding MoE "
                        "all-to-all (transformer/moe.py); falls back to "
                        "the bulk two-collective dispatch")
    g.add_argument("--use-distributed-optimizer", action="store_true",
                   default=True,
                   help="ZeRO-1 distributed optimizer (default on): "
                        "Adam m/v (and the fp32 master shard for "
                        "low-precision params) live sharded over the "
                        "data-parallel axis; grads enter the update "
                        "reduce-scattered and updated params return via "
                        "all-gather (training/distributed_optimizer.py)")
    g.add_argument("--no-use-distributed-optimizer", action="store_false",
                   dest="use_distributed_optimizer",
                   help="replicate optimizer state on every dp rank "
                        "(the A/B baseline for bench extra.dist_opt)")
    g.add_argument("--main-params-dtype", default="fp32",
                   help="dtype of the ZeRO-1 master-weight shard (kept "
                        "only when params are lower precision); fp32 is "
                        "the supported accumulation dtype")
    g.add_argument("--exp-avg-dtype", default="fp32",
                   help="storage dtype of the Adam first moment "
                        "(exp_avg): fp32 | bf16 — update math stays "
                        "fp32; bf16 halves per-rank m bytes and "
                        "requires --use-distributed-optimizer")
    g.add_argument("--exp-avg-sq-dtype", default="fp32",
                   help="storage dtype of the Adam second moment "
                        "(exp_avg_sq): fp32 | bf16; requires "
                        "--use-distributed-optimizer")
    g.add_argument("--dist-opt-comm", default="gspmd",
                   choices=["gspmd", "ring", "bulk"],
                   help="collectives of the ZeRO-1 weight update: gspmd "
                        "= XLA inserts grad slice / param all-gather "
                        "from the dp-sharded state layout (arXiv "
                        "2004.13336); ring = full-manual update with "
                        "the latency-hiding ring all-gather "
                        "(parallel/overlap.py); bulk = full-manual "
                        "with one tiled all-gather")
    g.add_argument("--cp-comm-type", default="p2p",
                   choices=["p2p", "a2a", "allgather", "a2a+p2p"])
    # MegaFBD / MegaDPP flags (reference arguments.py:2197-2205).
    g.add_argument("--forward-backward-disaggregating", action="store_true")
    g.add_argument("--use-dpp", action="store_true",
                   help="breadth-first-chunk pipeline order (MegaDPP)")
    # Pipeline schedule programs + the trace-driven planner (ISSUE 15,
    # parallel/schedule.py). Choices derive from the schedule layer's
    # canonical list so a new schedule is one edit, not three.
    from megatronapp_tpu.parallel.schedule import SCHEDULES
    g.add_argument("--pp-schedule", default="1f1b",
                   choices=list(SCHEDULES),
                   help="pipeline schedule program executed by the "
                        "manual region (parallel/schedule.py): 1f1b "
                        "(interleaved automatically when vpp > 1), vpp "
                        "(alias requiring "
                        "--num-layers-per-virtual-pipeline-stage), or "
                        "zero-bubble (backward split into B=dgrad / "
                        "W=wgrad; W deferred into bubble slots, the "
                        "weight update fenced on all W done — grads "
                        "identical to the fused backward)")
    g.add_argument("--pp-plan-from-trace", action="store_true",
                   help="let the trace-driven planner "
                        "(parallel/schedule.Planner) retune the "
                        "schedule from per-stage step-time EWMAs "
                        "(MegaScan ring-hop spans + the straggler "
                        "signal + the heterogeneous stage table); "
                        "re-plans log loudly and rebuild the train "
                        "step")
    # Multi-host runtime (reference torchrun MASTER_ADDR/RANK/WORLD_SIZE →
    # jax.distributed; auto-detected on TPU pods).
    g.add_argument("--multi-host", action="store_true",
                   help="join the jax.distributed multi-host runtime "
                        "before building the mesh (auto-detects "
                        "coordinator on TPU pods)")
    g.add_argument("--coordinator-address", default=None,
                   help="host:port of process 0 (manual launches)")
    g.add_argument("--num-processes", type=int, default=None)
    g.add_argument("--process-id", type=int, default=None)

    g = ap.add_argument_group("training")  # _add_training_args parity
    g.add_argument("--micro-batch-size", type=int, default=1)
    g.add_argument("--global-batch-size", type=int, default=8)
    g.add_argument("--rampup-batch-size", nargs=3, type=int, default=None,
                   metavar=("START", "INCR", "SAMPLES"),
                   help="linear global-batch rampup (reference "
                        "--rampup-batch-size)")
    g.add_argument("--seq-length", type=int, default=1024)
    g.add_argument("--train-iters", type=int, default=100)
    g.add_argument("--seed", type=int, default=1234)
    g.add_argument("--log-interval", type=int, default=10)
    g.add_argument("--eval-interval", type=int, default=None)
    g.add_argument("--eval-iters", type=int, default=10)
    g.add_argument("--exit-interval", type=int, default=None)
    g.add_argument("--recompute-activations", action="store_true",
                   help="selective recompute (default policy already "
                        "selective; use --recompute-granularity)")
    g.add_argument("--recompute-granularity", default="selective",
                   choices=["none", "selective", "selective_attn", "full"])
    g.add_argument("--attention-impl", default="auto",
                   choices=["auto", "pallas", "reference"],
                   help="auto = chosen from the call's shapes: on a TPU "
                        "the Pallas flash kernels from S 2048 on and "
                        "wherever the dense float32 scores of a call were "
                        "measured to leave the chip (bf16, heads of "
                        "64..128, S >= 512, 128 MiB a device) or pass 1 GB; "
                        "XLA's dense attention elsewhere. pallas / "
                        "reference force either")
    # --scan-unroll lives in add_serving_args (single source of truth
    # for both the training layer scan and the serving step scans).
    g.add_argument("--flash-head-fold", action="store_true",
                   help="fold q-head pairs into the trailing block dim "
                        "of the flash BACKWARD kernels (D=64 -> 128 "
                        "lanes, PERF.md lever #1); ineligible layouts "
                        "keep the standard kernels")
    g.add_argument("--bf16", action="store_true", default=True)
    g.add_argument("--fp32", action="store_true",
                   help="disable bf16 compute")
    # fp8 training GEMMs (ISSUE 13, training/fp8.py).
    g.add_argument("--fp8", action="store_true",
                   help="fp8 (e4m3) GEMMs with delayed-scaling amax "
                        "history inside the tp-overlap ring matmuls "
                        "(fwd + bwd; parallel/overlap.py). Requires "
                        "--tp-comm-overlap with tp > 1 on a pp==1, "
                        "cp==1, dense non-MLA/non-MoE layout; the amax/"
                        "scale state rides the train state, so "
                        "checkpoints resume bitwise")
    g.add_argument("--fp8-margin", type=int, default=0,
                   help="delayed-scaling margin: scale = FP8_MAX / "
                        "(amax * 2**margin) — headroom against "
                        "inter-step amax growth (TE --fp8-margin)")
    g.add_argument("--fp8-amax-history-len", type=int, default=16,
                   help="amax history window per (layer, site, tensor); "
                        "the scale follows the max over the window "
                        "(TE --fp8-amax-history-len)")

    g = ap.add_argument_group("learning-rate")  # _add_learning_rate_args
    g.add_argument("--lr", type=float, default=3e-4)
    g.add_argument("--min-lr", type=float, default=3e-5)
    g.add_argument("--lr-decay-style", default="cosine",
                   choices=["cosine", "linear", "constant"])
    g.add_argument("--lr-warmup-iters", type=int, default=0)
    g.add_argument("--lr-decay-iters", type=int, default=None)
    g.add_argument("--weight-decay", type=float, default=0.01)
    g.add_argument("--adam-beta1", type=float, default=0.9)
    g.add_argument("--adam-beta2", type=float, default=0.95)
    g.add_argument("--adam-eps", type=float, default=1e-8)
    g.add_argument("--clip-grad", type=float, default=1.0)
    g.add_argument("--optimizer", default="adam", choices=["adam", "sgd"])

    g = ap.add_argument_group("checkpointing")  # _add_checkpointing_args
    g.add_argument("--save", default=None, metavar="DIR")
    g.add_argument("--load", default=None, metavar="DIR")
    g.add_argument("--save-interval", type=int, default=None)
    g.add_argument("--use-checkpoint-args", action="store_true",
                   help="apply args.json stored with the --load checkpoint "
                        "as defaults (explicit flags still override; "
                        "reference --use-checkpoint-args)")
    g.add_argument("--config-yaml", default=None, metavar="FILE",
                   help="YAML of flag values applied as defaults "
                        "(reference yaml_arguments.py alternative)")

    g = ap.add_argument_group("data")  # _add_data_args parity
    g.add_argument("--data-path", default=None,
                   help=".bin/.idx prefix; omit for the mock dataset")
    g.add_argument("--tokenizer-type", default="NullTokenizer")
    g.add_argument("--tokenizer-name-or-path", default=None)

    g = ap.add_argument_group("logging")  # _add_logging_args parity
    g.add_argument("--tensorboard-dir", default=None)
    g.add_argument("--metrics-jsonl", default=None,
                   help="append per-log-step scalars to this JSONL file")

    g = ap.add_argument_group("fault-tolerance")  # _add_rerun args parity
    g.add_argument("--rerun-mode", default="validate_results",
                   choices=["disabled", "validate_results"])
    g.add_argument("--error-injection-rate", type=float, default=0.0)
    g.add_argument("--log-straggler", action="store_true")
    g.add_argument("--run-workload-inspector-server", action="store_true")
    g.add_argument("--workload-inspector-port", type=int, default=0)
    # Graceful exit + heartbeat + local checkpoints (ISSUE 6; reference
    # --exit-signal-handler / ft_integration / non_persistent ckpts).
    g.add_argument("--exit-signal-handler", action="store_true",
                   help="SIGTERM finishes the in-flight step, force-"
                        "saves an emergency checkpoint (durable + local "
                        "when configured) with resumable side state, "
                        "and exits cleanly; the exit decision is agreed "
                        "across processes")
    g.add_argument("--exit-signal-handler-sigint", action="store_true",
                   help="additionally catch SIGINT (^C) — implies "
                        "--exit-signal-handler")
    g.add_argument("--heartbeat-dir", default=None, metavar="DIR",
                   help="write heartbeat.json (section + timestamp, "
                        "atomic) for an external supervisor "
                        "(ft_integration.read_heartbeat); also enables "
                        "the in-process section-timeout watchdog")
    g.add_argument("--ft-timeouts", default=None,
                   metavar="SETUP,STEP,CKPT",
                   help="heartbeat section timeouts in seconds (three "
                        "comma-separated positive numbers, e.g. "
                        "'600,180,600'); enables the watchdog even "
                        "without --heartbeat-dir")
    g.add_argument("--simulated-fault", default=None, metavar="KIND:DELAY",
                   help="FT drill: schedule a simulated fault after "
                        "DELAY seconds — 'hang' wedges the train loop "
                        "(watchdog/supervisor must catch it), 'exit' "
                        "hard-kills the process (exit code 42)")
    g.add_argument("--non-persistent-save-interval", type=int,
                   default=None, metavar="N",
                   help="fast latest-only local checkpoint every N "
                        "steps (LocalCheckpointManager .npz, atomic "
                        "rename) — cheap enough for small N; restore "
                        "prefers the freshest of (local, durable)")
    g.add_argument("--non-persistent-ckpt-dir", default=None,
                   metavar="DIR",
                   help="directory for the local checkpoints (default: "
                        "<--save>/non_persistent)")

    add_serving_args(ap)   # paged KV serving flags (ISSUE 3)
    add_hybrid_args(ap)
    add_eva_args(ap)

    g = ap.add_argument_group("megascan")  # reference arguments.py:2705ff
    g.add_argument("--trace", action="store_true")
    g.add_argument("--trace-interval", type=int, default=5)
    g.add_argument("--continuous-trace-iterations", type=int, default=2)
    g.add_argument("--trace-dir", default="trace")
    g.add_argument("--trace-granularity", default="full",
                   choices=["full", "schedule", "collective"])
    return ap


def parse_args(ap: argparse.ArgumentParser, argv=None):
    """Parse with YAML-config and checkpoint-args defaults applied.

    Resolution order (lowest → highest precedence): parser defaults →
    --config-yaml values → --use-checkpoint-args stored values → explicit
    CLI flags. Use this instead of ap.parse_args in entry points."""
    import sys

    from megatronapp_tpu.utils.platform import enable_compile_cache

    # Every pretrain_*.py comes through here before its first jit.
    enable_compile_cache()
    argv = list(sys.argv[1:] if argv is None else argv)
    pre, _ = ap.parse_known_args(argv)
    defaults = {}
    if getattr(pre, "config_yaml", None):
        defaults.update(_flags_from_yaml(pre.config_yaml))
    if getattr(pre, "use_checkpoint_args", False):
        if not pre.load:
            raise ValueError("--use-checkpoint-args requires --load")
        stored = load_saved_args(pre.load) or {}
        # Restore ARCHITECTURE/hyperparameter args only — run-control args
        # (where to save, how long to run, IO paths) stay with the new
        # invocation (reference --use-checkpoint-args skips the same set).
        defaults.update({k: v for k, v in stored.items()
                         if k not in _RUN_CONTROL_ARGS})
    if defaults:
        valid = {a.dest for a in ap._actions}
        unknown = sorted(set(defaults) - valid)
        if unknown:
            raise ValueError(f"unknown config keys: {unknown}")
        ap.set_defaults(**defaults)
    args = ap.parse_args(argv)
    if getattr(args, "multi_host", False):
        # Join the multi-host runtime before anything touches the backend
        # (parse_args itself never does). Checked on the FINAL namespace so
        # --multi-host works from the CLI, --config-yaml, and
        # --use-checkpoint-args restores alike.
        from megatronapp_tpu.parallel.mesh import initialize_multi_host
        initialize_multi_host(args.coordinator_address,
                              args.num_processes, args.process_id)
    return args


def _flags_from_yaml(path: str) -> dict:
    """{flag: value} from a YAML file; keys may use dashes or
    underscores."""
    import yaml
    with open(path) as f:
        raw = yaml.safe_load(f) or {}
    if not isinstance(raw, dict):
        raise ValueError(f"{path}: expected a mapping of flag: value")
    return {k.replace("-", "_"): v for k, v in raw.items()}


_ARGS_FILE = "resolved_args.json"

# Args --use-checkpoint-args must NOT resurrect from a stored run.
_RUN_CONTROL_ARGS = frozenset({
    "save", "load", "save_interval", "train_iters", "exit_interval",
    "use_checkpoint_args", "config_yaml", "data_path", "metrics_jsonl",
    "tensorboard_dir", "trace", "trace_dir", "log_interval",
    "eval_interval", "eval_iters",
})


def save_resolved_args(args, save_dir: str):
    """Persist the resolved flag namespace next to the checkpoint
    (reference stores args inside the ckpt; a sidecar JSON keeps ours
    format-agnostic)."""
    import json
    import os
    os.makedirs(save_dir, exist_ok=True)
    payload = {k: v for k, v in vars(args).items()
               if isinstance(v, (int, float, str, bool, list, tuple,
                                 type(None)))}
    with open(os.path.join(save_dir, _ARGS_FILE), "w") as f:
        json.dump(payload, f, indent=1)


def load_saved_args(load_dir: str) -> Optional[dict]:
    import json
    import os
    path = os.path.join(load_dir, _ARGS_FILE)
    if not os.path.exists(path):
        return None
    with open(path) as f:
        return json.load(f)


def _hetero_json(args):
    """--heterogeneous-layers-config-{path,encoded-json} → encoded JSON
    (reference arguments.py _add_heterogeneous_args; the path is read once
    and carried as the encoded string, heterogeneous_config.py:196-205)."""
    encoded = getattr(args, "heterogeneous_layers_config_encoded_json",
                      None)
    path = getattr(args, "heterogeneous_layers_config_path", None)
    if encoded:
        return encoded
    if path:
        with open(path) as f:
            return f.read()
    return None


def _parse_ft_timeouts(s: Optional[str]) -> Optional[tuple]:
    """--ft-timeouts 'SETUP,STEP,CKPT' → (float, float, float), each > 0."""
    if s is None:
        return None
    parts = str(s).split(",")
    try:
        vals = tuple(float(p) for p in parts)
    except ValueError:
        vals = ()
    if len(vals) != 3 or any(v <= 0 for v in vals):
        raise ValueError(
            f"--ft-timeouts expects three positive comma-separated "
            f"seconds 'SETUP,STEP,CKPT' (e.g. '600,180,600'), got {s!r}")
    return vals


def _parse_simulated_fault(s: Optional[str]) -> Optional[tuple]:
    """--simulated-fault 'KIND:DELAY' → (kind, float delay >= 0)."""
    if s is None:
        return None
    kind, sep, delay_s = str(s).partition(":")
    try:
        delay = float(delay_s) if sep else -1.0
    except ValueError:
        delay = -1.0
    if kind not in ("hang", "exit") or delay < 0:
        raise ValueError(
            f"--simulated-fault expects 'KIND:DELAY' with KIND in "
            f"(hang, exit) and DELAY >= 0 seconds, got {s!r}")
    return kind, delay


def _validate_dist_opt_args(args) -> dict:
    """Parse + validate the ZeRO-1 mixed-precision knobs; returns the
    OptimizerConfig field values (clear errors at startup — a bad state
    dtype must not surface as a jit trace failure mid-setup)."""
    from megatronapp_tpu.training.distributed_optimizer import (
        STATE_DTYPES, resolve_state_dtype,
    )
    import jax.numpy as _jnp
    for flag, val in (("--main-params-dtype", args.main_params_dtype),
                      ("--exp-avg-dtype", args.exp_avg_dtype),
                      ("--exp-avg-sq-dtype", args.exp_avg_sq_dtype)):
        if str(val).lower() not in STATE_DTYPES:
            raise ValueError(
                f"{flag} expects one of {sorted(set(STATE_DTYPES))}, "
                f"got {val!r}")
    if resolve_state_dtype(args.main_params_dtype) != _jnp.float32:
        raise ValueError(
            "--main-params-dtype: only fp32 master weights are "
            "supported — the master shard is the fp32 accumulation "
            "domain (low-precision params get one automatically)")
    low_moments = any(
        resolve_state_dtype(v) != _jnp.float32
        for v in (args.exp_avg_dtype, args.exp_avg_sq_dtype))
    if low_moments and not args.use_distributed_optimizer:
        raise ValueError(
            "--exp-avg-dtype/--exp-avg-sq-dtype bf16 require "
            "--use-distributed-optimizer: low-precision moments are "
            "only supported on the ZeRO-1 state layout (the replicated "
            "optax chain stores fp32)")
    if low_moments and getattr(args, "forward_backward_disaggregating",
                               False):
        # The FBD executor path builds the plain chain (the ZeRO-1
        # wrapper is not wired there yet — ROADMAP follow-up); reject at
        # parse time with the real reason instead of the plain chain's
        # guard firing after mesh build.
        raise ValueError(
            "--exp-avg-dtype/--exp-avg-sq-dtype bf16 are not supported "
            "with --forward-backward-disaggregating: the FBD path runs "
            "the replicated optax chain (ZeRO-1 wiring is a ROADMAP "
            "follow-up)")
    return dict(
        main_params_dtype=args.main_params_dtype,
        exp_avg_dtype=args.exp_avg_dtype,
        exp_avg_sq_dtype=args.exp_avg_sq_dtype,
        dist_opt_comm=args.dist_opt_comm,
    )


def _validate_ft_args(args) -> dict:
    """Parse + validate the fault-tolerance flags; returns the
    TrainingConfig field values (clear errors at startup, not a stack
    trace hours into a run)."""
    ft_timeouts = _parse_ft_timeouts(args.ft_timeouts)
    simulated_fault = _parse_simulated_fault(args.simulated_fault)
    npsi = args.non_persistent_save_interval
    if npsi is not None and npsi <= 0:
        raise ValueError(
            f"--non-persistent-save-interval must be a positive step "
            f"count, got {npsi}")
    # The default-location policy (<--save>/non_persistent) lives in
    # TrainingConfig.resolved_non_persistent_dir — here we only reject
    # configs it cannot resolve, at parse time.
    if npsi and not (args.non_persistent_ckpt_dir or args.save):
        raise ValueError(
            "--non-persistent-save-interval needs a directory: pass "
            "--non-persistent-ckpt-dir or --save (the default is "
            "<--save>/non_persistent)")
    return dict(
        exit_signal_handler=(args.exit_signal_handler
                             or args.exit_signal_handler_sigint),
        exit_signal_handler_sigint=args.exit_signal_handler_sigint,
        heartbeat_dir=args.heartbeat_dir,
        ft_timeouts=ft_timeouts,
        simulated_fault=simulated_fault,
        non_persistent_save_interval=npsi,
        non_persistent_ckpt_dir=args.non_persistent_ckpt_dir,
    )


def configs_from_args(args) -> Tuple[TransformerConfig, ParallelConfig,
                                     TrainingConfig, OptimizerConfig]:
    """Build + cross-validate the four configs (validate_args parity)."""
    if args.preset:
        import dataclasses as _dc
        from megatronapp_tpu.models.presets import PRESETS
        model = PRESETS[args.preset]()
        # Explicitly-passed flags override preset fields. Detect "explicit"
        # by re-parsing with defaults suppressed.
        sentinel = build_parser().parse_args([])
        overrides = {}
        flag_to_field = {
            "num_layers": "num_layers", "hidden_size": "hidden_size",
            "num_attention_heads": "num_attention_heads",
            "num_query_groups": "num_query_groups",
            "ffn_hidden_size": "ffn_hidden_size",
            "vocab_size": "vocab_size",
            "max_position_embeddings": "max_position_embeddings",
            "init_method_std": "init_method_std",
            "tp_comm_overlap": "tp_comm_overlap",
            "tp_sharded_stage": "tp_sharded_stage",
        }
        for flag, field in flag_to_field.items():
            val = getattr(args, flag)
            if val != getattr(sentinel, flag):
                overrides[field] = val
        overrides.update(hybrid_fields(args))
        overrides.update(eva_fields(args))
        if overrides:
            model = _dc.replace(model, **overrides)
    else:
        activation = ActivationKind.gelu
        if args.swiglu:
            activation = ActivationKind.swiglu
        elif args.squared_relu:
            activation = ActivationKind.squared_relu
        model = TransformerConfig(
            num_layers=args.num_layers,
            hidden_size=args.hidden_size,
            num_attention_heads=args.num_attention_heads,
            num_query_groups=args.num_query_groups,
            ffn_hidden_size=args.ffn_hidden_size,
            kv_channels=args.kv_channels,
            vocab_size=args.vocab_size,
            max_position_embeddings=args.max_position_embeddings,
            position_embedding=PositionEmbeddingKind(
                args.position_embedding_type),
            rotary_base=args.rotary_base,
            rotary_percent=args.rotary_percent,
            rope_scaling_factor=args.rope_scaling_factor,
            yarn_original_max_position=args.yarn_original_max_position,
            yarn_mscale_coeff=args.yarn_mscale_coeff,
            normalization=NormKind(args.normalization),
            activation=activation,
            add_bias_linear=not args.disable_bias_linear,
            add_qkv_bias=args.add_qkv_bias,
            qk_layernorm=args.qk_layernorm,
            untie_embeddings_and_output_weights=(
                args.untie_embeddings_and_output_weights),
            init_method_std=args.init_method_std,
            num_moe_experts=args.num_experts,
            moe_router_topk=args.moe_router_topk,
            moe_ffn_hidden_size=args.moe_ffn_hidden_size,
            moe_aux_loss_coeff=args.moe_aux_loss_coeff,
            moe_z_loss_coeff=args.moe_z_loss_coeff,
            moe_capacity_factor=args.moe_expert_capacity_factor,
            moe_layer_freq=args.moe_layer_freq,
            moe_first_k_dense=args.moe_first_k_dense,
            moe_router_norm_topk_prob=args.moe_router_norm_topk_prob,
            moe_routed_scaling_factor=args.moe_routed_scaling_factor,
            moe_shared_expert_intermediate_size=(
                args.moe_shared_expert_intermediate_size),
            mtp_num_layers=args.mtp_num_layers,
            mtp_loss_scaling_factor=args.mtp_loss_scaling_factor,
            multi_latent_attention=args.multi_latent_attention,
            q_lora_rank=args.q_lora_rank,
            kv_lora_rank=args.kv_lora_rank,
            qk_head_dim=args.qk_head_dim,
            qk_pos_emb_head_dim=args.qk_pos_emb_head_dim,
            v_head_dim=args.v_head_dim,
            cp_comm_type=args.cp_comm_type,
            hierarchical_cp_a2a_size=(
                args.hierarchical_context_parallel_sizes[0]
                if args.hierarchical_context_parallel_sizes else 2),
            remat_policy=args.recompute_granularity,
            tp_comm_overlap=args.tp_comm_overlap,
            tp_sharded_stage=args.tp_sharded_stage,
            cp_comm_overlap=args.cp_comm_overlap,
            moe_comm_overlap=args.moe_comm_overlap,
            attention_impl=args.attention_impl,
            scan_unroll=args.scan_unroll,
            flash_head_fold=args.flash_head_fold,
            compute_dtype=jnp.float32 if args.fp32 else jnp.bfloat16,
            heterogeneous_layers_config_json=_hetero_json(args),
            **hybrid_fields(args),
            **eva_fields(args),
        )

    if getattr(args, "fp8", False):
        import dataclasses as _dc_fp8
        if args.fp8_amax_history_len < 1:
            raise ValueError(
                f"--fp8-amax-history-len must be >= 1, got "
                f"{args.fp8_amax_history_len}")
        model = _dc_fp8.replace(
            model, fp8=True, fp8_margin=args.fp8_margin,
            fp8_amax_history_len=args.fp8_amax_history_len)

    vpp = 1
    if args.num_layers_per_virtual_pipeline_stage:
        per_stage = (model.num_layers //
                     args.pipeline_model_parallel_size)
        if per_stage % args.num_layers_per_virtual_pipeline_stage != 0:
            raise ValueError(
                "--num-layers-per-virtual-pipeline-stage must divide "
                "layers-per-stage")
        vpp = per_stage // args.num_layers_per_virtual_pipeline_stage

    parallel = ParallelConfig(
        tensor_parallel=args.tensor_model_parallel_size,
        pipeline_parallel=args.pipeline_model_parallel_size,
        context_parallel=args.context_parallel_size,
        expert_parallel=args.expert_model_parallel_size,
        virtual_pipeline_parallel=vpp,
        sequence_parallel=args.sequence_parallel,
        distributed_optimizer=args.use_distributed_optimizer,
        forward_backward_disaggregating=args.forward_backward_disaggregating,
        pipeline_order_policy="bfc" if args.use_dpp else "dfc",
        use_dpp=args.use_dpp,
        pp_schedule=args.pp_schedule,
        pp_plan_from_trace=args.pp_plan_from_trace,
    )

    # Schedule-flag cross-validation (ISSUE 15): the host-driven MegaDPP
    # runtime executes its own dynamic order — a non-default
    # --pp-schedule there would be silently ignored, which is worse
    # than an error.
    if args.use_dpp and args.pp_schedule != "1f1b":
        raise ValueError(
            f"--pp-schedule {args.pp_schedule} does not compose with "
            "--use-dpp (the host-driven MegaDPP runtime schedules "
            "dynamically); drop one of the flags")
    if args.use_dpp and args.pp_plan_from_trace:
        raise ValueError(
            "--pp-plan-from-trace does not compose with --use-dpp (the "
            "host runtime already schedules dynamically); drop one")
    # Same policy for the FBD executor (it runs its own legacy
    # schedule; train.py re-checks for programmatic callers).
    if args.forward_backward_disaggregating and (
            args.pp_schedule != "1f1b" or args.pp_plan_from_trace):
        raise ValueError(
            "--pp-schedule/--pp-plan-from-trace do not compose with "
            "--forward-backward-disaggregating (the FBD executor runs "
            "its own schedule); drop one")

    # fp8 eligibility (ISSUE 13): reject impossible layouts at parse
    # time with the predicate that failed (training/fp8.py names it) —
    # a silent no-op fp8 run would be worse than an error.
    if model.fp8:
        from megatronapp_tpu.training.fp8 import fp8_ineligible_reason
        reason = fp8_ineligible_reason(model, parallel)
        if reason is not None:
            raise ValueError(reason)

    # Cross-validation (reference validate_args: seq/cp divisibility :695).
    if args.seq_length % (args.context_parallel_size or 1) != 0:
        raise ValueError("--seq-length must be divisible by "
                         "--context-parallel-size")
    if args.hierarchical_context_parallel_sizes:
        a2a_sz, ring_sz = args.hierarchical_context_parallel_sizes
        if a2a_sz * ring_sz != args.context_parallel_size:
            raise ValueError(
                f"--hierarchical-context-parallel-sizes {a2a_sz} {ring_sz} "
                f"must multiply to --context-parallel-size "
                f"({args.context_parallel_size})")
        if args.cp_comm_type != "a2a+p2p":
            raise ValueError(
                "--hierarchical-context-parallel-sizes requires "
                "--cp-comm-type a2a+p2p")
    if args.seq_length > model.max_position_embeddings:
        raise ValueError("--seq-length exceeds --max-position-embeddings")

    # --tp-comm-overlap divisibility (fail at parse time with a clear
    # message instead of a shard_map trace failure / silent GSPMD
    # fallback deep inside the first step): the ring primitives shard the
    # projection output/input dims — and, inside a pp>1 manual pipeline,
    # whole heads and the sequence — evenly over tp.
    tp = args.tensor_model_parallel_size
    if model.tp_comm_overlap and tp > 1:
        def _reject(what, dim):
            raise ValueError(
                f"--tp-comm-overlap: {what} ({dim}) is not divisible by "
                f"--tensor-model-parallel-size ({tp}); pick divisible "
                "sizes or drop the flag")
        if model.hidden_size % tp:
            _reject("--hidden-size", model.hidden_size)
        if not model.is_moe or model.moe_layer_freq > 1:
            if model.ffn_hidden_size % tp:
                _reject("--ffn-hidden-size (fc1/fc2 shard dim)",
                        model.ffn_hidden_size)
        # The tp-sharded stage body runs when pp>1 and the kill switch
        # is off (tp_stage_eligible) — INCLUDING cp>1 since the
        # pp x cp x tp composition (ISSUE 15), where the residual
        # stream shards the sequence over (cp, tp) jointly on the
        # contiguous p2p cp ring. Layouts the composition excludes
        # (MLA, MoE, a2a-family cp comms) keep the tp-replicated body,
        # so the stricter whole-head / sequence divisibility rules must
        # not reject those configs.
        from megatronapp_tpu.parallel.overlap import (
            tp_stage_cp_excluded_reason,
        )
        cp = args.context_parallel_size or 1
        tp_stage_candidate = (args.pipeline_model_parallel_size > 1
                              and model.tp_sharded_stage
                              and (cp <= 1
                                   or tp_stage_cp_excluded_reason(
                                       model, cp) is None))
        seq_shard = tp * (cp if cp > 1 else 1)
        if tp_stage_candidate and args.seq_length % seq_shard:
            what = (f"tp ({tp})" if cp <= 1
                    else f"cp*tp ({seq_shard})")
            raise ValueError(
                "--tp-comm-overlap with pp>1 runs the tp-SHARDED "
                "pipeline stage body, which shards the sequence over "
                f"{'tp' if cp <= 1 else '(cp, tp) jointly'}: "
                f"--seq-length ({args.seq_length}) must divide by "
                f"{what} — or pass --no-tp-sharded-stage for the "
                "replicated baseline")
        if model.multi_latent_attention:
            # Dense MLA never routes through the GSPMD overlap rings
            # (only its MLP does — covered by the ffn check above); only
            # the pp>1 tp-SHARDED stage body slices whole MLA heads.
            if tp_stage_candidate and model.num_attention_heads % tp:
                raise ValueError(
                    "--tp-comm-overlap with pp>1 runs the tp-SHARDED "
                    "pipeline stage body, which slices WHOLE MLA heads: "
                    f"--num-attention-heads ({model.num_attention_heads})"
                    f" must divide by tp ({tp}) — or pass "
                    "--no-tp-sharded-stage for the replicated baseline")
        else:
            d = model.head_dim
            if (model.num_attention_heads * d) % tp:
                _reject("QKV projection dim (heads*head_dim)",
                        model.num_attention_heads * d)
            if (2 * model.num_query_groups * d) % tp:
                _reject("KV projection dim (2*num-query-groups*head_dim)",
                        2 * model.num_query_groups * d)
            if tp_stage_candidate and (model.num_attention_heads % tp
                                       or model.num_query_groups % tp):
                raise ValueError(
                    "--tp-comm-overlap with pp>1 runs the tp-SHARDED "
                    "pipeline stage body, which slices WHOLE heads: "
                    f"--num-attention-heads ({model.num_attention_heads}) "
                    f"and --num-query-groups ({model.num_query_groups}) "
                    f"must both divide by tp ({tp}) — or pass "
                    "--no-tp-sharded-stage for the replicated baseline")

    training = TrainingConfig(
        rampup_batch_size=(tuple(args.rampup_batch_size)
                           if args.rampup_batch_size else None),
        sharded_init=args.sharded_init,
        **_validate_ft_args(args),
        metrics_jsonl=args.metrics_jsonl,
        tensorboard_dir=args.tensorboard_dir,
        rerun_mode=args.rerun_mode,
        error_injection_rate=args.error_injection_rate,
        log_straggler=args.log_straggler,
        run_workload_inspector_server=args.run_workload_inspector_server,
        workload_inspector_port=args.workload_inspector_port,
        micro_batch_size=args.micro_batch_size,
        global_batch_size=args.global_batch_size,
        seq_length=args.seq_length,
        train_iters=args.train_iters,
        seed=args.seed,
        log_interval=args.log_interval,
        eval_interval=args.eval_interval,
        eval_iters=args.eval_iters,
        exit_interval=args.exit_interval,
        save_dir=args.save,
        load_dir=args.load,
        save_interval=args.save_interval,
        trace=args.trace,
        trace_interval=args.trace_interval,
        continuous_trace_iterations=args.continuous_trace_iterations,
        trace_dir=args.trace_dir,
        trace_granularity=args.trace_granularity,
    )

    optimizer = OptimizerConfig(
        optimizer=args.optimizer,
        **_validate_dist_opt_args(args),
        lr=args.lr, min_lr=args.min_lr,
        lr_decay_style=args.lr_decay_style,
        lr_warmup_iters=args.lr_warmup_iters,
        lr_decay_iters=args.lr_decay_iters,
        weight_decay=args.weight_decay,
        adam_beta1=args.adam_beta1, adam_beta2=args.adam_beta2,
        adam_eps=args.adam_eps,
        clip_grad=args.clip_grad,
    )
    return model, parallel, training, optimizer


def make_batch_iter_factory(args, training: TrainingConfig,
                            model: TransformerConfig):
    """Data-iterator FACTORY from --data-path (.bin/.idx): called with the
    resume sample offset so checkpoint restarts skip already-consumed data
    (reference consumed_train_samples semantics). Returns None for the
    mock-data fallback (pretrain_gpt builds its own resume-aware stream)."""
    if not args.data_path:
        return None
    from megatronapp_tpu.data.gpt_dataset import GPTDataset, gpt_batches
    from megatronapp_tpu.data.indexed_dataset import IndexedDataset
    indexed = IndexedDataset(args.data_path)
    num_samples = (training.train_iters * training.global_batch_size)
    ds = GPTDataset(indexed, training.seq_length, num_samples,
                    seed=training.seed)

    def factory(start_sample_idx: int = 0):
        return gpt_batches(ds, training.global_batch_size,
                           start_idx=start_sample_idx)

    return factory
