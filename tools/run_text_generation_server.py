"""Launch the text-generation server on a checkpoint.

Parity with /root/reference/tools/run_text_generation_server.py (engine
assembly :120-150, --enable-ws-server :158 — WS is always mounted at /ws
here).

Usage:
  python tools/run_text_generation_server.py --load-dir CKPT \
      --preset gpt2-125m --tokenizer-type GPT2BPETokenizer --port 5000
"""

import argparse
import sys

sys.path.insert(0, __file__.rsplit("/tools/", 1)[0])


def main():
    import jax

    from megatronapp_tpu.data.tokenizers import build_tokenizer
    from megatronapp_tpu.inference.dynamic_engine import (
        DynamicInferenceEngine,
    )
    from megatronapp_tpu.inference.engine import StaticInferenceEngine
    from megatronapp_tpu.inference.server import TextGenerationServer
    from megatronapp_tpu.models.gpt import init_gpt_params
    from megatronapp_tpu.models.presets import PRESETS
    from megatronapp_tpu.training.checkpointing import CheckpointManager

    ap = argparse.ArgumentParser()
    ap.add_argument("--load-dir", default=None)
    ap.add_argument("--load-quantized", default=None,
                    help="int8 .npz from tools/checkpoint/quantize.py "
                         "(dequantized on load)")
    ap.add_argument("--preset", default="gpt2-125m",
                    choices=sorted(PRESETS))
    ap.add_argument("--tokenizer-type", default="NullTokenizer")
    ap.add_argument("--tokenizer-name-or-path", default=None)
    ap.add_argument("--port", type=int, default=5000)
    ap.add_argument("--host", default="0.0.0.0")
    ap.add_argument("--max-seq-len", type=int, default=None)
    # Serving flags shared with the main parser (config/arguments.py
    # add_serving_args — single source of truth): --engine, --max-batch,
    # --kv-block-size, --num-kv-blocks, --scan-unroll,
    # --no-prefix-caching.
    from megatronapp_tpu.config.arguments import (
        add_eva_args, add_hybrid_args, add_serving_args, eva_fields,
        hybrid_fields, validate_serving_args,
    )
    add_serving_args(ap)
    # --attn-layer-period / -offset, --ssm-inner-norms, --ssm-heads /
    # -head-dim / -state-dim / -chunk-size: a hybrid state-space stack on a
    # preset (Mamba-1 or, with heads, Mamba-2), served by --engine dynamic.
    add_hybrid_args(ap)
    # --eva-window-size / --eva-chunk-size: EVA attention on a preset,
    # served by --engine dynamic.
    add_eva_args(ap)
    args = ap.parse_args()
    from megatronapp_tpu.utils.platform import (
        device_line, enable_compile_cache,
    )
    enable_compile_cache()

    # Telemetry opt-in BEFORE engine construction, so admission-time
    # counters and the first prefill spans are captured (ISSUE 12).
    if args.serving_metrics:
        from megatronapp_tpu.utils import metrics as telemetry
        telemetry.enable()
        print("telemetry registry enabled — GET /metrics serves "
              "Prometheus text")
    if args.request_trace:
        from megatronapp_tpu.trace.request_trace import (
            get_request_tracer,
        )
        get_request_tracer().configure(
            enabled=True, capacity=args.request_trace_capacity)
        print(f"request tracing enabled (ring capacity "
              f"{args.request_trace_capacity}) — GET /trace serves a "
              "merged Chrome trace")

    cfg = PRESETS[args.preset]()
    validate_serving_args(
        args, multi_latent_attention=cfg.multi_latent_attention)
    import dataclasses
    shape = {**hybrid_fields(args), **eva_fields(args)}
    if shape:
        cfg = dataclasses.replace(cfg, **shape)
    if cfg.is_eva and args.engine != "dynamic":
        raise SystemExit(
            "a model with EVA attention keeps its chunk summaries in the "
            "paged engine's page tables: serve it with --engine dynamic")
    if cfg.num_ssm_layers and args.engine != "dynamic":
        raise SystemExit(
            "a model with state-space layers keeps its recurrent state in "
            "the paged engine's slots: serve it with --engine dynamic")
    if args.scan_unroll != 1:
        cfg = dataclasses.replace(cfg, scan_unroll=args.scan_unroll)
    mcfg = None
    if args.engine == "mamba":
        from megatronapp_tpu.models.mamba import (
            MambaConfig, init_mamba_params,
        )
        mcfg = MambaConfig()
        params, _ = init_mamba_params(jax.random.PRNGKey(0), cfg, mcfg)
    else:
        params, _ = init_gpt_params(jax.random.PRNGKey(0), cfg)
    if args.load_quantized:
        from tools.checkpoint.quantize import load_quantized_params
        # --quantized-weights keeps the int8 kernels RESIDENT (dequant
        # fused at matmul entry, inference/quantization.py
        # residentize_params) instead of dequantizing on load.
        loaded = load_quantized_params(args.load_quantized,
                                       dequantize=not
                                       args.quantized_weights)
        expect = "layers" if args.engine == "mamba" else "block"
        if expect not in loaded:
            raise SystemExit(
                f"--load-quantized artifact does not look like a "
                f"{args.engine} checkpoint (missing '{expect}'); "
                f"top-level keys: {sorted(loaded)[:8]}")
        if args.quantized_weights:
            from megatronapp_tpu.inference.quantization import (
                resident_nbytes, residentize_params,
            )
            params = residentize_params(loaded)
            print(f"serving RESIDENT int8 params from "
                  f"{args.load_quantized} "
                  f"({resident_nbytes(params)/2**20:.1f} MiB on device)")
        else:
            params = loaded
            print(f"loaded int8-quantized params from "
                  f"{args.load_quantized}")
    elif args.load_dir:
        mngr = CheckpointManager(args.load_dir)
        state = mngr.restore({"step": 0, "params": params, "opt_state": {}})
        if state is not None:
            params = state["params"]
            print(f"loaded checkpoint step {state['step']}")
        mngr.close()
    if args.quantized_weights and not args.load_quantized:
        # (mamba is rejected by validate_serving_args above.)
        from megatronapp_tpu.inference.quantization import (
            quantize_params, residentize_params,
        )
        # resident_only: quantize ONLY leaves that will stay int8 —
        # rounding a weight residentize would dequantize eagerly again
        # costs accuracy for zero memory win.
        qparams, report = quantize_params(params, resident_only=True)
        params = residentize_params(qparams)
        worst = max(report.values()) if report else 0.0
        print(f"PTQ-quantized {len(report)} kernels at startup "
              f"(max |w err| {worst:.4g}); int8 kept resident")
    tok = build_tokenizer(args.tokenizer_type, args.tokenizer_name_or_path,
                          vocab_size=cfg.vocab_size)
    if args.engine == "mamba":
        from megatronapp_tpu.inference.engine import MambaInferenceEngine
        engine = MambaInferenceEngine(params, cfg, mcfg, tokenizer=tok,
                                      max_seq_len=args.max_seq_len)
        print(f"serving mamba on {args.host}:{args.port}")
        TextGenerationServer(engine, args.host, args.port).run()
        return
    if getattr(args, "engine", "static") == "dynamic":
        draft_params = draft_cfg = None
        if args.spec_method == "draft":
            if args.draft_model is None:
                raise SystemExit("--spec-method draft needs --draft-model "
                                 "(a models/presets.py preset)")
            draft_cfg = PRESETS[args.draft_model]()
            draft_params, _ = init_gpt_params(jax.random.PRNGKey(1),
                                              draft_cfg)
            if args.draft_load_dir:
                mngr = CheckpointManager(args.draft_load_dir)
                state = mngr.restore({"step": 0, "params": draft_params,
                                      "opt_state": {}})
                if state is not None:
                    draft_params = state["params"]
                    print(f"loaded draft checkpoint step {state['step']}")
                mngr.close()
            else:
                print("WARNING: draft model is randomly initialized "
                      "(--draft-load-dir not given) — acceptance will be "
                      "poor; outputs stay exact either way")
        spec = None if args.spec_method == "none" else args.spec_method

        def make_adapter_cache():
            # Multi-tenant LoRA serving (ISSUE 19): one HBM adapter
            # cache PER ENGINE (fleet replicas each own their banks —
            # the router's tenant affinity keeps a tenant's requests on
            # the replica already holding its adapter).
            if not args.lora_dir:
                return None
            from megatronapp_tpu.inference.lora import (
                AdapterCache, AdapterRegistry,
            )
            registry = AdapterRegistry(args.lora_dir)
            cache = AdapterCache(
                cfg, registry,
                max_resident=args.max_resident_adapters,
                rank=args.lora_rank)
            print(f"LoRA serving from {args.lora_dir}: "
                  f"{len(registry.ids())} adapters on disk, rank "
                  f"{args.lora_rank}, {args.max_resident_adapters} "
                  f"resident ({cache.adapter_nbytes / 2**20:.2f} MiB "
                  f"each)")
            return cache

        if getattr(args, "fleet_procs", 0) > 0:
            # Cross-process fleet (ISSUE 18): N replica WORKER
            # PROCESSES behind the RPC router
            # (inference/fleet_rpc.py). Workers build deterministic
            # seed-params from the spec; this process then pushes ITS
            # params (checkpoint-restored / PTQ-quantized above) over
            # the set_params verb so the fleet serves the loaded
            # weights.
            import tempfile

            from megatronapp_tpu.inference.fleet_rpc import (
                ProcessFleetRouter, default_engine_spec,
            )
            proc_spec = default_engine_spec(
                num_layers=cfg.num_layers,
                hidden_size=cfg.hidden_size,
                num_attention_heads=cfg.num_attention_heads,
                num_query_groups=(cfg.num_query_groups
                                  or cfg.num_attention_heads),
                vocab_size=cfg.vocab_size,
                max_position_embeddings=cfg.max_position_embeddings,
                max_batch=args.max_batch,
                max_seq_len=args.max_seq_len,
                block_size=args.kv_block_size,
                num_blocks=args.num_kv_blocks,
                kv_cache_dtype=args.kv_cache_dtype,
                prefill_chunk=args.prefill_chunk,
                kv_spill_host_mb=args.kv_spill_host_mb,
                kv_spill_watermark_blocks=(
                    args.kv_spill_watermark_blocks),
                lora_dir=args.lora_dir,
                lora_rank=args.lora_rank,
                max_resident_adapters=args.max_resident_adapters)
            state_dir = tempfile.mkdtemp(prefix="fleet-state-")
            # Workers are fresh processes: telemetry / request tracing
            # opt-ins ride the env (utils/metrics.py MEGATRON_METRICS,
            # trace/request_trace.py MEGATRON_REQUEST_TRACE enable at
            # import) so /metrics and the merged /trace see them.
            worker_env = {}
            if args.serving_metrics:
                worker_env["MEGATRON_METRICS"] = "1"
            if args.request_trace:
                worker_env["MEGATRON_REQUEST_TRACE"] = "1"
            router = ProcessFleetRouter.launch(
                state_dir, proc_spec, num_replicas=args.fleet_procs,
                slo_ms=args.decode_slo_ms,
                base_port=args.replica_rpc_port,
                supervise=(None if args.supervisor == "off"
                           else args.supervisor),
                prefix_store_mb=args.fleet_prefix_store_mb,
                extra_env=worker_env)
            router.set_params(params)
            router.tokenizer = tok
            print(f"serving CROSS-PROCESS fleet of {args.fleet_procs} "
                  f"replica workers on {args.host}:{args.port} "
                  f"(state_dir={state_dir}, "
                  f"supervisor={args.supervisor}, "
                  f"kv={args.kv_cache_dtype})")
            try:
                TextGenerationServer(router, args.host,
                                     args.port).run()
            finally:
                router.shutdown()
            return
        if args.serve_fleet > 1 or args.fleet_autoscale:
            # Fleet serving (ISSUE 14): N replicas behind the
            # KV-affinity router. Disagg replicas divide the device
            # pool into disjoint slices; plain (non-disagg) replicas
            # all run on the default device — per-replica device
            # placement for plain fleets is a recorded follow-up
            # (the tp path already needs a per-replica MeshContext).
            from megatronapp_tpu.inference.fleet import FleetRouter
            devices = jax.devices()
            n = args.serve_fleet
            # Disagg replicas divide the WHOLE device pool so the
            # autoscaler has room to move tp groups between each
            # replica's prefill/decode sub-meshes; a minimal 2*tp
            # slice would pin every split at tp/tp and recommend()
            # could never fire.
            if args.serve_disagg and len(devices) < n * 2 * args.serve_tp:
                raise SystemExit(
                    f"--serve-fleet {n} --serve-disagg at tp="
                    f"{args.serve_tp} needs {n * 2 * args.serve_tp} "
                    f"devices ({n} replicas x 2 sub-meshes x tp), "
                    f"have {len(devices)}")
            per = max(2 * args.serve_tp,
                      (len(devices) // max(n, 1))
                      // args.serve_tp * args.serve_tp)
            if args.fleet_autoscale and per <= 2 * args.serve_tp:
                print("WARNING: --fleet-autoscale has no headroom — "
                      f"each replica gets {per} devices (= 2*tp), so "
                      "the prefill/decode split cannot move; add "
                      "devices or lower --serve-fleet/--serve-tp")

            def replica_engine(i, **hints):
                if args.serve_disagg:
                    from megatronapp_tpu.inference.disagg import (
                        DisaggServingEngine,
                    )
                    hints.setdefault("prefill_devices",
                                     per // 2 // args.serve_tp
                                     * args.serve_tp)
                    return DisaggServingEngine(
                        params, cfg, tokenizer=tok,
                        max_batch=args.max_batch,
                        max_seq_len=args.max_seq_len,
                        block_size=args.kv_block_size,
                        num_blocks=args.num_kv_blocks,
                        enable_prefix_caching=args.prefix_caching,
                        prefill_chunk=args.prefill_chunk,
                        prefill_slots=args.disagg_prefill_slots,
                        decode_slo_ms=args.decode_slo_ms,
                        tp=args.serve_tp,
                        devices=devices[i * per:(i + 1) * per],
                        spec_method=spec, spec_k=args.spec_k,
                        draft_params=draft_params, draft_cfg=draft_cfg,
                        kv_cache_dtype=args.kv_cache_dtype, **hints)
                return DynamicInferenceEngine(
                    params, cfg, tokenizer=tok,
                    max_batch=args.max_batch,
                    max_seq_len=args.max_seq_len,
                    block_size=args.kv_block_size,
                    num_blocks=args.num_kv_blocks,
                    enable_prefix_caching=args.prefix_caching,
                    spec_method=spec, spec_k=args.spec_k,
                    draft_params=draft_params, draft_cfg=draft_cfg,
                    prefill_chunk=args.prefill_chunk,
                    kv_cache_dtype=args.kv_cache_dtype,
                    adapter_cache=make_adapter_cache(),
                    spill_host_mb=args.kv_spill_host_mb,
                    spill_watermark_blocks=(
                        args.kv_spill_watermark_blocks))

            engine = FleetRouter(
                engine_factory=replica_engine, num_replicas=n,
                migrate=args.fleet_migrate,
                autoscale=args.fleet_autoscale,
                slo_ms=args.decode_slo_ms,
                prefix_store_mb=args.fleet_prefix_store_mb)
            print(f"serving FLEET of {n} "
                  f"{'disagg' if args.serve_disagg else 'dynamic'} "
                  f"replicas on {args.host}:{args.port} "
                  f"(policy=affinity, migrate={args.fleet_migrate}, "
                  f"autoscale={args.fleet_autoscale}, "
                  f"kv={args.kv_cache_dtype})")
            TextGenerationServer(engine, args.host, args.port).run()
            return
        if args.serve_disagg:
            from megatronapp_tpu.inference.disagg import (
                DisaggServingEngine,
            )
            engine = DisaggServingEngine(
                params, cfg, tokenizer=tok, max_batch=args.max_batch,
                max_seq_len=args.max_seq_len,
                block_size=args.kv_block_size,
                num_blocks=args.num_kv_blocks,
                enable_prefix_caching=args.prefix_caching,
                prefill_chunk=args.prefill_chunk,
                prefill_slots=args.disagg_prefill_slots,
                decode_slo_ms=args.decode_slo_ms, tp=args.serve_tp,
                spec_method=spec, spec_k=args.spec_k,
                draft_params=draft_params, draft_cfg=draft_cfg,
                kv_cache_dtype=args.kv_cache_dtype)
            print(f"serving DISAGGREGATED on {args.host}:{args.port} "
                  f"(prefill {engine.prefill_ctx.num_devices}d / decode "
                  f"{engine.decode_ctx.num_devices}d, tp={args.serve_tp}, "
                  f"slo={args.decode_slo_ms} ms, "
                  f"kv={args.kv_cache_dtype}, "
                  f"spec={spec or 'off'})")
            TextGenerationServer(engine, args.host, args.port).run()
            return
        tp_ctx = None
        if args.serve_tp > 1:
            from megatronapp_tpu.config.parallel_config import (
                ParallelConfig,
            )
            from megatronapp_tpu.parallel.mesh import build_mesh
            tp_ctx = build_mesh(
                ParallelConfig(tensor_parallel=args.serve_tp),
                devices=jax.devices()[:args.serve_tp])
        engine = DynamicInferenceEngine(
            params, cfg, tokenizer=tok, max_batch=args.max_batch,
            max_seq_len=args.max_seq_len,
            block_size=args.kv_block_size, num_blocks=args.num_kv_blocks,
            enable_prefix_caching=args.prefix_caching,
            spec_method=spec,
            spec_k=args.spec_k, draft_params=draft_params,
            draft_cfg=draft_cfg, prefill_chunk=args.prefill_chunk,
            ctx=tp_ctx, kv_cache_dtype=args.kv_cache_dtype,
            adapter_cache=make_adapter_cache(),
            spill_host_mb=args.kv_spill_host_mb,
            spill_watermark_blocks=args.kv_spill_watermark_blocks)
        if args.lora_dir:
            # Tenant SLO composition point: all tenants default to the
            # "standard" class; operators assign premium/batch classes
            # programmatically (inference/lora.py TenantSLO.assign).
            from megatronapp_tpu.inference.lora import TenantSLO
            engine.tenant_slo = TenantSLO()
        print(device_line(None if tp_ctx is None else tp_ctx.mesh))
        print(f"serving continuous batching on {args.host}:{args.port} "
              f"(kv={args.kv_cache_dtype}, tp={args.serve_tp}, "
              f"lora={'on' if args.lora_dir else 'off'}, "
              f"spec={engine.spec_method or 'off'})")
        print(engine.startup_line())
        TextGenerationServer(engine, args.host, args.port).run()
        return
    engine = StaticInferenceEngine(params, cfg, tokenizer=tok,
                                   max_seq_len=args.max_seq_len)
    print(device_line())
    print(f"serving on {args.host}:{args.port} (PUT /api, WS /ws)")
    TextGenerationServer(engine, args.host, args.port).run()


if __name__ == "__main__":
    main()
