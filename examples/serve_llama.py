#!/usr/bin/env python
"""Serve a LLaMA-family model: the deployment user journey.

Covers the four serving tiers end to end:
  1. paged-KV generation through LLMEngine (device-side decode loop:
     the WHOLE generation is one compiled dispatch; its gain over
     per-token dispatch on the chip: not measured on this stack);
  2. int8 weight-only serving (expected to pay at 7B+, where decode is
     weight-streaming-bound; on the chip: not measured on this stack);
  3. checkpoint-scale loading: a LazyGuard (meta-init) model materializes
     leaf-by-leaf straight to the serving dtype at engine construction,
     so a 7B reaches a 16 GB chip as 13.5 GB bf16 / 6.7 GB int8 without
     the 27 GB eager-f32 tree ever existing;
  4. continuous batching (--scheduler): ragged requests stream through
     the ContinuousBatchingEngine — per-request retirement, chunked
     prefill, prefix-cached prompt pages (docs/serving.md).

Run anywhere (CPU smoke):  python examples/serve_llama.py [--scheduler]
On a TPU host the same code runs unchanged on the chip (chip_smoke.py
drives the 7B int8 --scheduler shape there and checks what comes out).

ref journey: Paddle's inference deployment (AnalysisPredictor +
fused_multi_transformer serving); the paged-KV engine is this
framework's fused-decode tier.
"""
import argparse
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", choices=["tiny", "350m", "7b"],
                    default="tiny", help="geometry (tiny = CPU smoke)")
    ap.add_argument("--plan", metavar="auto|PATH.json", default=None,
                    help="serving plan from the cost-model planner "
                         "(docs/distributed_perf.md \"Plan search\"): "
                         "'auto' searches the feasible tp x topology x "
                         "megakernel x decode_block space for this "
                         "--model on the visible devices and applies "
                         "the top-ranked EngineSpec; a PATH.json loads "
                         "a spec saved by EngineSpec.save / "
                         "benchmarks/plan_sweep.py. The plan SUBSUMES "
                         "--tp/--tp-mode/--tp-compress/--decode-block/"
                         "--megakernel/--replicas/--disagg (still "
                         "accepted, but the plan's values win with a "
                         "DeprecationWarning). Prints the chosen plan "
                         "and its predicted TTFT/TPOT at startup")
    ap.add_argument("--quant", choices=["none", "int8"], default="none")
    ap.add_argument("--max_new_tokens", type=int, default=16)
    ap.add_argument("--scheduler", action="store_true",
                    help="serve a ragged request stream through the "
                         "continuous-batching scheduler instead of one "
                         "static generate() batch")
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="per-request wall-clock deadline; an expired "
                         "request retires with a DeadlineExceededError "
                         "record instead of squatting on its slot "
                         "(scheduler mode)")
    ap.add_argument("--queue-limit", type=int, default=None,
                    help="bounded admission queue: add_request past this "
                         "depth raises EngineBusyError backpressure "
                         "(scheduler mode)")
    ap.add_argument("--decode-block", type=int, default=1,
                    help="K > 1: device-resident multi-step decode — one "
                         "compiled dispatch runs a ragged prefill phase "
                         "+ K decode steps (on-device sampling/EOS); "
                         "the host intervenes every K tokens "
                         "(scheduler mode; see docs/serving.md)")
    ap.add_argument("--speculate", type=int, default=0,
                    help="T >= 2: speculative decoding — a drafter "
                         "proposes T-1 tokens per verify pass, the "
                         "target scores all of them in ONE multi-token-q"
                         " ragged-paged-attention pass, and accept/"
                         "reject runs inside the on-device scan carries;"
                         " greedy outputs stay byte-identical to "
                         "non-speculative serving (scheduler mode, "
                         "docs/serving.md \"Speculative decoding\")")
    ap.add_argument("--drafter", choices=["ngram", "prefix"],
                    default="ngram",
                    help="zero-extra-model drafter: 'ngram' = prompt-"
                         "lookup over the request's own context; "
                         "'prefix' = continuations walked from the "
                         "content-addressed prefix cache (other "
                         "requests' traffic)")
    ap.add_argument("--replicas", type=int, default=1,
                    help="N > 1: serve through the fault-tolerant "
                         "EngineRouter — N engine replicas, health-"
                         "balanced routing, replica failover with "
                         "in-flight re-queue, circuit-breaker "
                         "quarantine (scheduler mode; docs/serving.md "
                         "\"Multi-replica routing & hot-swap\")")
    ap.add_argument("--hot-swap", metavar="DIR", default=None,
                    help="perform a mid-stream zero-downtime rolling "
                         "weight swap from this CRC32-manifest snapshot "
                         "directory (saved first from the live weights "
                         "when the path does not exist yet — a self-"
                         "contained round-trip demo); needs "
                         "--replicas >= 2")
    ap.add_argument("--tp", type=int, default=1,
                    help="N > 1: tensor-parallel serving — ONE engine "
                         "sharded over an N-device 'mp' mesh (heads + "
                         "paged-KV pools sharded over heads, column/"
                         "row-parallel matmuls under shard_map); greedy "
                         "outputs byte-identical to tp=1 in the default "
                         "exact mode (docs/serving.md \"Sharded decode "
                         "& disaggregated prefill\")")
    ap.add_argument("--tp-mode", choices=["exact", "psum"],
                    default="exact",
                    help="TP tail mode: 'exact' reassembles via "
                         "all_gather (byte-identical), 'psum' runs the "
                         "Megatron per-token all-reduce (wire-optimal, "
                         "rtol-close)")
    ap.add_argument("--tp-compress", choices=["none", "int8"],
                    default="none",
                    help="int8-quantize the psum-mode all-reduce "
                         "(comm_compress.quantized_psum; ~4x fewer "
                         "wire bytes)")
    ap.add_argument("--kv-tier", choices=["host", "disk"], default=None,
                    help="KV tiering: demote cold request pages out of "
                         "the device pool to host RAM ('host') or host+"
                         "disk ('disk', spilling under --tier-dir) in "
                         "the CRC-stamped page-export format, restoring "
                         "on demand at a block boundary — admission "
                         "OVERSUBSCRIBES device pages against the tier, "
                         "so long conversations survive at a fraction "
                         "of HBM cost (scheduler/router modes, "
                         "docs/serving.md \"Prefix-aware routing & KV "
                         "tiering\")")
    ap.add_argument("--tier-dir", default="/tmp/paddle_tpu_kv_tier",
                    help="spill directory for --kv-tier disk")
    ap.add_argument("--prefix-routing", action="store_true",
                    help="cache-aware routing: replicas publish their "
                         "content-addressed prefix chains into a fleet "
                         "index and each admission lands on the replica "
                         "with the longest cached prefix (headroom-"
                         "weighted; a loaded best-prefix replica SHIPS "
                         "its pages to a fresh one over the ticketed "
                         "transfer path instead of re-prefilling) — "
                         "needs --replicas >= 2")
    ap.add_argument("--disagg", metavar="P:D", default=None,
                    help="disaggregated serving: P prefill workers + D "
                         "decode workers behind the router — new "
                         "requests prefill on the P pool and migrate at "
                         "first-token via CRC-checked KV-page handoff "
                         "(zero recompute; scheduler machinery, implies "
                         "router mode)")
    ap.add_argument("--fleet", type=int, default=0, metavar="N",
                    help="PROCESS-BACKED fleet: spawn N worker "
                         "processes (each owning one engine) and route "
                         "over them via RPC/TCPStore — the multi-host "
                         "serving surface, single-host demo "
                         "(docs/serving.md \"Multi-host fleets\"). "
                         "The fleet StorePrefixIndex is wired by "
                         "default; composes with --disagg P:D "
                         "(cross-process KV handoff over the "
                         "negotiated store transport)")
    ap.add_argument("--fleet-worker", action="store_true",
                    help="run THIS process as one fleet worker: build "
                         "the engine from the same flags and serve the "
                         "replica surface until killed (multi-host "
                         "mode — one per host, all pointing at "
                         "--fleet-store)")
    ap.add_argument("--fleet-store", metavar="HOST:PORT", default=None,
                    help="rendezvous TCPStore for --fleet-worker (the "
                         "--fleet spawner creates its own)")
    ap.add_argument("--fleet-name", default="w0",
                    help="this worker's replica name (--fleet-worker)")
    ap.add_argument("--autoscale", action="store_true",
                    help="close the telemetry→control loop: tick an "
                         "SLO-driven FleetController while draining — "
                         "scale out on windowed p99 breach, drain-then-"
                         "retire on sustained slack, rebalance the "
                         "prefill:decode split under --disagg, shed "
                         "load as last resort (inference/autoscale.py; "
                         "router modes: --replicas/--disagg/--fleet; "
                         "docs/serving.md \"Elastic fleet\")")
    ap.add_argument("--slo-ttft-ms", type=float, default=None,
                    metavar="MS",
                    help="--autoscale: p99 TTFT target over the sliding "
                         "window (unset = not watched)")
    ap.add_argument("--slo-queue-wait-ms", type=float, default=50.0,
                    metavar="MS",
                    help="--autoscale: p99 queue-wait target over the "
                         "sliding window (default 50)")
    ap.add_argument("--min-replicas", type=int, default=1, metavar="N",
                    help="--autoscale: never drain the fleet below N")
    ap.add_argument("--max-replicas", type=int, default=4, metavar="N",
                    help="--autoscale: never grow the fleet past N "
                         "(breaches beyond the cap fall through the "
                         "degradation ladder to load-shedding)")
    ap.add_argument("--metrics-port", type=int, default=None,
                    metavar="P",
                    help="serve router.prometheus() at "
                         "http://127.0.0.1:P/metrics on a stdlib "
                         "http.server thread (0 = ephemeral; router "
                         "modes: --replicas/--disagg/--fleet)")
    ap.add_argument("--trace-out", metavar="PATH", default=None,
                    help="write the request-lifecycle timeline as "
                         "chrome-trace/perfetto JSON to PATH when the "
                         "demo finishes (admission/queue/prefill/TTFT/"
                         "decode spans per request, plus demote/"
                         "handoff/failover legs and fault events; "
                         "scheduler and router modes, "
                         "docs/observability.md)")
    ap.add_argument("--metrics-every", type=int, metavar="N", default=0,
                    help="print a compact telemetry snapshot every N "
                         "engine/router steps while draining: TTFT/"
                         "TPOT/queue-wait p50+p99, counters, and "
                         "rate-converted health() deltas "
                         "(docs/observability.md)")
    ap.add_argument("--adapters", metavar="NAME=PATH,...", default=None,
                    help="multi-LoRA serving: load each NAME=PATH LoRA "
                         "adapter into the engine's paged adapter pool "
                         "(a path that does not exist yet is CREATED "
                         "as a random rank---adapter-rank adapter "
                         "first — a self-contained round-trip demo, "
                         "like --hot-swap); deploying to a router/"
                         "fleet is ONE registry write fanned to every "
                         "replica (docs/serving.md \"Multi-LoRA & the "
                         "model zoo\")")
    ap.add_argument("--adapter-rotate", action="store_true",
                    help="round-robin the demo requests across the "
                         "--adapters names (plus one base-weights "
                         "request), demonstrating a MIXED batch — "
                         "byte-identical to per-adapter dedicated "
                         "engines; works under --fleet via the "
                         "ProcessReplica registry write path")
    ap.add_argument("--adapter-rank", type=int, default=8,
                    help="rank of the adapter pool (and of the demo "
                         "adapters created for missing --adapters "
                         "paths)")
    ap.add_argument("--calibrate", metavar="NPZ", default=None,
                    help="PTQ: run quantization.ptq.calibrate over the "
                         "model on a small sample stream, save the "
                         "per-channel int8 scales to NPZ, and serve "
                         "through quant='int8' WITH them (implies "
                         "--quant int8) — the model-zoo deploy shape: "
                         "one base checkpoint, calibrated once, N "
                         "adapters on top")
    ap.add_argument("--megakernel", choices=["auto", "off", "layer",
                                             "multi"], default="auto",
                    help="decode megakernel: one fused Pallas kernel "
                         "per layer ('layer') or the WHOLE decode step "
                         "('multi': every layer + final norm + lm_head "
                         "+ greedy argmax in one invocation) streams "
                         "int8/dense weights through VMEM — composes "
                         "with --speculate (the tq>1 verify schedule) "
                         "and --tp (per-shard segments, exact mode). "
                         "auto turns it on only on a real TPU with a "
                         "lane-aligned geometry; forcing it on CPU runs "
                         "interpret mode (parity, not speed; scheduler "
                         "mode, docs/serving.md \"Megakernel decode\")")
    ap.add_argument("--temperature", type=float, default=None,
                    help="per-request sampled decoding: softmax "
                         "temperature (unset = greedy argmax). With "
                         "--megakernel multi the top-K candidates come "
                         "out of the whole-step kernel — the [batch, "
                         "vocab] logits never materialize "
                         "(docs/serving.md \"Sampling & structured "
                         "decoding\")")
    ap.add_argument("--top-k", type=int, default=0,
                    help="sampled decoding: keep the k most likely "
                         "tokens before renormalizing (0 = no top-k "
                         "cut; capped by the engine's sample_k "
                         "candidate width)")
    ap.add_argument("--top-p", type=float, default=1.0,
                    help="sampled decoding: nucleus cutoff — smallest "
                         "probability mass kept (1.0 = off)")
    ap.add_argument("--seed", type=int, default=0,
                    help="sampled decoding: base PRNG seed; request i "
                         "streams from seed+i, and the counter-based "
                         "key schedule makes each stream reproducible "
                         "across batch composition, preemption, and "
                         "failover")
    ap.add_argument("--sample-rotate", action="store_true",
                    help="alternate sampled/greedy demo requests, "
                         "demonstrating a MIXED batch — greedy rows in "
                         "a sampled block stay bit-identical to an "
                         "all-greedy block (needs --temperature)")
    args = ap.parse_args()

    from paddle_tpu.chip import enable_compile_cache
    enable_compile_cache()
    import paddle_tpu as paddle
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.inference.serving import LLMEngine
    from paddle_tpu.inference.scheduler import ContinuousBatchingEngine

    geometries = {
        "tiny": dict(cfg=LlamaConfig.tiny(), max_len=64, page=16, bs=2),
        "350m": dict(cfg=LlamaConfig(vocab_size=32000, hidden_size=1024,
                                     intermediate_size=2816,
                                     num_hidden_layers=16,
                                     num_attention_heads=16,
                                     max_position_embeddings=2048),
                     max_len=512, page=64, bs=4),
        "7b": dict(cfg=LlamaConfig.llama_7b(), max_len=256, page=64, bs=1),
    }
    g = geometries[args.model]

    if args.plan:
        # -- cost-model-driven serving plan: the searcher (or a saved
        # -- spec) pins the knobs a human used to hand-pick; the
        # -- individual flags it subsumes still parse but lose, loudly
        import warnings
        import jax
        from paddle_tpu.cost_model import (Calibration, EngineSpec,
                                           predict_serving, search_plan)
        subsumed = [("--tp", args.tp != 1),
                    ("--tp-mode", args.tp_mode != "exact"),
                    ("--tp-compress", args.tp_compress != "none"),
                    ("--decode-block", args.decode_block != 1),
                    ("--megakernel", args.megakernel != "auto"),
                    ("--replicas", args.replicas != 1),
                    ("--disagg", args.disagg is not None)]
        for flag, was_set in subsumed:
            if was_set:
                warnings.warn(
                    f"{flag} is subsumed by --plan; the plan's value "
                    f"wins (drop the flag, or edit the plan JSON)",
                    DeprecationWarning, stacklevel=1)
        calib = Calibration.load()
        if args.plan == "auto":
            base = EngineSpec.from_model_cfg(
                g["cfg"], seed=0, max_len=g["max_len"],
                page_size=g["page"], max_batch=max(2, g["bs"]),
                quant=(None if args.quant == "none" else args.quant))
            if args.model == "tiny":
                base.model = {"preset": "tiny", "seed": 0}
            n_dev = len(jax.devices())
            ranked = search_plan(g["cfg"], n_dev, mode="serving",
                                 base_spec=base, calib=calib,
                                 prompt_len=16,
                                 gen_tokens=args.max_new_tokens)
            if not ranked:
                ap.error(f"--plan auto: no feasible serving plan for "
                         f"{args.model} on {n_dev} device(s)")
            spec, cost = ranked[0].plan, ranked[0].cost
        else:
            spec = EngineSpec.load(args.plan)
            cost = predict_serving(g["cfg"], spec, calib=calib,
                                   prompt_len=16,
                                   gen_tokens=args.max_new_tokens)
        # the spec is the source of truth: push its knobs back into
        # args so every mode branch below consumes them unchanged
        args.tp = spec.tp
        args.tp_mode = spec.tp_mode
        args.tp_compress = spec.tp_compress or "none"
        args.decode_block = spec.decode_block
        args.megakernel = {False: "off", None: "auto"}.get(
            spec.megakernel, spec.megakernel)
        if spec.quant is not None:
            args.quant = spec.quant
        topo = spec.topology()
        if args.fleet:
            if spec.replicas != args.fleet:
                ap.error(f"--fleet {args.fleet} but the plan wants "
                         f"{spec.replicas} replicas")
            args.disagg = (f"{topo['prefill']}:{topo['decode']}"
                           if topo else None)
        elif topo:
            args.disagg = f"{topo['prefill']}:{topo['decode']}"
            args.replicas = 1
        else:
            args.replicas = spec.replicas
            args.disagg = None
        if spec.replicas > 1 and not args.scheduler and not args.fleet:
            args.scheduler = False      # router modes drive themselves
        elif spec.replicas == 1 and not args.fleet:
            # the searched knobs (decode_block/megakernel) live on the
            # continuous-batching engine — route through --scheduler
            args.scheduler = True
        print(f"plan[{'auto' if args.plan == 'auto' else args.plan}]: "
              f"tp={spec.tp}({spec.tp_mode}) replicas={spec.replicas}"
              + (f" disagg={topo['prefill']}:{topo['decode']}" if topo
                 else "")
              + f" megakernel={spec.megakernel}"
                f" decode_block={spec.decode_block}")
        print(f"  predicted: TTFT {cost.meta['ttft_ms']:.2f} ms, "
              f"TPOT {cost.meta['tpot_ms']:.3f} ms/tok — {cost.why()} "
              f"[{cost.meta['calibration']}]")

    def _fleet_spec():
        """Engine spec for fleet WORKER processes — the same model +
        engine the in-process branches build, as plain data
        (fleet.build_engine_from_spec), so a worker needs no code
        shipped and every process builds byte-identical weights from
        the shared seed."""
        if args.model == "tiny":
            model_spec = {"preset": "tiny", "seed": 0}
        elif args.model == "350m":
            # derived from the SAME LlamaConfig the in-process
            # branches build (every field is a plain scalar, so the
            # spec round-trips the geometry exactly) — a duplicated
            # literal here would silently drift when the geometries
            # table changes
            model_spec = {"preset": "config", "seed": 0,
                          **vars(g["cfg"])}
        else:
            ap.error("--fleet/--fleet-worker supports tiny/350m (7b "
                     "needs the LazyGuard checkpoint path — load from "
                     "a snapshot on each host instead)")
        engine_spec = dict(max_len=g["max_len"], page_size=g["page"],
                          max_batch=max(2, g["bs"]),
                          quant=(None if args.quant == "none"
                                 else args.quant),
                          decode_block=args.decode_block, **ad_kw)
        if args.tp > 1:
            # workers inherit the parent env (device count flags), so
            # TP shards inside each worker exactly like the in-process
            # branches — dropping it here would silently serve
            # unsharded while the user believes they demoed TP
            engine_spec.update(
                tp=args.tp, tp_mode=args.tp_mode,
                tp_compress=(None if args.tp_compress == "none"
                             else args.tp_compress))
        if args.kv_tier:
            engine_spec.update(kv_tier=args.kv_tier,
                               tier_dir=(args.tier_dir if
                                         args.kv_tier == "disk"
                                         else None))
        return {"model": model_spec, "engine": engine_spec}

    # -- multi-LoRA adapters (docs/serving.md "Multi-LoRA & the model
    # -- zoo"): parse NAME=PATH pairs, create missing demo adapters,
    # -- and round-robin requests across them under --adapter-rotate
    adapter_list = []
    if args.adapters:
        for item in args.adapters.split(","):
            name, _, path = item.partition("=")
            if not name.strip() or not path.strip():
                ap.error("--adapters expects NAME=PATH[,NAME=PATH...]")
            adapter_list.append((name.strip(), path.strip()))
    if adapter_list and not (args.scheduler or args.replicas > 1
                             or args.disagg or args.fleet
                             or args.fleet_worker):
        ap.error("--adapters needs a continuous-batching mode "
                 "(--scheduler, --replicas N, --disagg P:D, or "
                 "--fleet N) — the static LLMEngine path has no "
                 "adapter pool")
    ad_kw = ({"adapters": {"rank": args.adapter_rank,
                           "max_adapters": max(4, len(adapter_list))}}
             if adapter_list else {})

    def ensure_adapter_files():
        """Missing --adapters paths are created as random adapters of
        the engine geometry first (self-contained round trip, the
        --hot-swap pattern) — a real deploy points at fine-tune
        artifacts written by adapters.save_adapter."""
        from paddle_tpu.inference.adapters import (make_lora_adapter,
                                                   save_adapter)
        for i, (name, path) in enumerate(adapter_list):
            if not os.path.isdir(path):
                save_adapter(path, make_lora_adapter(
                    g["cfg"], rank=args.adapter_rank, seed=100 + i))
                print(f"  adapter {name}: wrote random "
                      f"rank-{args.adapter_rank} demo adapter -> {path}")

    def adapter_for(i):
        """Adapter name for demo request i: round-robin over base +
        every named adapter (--adapter-rotate), else the first name
        (single-fine-tune deploy)."""
        if not adapter_list:
            return None
        if args.adapter_rotate:
            names = [None] + [n for n, _ in adapter_list]
            return names[i % len(names)]
        return adapter_list[0][0]

    # -- per-request sampling (docs/serving.md "Sampling & structured
    # -- decoding"): --temperature arms it; the other knobs without it
    # -- are inert, which deserves a loud flag-convention warning
    if args.temperature is None and (args.top_k or args.top_p != 1.0
                                     or args.seed or args.sample_rotate):
        import warnings
        warnings.warn(
            "--top-k/--top-p/--seed/--sample-rotate do nothing without "
            "--temperature (decoding stays greedy); set --temperature "
            "to sample", DeprecationWarning, stacklevel=1)

    def sampling_for(i):
        """SamplingParams spec dict for demo request i, or None for
        engine-default greedy. --sample-rotate alternates sampled and
        greedy rows — a MIXED batch, where the greedy rows are pinned
        bit-identical to an all-greedy block. seed+i gives every
        request its own counter-based key stream, so re-running with
        the same flags reproduces the same tokens regardless of which
        replica serves it or how the batch packs."""
        if args.temperature is None:
            return None
        if args.sample_rotate and i % 2 == 1:
            return None
        return {"do_sample": True, "temperature": args.temperature,
                "top_k": args.top_k, "top_p": args.top_p,
                "seed": args.seed + i}

    def deploy_adapters(target):
        """The ONE deploy sequence every branch runs: materialize
        missing demo files, then one registry write per adapter on the
        target (an engine prints its pool slot, a router its
        per-replica summary)."""
        if not adapter_list:
            return
        ensure_adapter_files()
        for name, path in adapter_list:
            print(f"  adapter {name}: {target.load_adapter(name, path)}")

    if args.fleet_worker:
        # multi-host mode: one of these per host, all pointing at the
        # master store; the router host builds ProcessReplica(name,
        # store) per worker (single-host demo: --fleet N does all of
        # this in one command)
        if not args.fleet_store:
            ap.error("--fleet-worker needs --fleet-store HOST:PORT")
        from paddle_tpu.distributed.store import TCPStore
        from paddle_tpu.inference.fleet import (EngineHost,
                                                build_engine_from_spec)
        host_s, _, port_s = args.fleet_store.partition(":")
        store = TCPStore(host_s, int(port_s))
        engine = build_engine_from_spec(_fleet_spec())
        host = EngineHost(engine, args.fleet_name, store)
        print(f"fleet worker {args.fleet_name} serving "
              f"{host.ip}:{host.port} (store {args.fleet_store})",
              flush=True)
        host.serve_forever()
        return

    paddle.seed(0)
    if args.fleet:
        # fleet mode: every worker PROCESS builds its own engine from
        # the spec — the router side never touches the weights, so
        # building the model here would only burn startup time and RAM
        model = weight_dtype = None
    elif args.model == "7b":
        # checkpoint scale: NEVER build eagerly — meta init + lazy
        # materialization straight to the serving dtype
        with paddle.LazyGuard():
            model = LlamaForCausalLM(g["cfg"])
        weight_dtype = "bfloat16"
    else:
        model = LlamaForCausalLM(g["cfg"])
        weight_dtype = None

    quant = None if args.quant == "none" else args.quant
    # PTQ calibration (quantization/ptq.py): observe the model, save the
    # per-channel int8 scales, serve int8 WITH them — byte-identical to
    # the absmax-from-weights engine (the observers reduce identically),
    # which is the point: the zoo path swaps in any later calibration
    # without touching the serving stack
    quant_scales = None
    if args.calibrate:
        if args.fleet:
            ap.error("--calibrate needs an in-process model (fleet "
                     "workers build their own engines; calibrate once, "
                     "ship the NPZ, load via quant_scales=)")
        if args.model == "7b":
            ap.error("--calibrate runs eager forwards (calibrate the "
                     "checkpoint before meta-init serving)")
        from paddle_tpu.quantization import ptq
        c_rng = np.random.RandomState(42)
        batches = [c_rng.randint(0, g["cfg"].vocab_size, (2, 12))
                   for _ in range(4)]
        quant_scales = ptq.calibrate(model, sample_batches=batches)
        quant_scales.save(args.calibrate)
        quant = args.quant = "int8"
        print(f"  PTQ: calibrated {len(batches)} batches -> "
              f"{args.calibrate} (serving int8 with calibrated scales)")
    # observability (docs/observability.md): --trace-out/--metrics-every
    # turn the telemetry plane on; router modes aggregate per-replica
    # registries into the fleet view printed/exported below
    want_tel = bool(args.trace_out or args.metrics_every
                    or args.metrics_port is not None
                    # the controller reads the windowed fleet
                    # percentiles — no telemetry, no control signal
                    or args.autoscale)

    def metrics_endpoint(router):
        """--metrics-port: the Prometheus scrape endpoint over the
        live router (telemetry.serve_prometheus); returns the server
        or None."""
        if args.metrics_port is None:
            return None
        from paddle_tpu.inference.telemetry import serve_prometheus
        srv = serve_prometheus(router, port=args.metrics_port)
        print(f"  metrics: http://127.0.0.1:{srv.server_address[1]}"
              "/metrics")
        return srv

    def make_controller(router, spawner=None, retirer=None):
        """--autoscale: the SLO-driven elastic-fleet controller
        (docs/serving.md "Elastic fleet") that drive_router ticks
        between steps; scale actions land on the live router."""
        if not args.autoscale:
            return None
        from paddle_tpu.inference.autoscale import (FleetController,
                                                    SLOTarget)
        slo = SLOTarget(ttft_p99_ms=args.slo_ttft_ms,
                        queue_wait_p99_ms=args.slo_queue_wait_ms)
        return FleetController(router, slo, spawner=spawner,
                               retirer=retirer,
                               min_replicas=args.min_replicas,
                               max_replicas=args.max_replicas)

    def drive_router(router, ctl=None):
        """Drain the router, printing a compact fleet-metrics line
        every --metrics-every steps (TTFT/TPOT/queue-wait p50s from the
        merged per-replica histograms); with --autoscale the controller
        ticks on the same cadence the traffic advances."""
        n = 0
        while router.step():
            n += 1
            if ctl is not None:
                ctl.maybe_tick(every_steps=4)
            if args.metrics_every and n % args.metrics_every == 0:
                hists = (router.metrics().get("fleet") or {}).get(
                    "histograms", {})
                line = {k: {"p50_ms": v.get("p50_ms"),
                            "n": v.get("count")}
                        for k, v in hists.items() if v.get("count")}
                print(f"  metrics@{n}: {json.dumps(line)}")
        router.drain()                  # final collect pass
        if ctl is not None:
            s = ctl.stats()
            last = s["last_decision"]
            print(f"  autoscale: {s['ticks']} ticks, "
                  f"+{s['scale_outs']}/-{s['scale_ins']} replicas "
                  f"({s['replicas']} final), {s['rebalances']} "
                  f"rebalances, {s['sheds']} sheds, "
                  f"last={last and last['action']}")

    def router_trace_out(router):
        if args.trace_out and want_tel:
            router.export_chrome_trace(args.trace_out)
            print(f"  trace written: {args.trace_out} (fleet timeline; "
                  "load in Perfetto / chrome://tracing)")

    tp_kw = {}
    if args.tp > 1:
        tp_kw = dict(tp=args.tp, tp_mode=args.tp_mode,
                     tp_compress=(None if args.tp_compress == "none"
                                  else args.tp_compress))
    if args.hot_swap and args.replicas < 2:
        ap.error("--hot-swap needs --replicas >= 2 (the router keeps "
                 "serving from the other replicas while one flips)")
    if args.prefix_routing and args.replicas < 2 and not args.disagg:
        ap.error("--prefix-routing needs --replicas >= 2 (a fleet to "
                 "route across)")
    if args.autoscale and not (args.fleet or args.disagg
                               or args.replicas > 1):
        ap.error("--autoscale needs a router mode (--replicas >= 2, "
                 "--disagg P:D, or --fleet N)")
    tier_kw = {}
    if args.kv_tier:
        tier_kw = dict(kv_tier=args.kv_tier,
                       tier_dir=(args.tier_dir
                                 if args.kv_tier == "disk" else None))
    if args.fleet:
        # PROCESS-BACKED fleet: N worker processes behind one router —
        # every replica is a ProcessReplica speaking the EngineReplica
        # surface over RPC; with --disagg the KV handoff crosses
        # processes on the negotiated store transport
        from paddle_tpu.inference.fleet import spawn_fleet
        from paddle_tpu.inference.router import EngineRouter
        topo = roles = None
        if args.disagg:
            try:
                p_n, d_n = (int(x) for x in args.disagg.split(":"))
            except ValueError:
                ap.error("--disagg expects P:D (e.g. --disagg 1:2)")
            if p_n + d_n != args.fleet:
                ap.error(f"--disagg {args.disagg} needs "
                         f"--fleet {p_n + d_n}")
            topo = {"prefill": p_n, "decode": d_n}
            roles = ["prefill"] * p_n + ["decode"] * d_n
        # spawn_fleet wires the fleet StorePrefixIndex by default (the
        # natural multi-process backend — what the --fleet help text
        # promises); --prefix-routing is only meaningful in-process
        handle = spawn_fleet(_fleet_spec(), args.fleet, roles=roles)
        srv = None
        try:
            # the workers are non-daemon processes: anything that
            # raises after spawn (a RequestFailure out of result(),
            # Ctrl-C mid-drive) must still shut the fleet down or the
            # interpreter hangs at exit joining orphan workers
            router = EngineRouter(backends=handle.replicas,
                                  topology=topo,
                                  prefix_index=handle.prefix_index,
                                  telemetry=want_tel)
            srv = metrics_endpoint(router)
            # registry write over the ProcessReplica RPC surface:
            # every worker hot-loads from the shared path
            deploy_adapters(router)
            rng = np.random.RandomState(0)
            prompts = [rng.randint(0, g["cfg"].vocab_size, (t,))
                       .astype(np.int64) for t in (16, 9, 5, 12)]
            uids = [router.add_request(p,
                                       max_new_tokens=args.max_new_tokens,
                                       adapter=adapter_for(i),
                                       sampling=sampling_for(i))
                    for i, p in enumerate(prompts)]
            # elastic fleet: scale-out forks REAL worker processes via
            # the handle (respawn-governed), scale-in drains then
            # reaps them — the full docs/serving.md control loop
            drive_router(router,
                         make_controller(router,
                                         spawner=handle.spawn_worker,
                                         retirer=handle.retire_worker))
            router_trace_out(router)
            h = router.health()
            print(f"model={args.model} quant={args.quant} fleet "
                  f"{args.fleet} processes"
                  + (f" (disagg {args.disagg})" if topo else "")
                  + f": {h['done']} done / {h['failed']} failed, "
                  f"{h['failovers']} failovers, {h['kv_handoffs']} KV "
                  f"handoffs "
                  f"(transports {dict(router.handoff_transports)})")
            for name, rh in h["replicas"].items():
                print(f"  {name} [{rh['role']}]: breaker={rh['breaker']} "
                      f"worker={rh.get('worker')}")
            for i, u in enumerate(uids):
                o = router.result(u)
                print(f"  request {i}: {prompts[i].size} -> {o.size} "
                      f"tokens, tail {o[-4:].tolist()}")
        finally:
            if srv is not None:
                srv.shutdown()
            handle.shutdown()
        return

    if args.disagg:
        # disaggregated prefill/decode: P prefill + D decode workers,
        # requests migrate at first-token via KV-page handoff
        from paddle_tpu.inference.router import EngineRouter
        try:
            p_n, d_n = (int(x) for x in args.disagg.split(":"))
        except ValueError:
            ap.error("--disagg expects P:D (e.g. --disagg 1:2)")

        def factory():
            return ContinuousBatchingEngine(
                model, max_len=g["max_len"], page_size=g["page"],
                max_batch=max(2, g["bs"]), quant=quant,
                quant_scales=quant_scales, weight_dtype=weight_dtype,
                decode_block=args.decode_block, **tp_kw, **tier_kw,
                **ad_kw)

        router = EngineRouter(factory,
                              topology={"prefill": p_n, "decode": d_n},
                              prefix_routing=args.prefix_routing,
                              telemetry=want_tel)
        srv = metrics_endpoint(router)
        deploy_adapters(router)
        rng = np.random.RandomState(0)
        prompts = [rng.randint(0, g["cfg"].vocab_size, (t,))
                   .astype(np.int64) for t in (16, 9, 5, 12)]
        uids = [router.add_request(p, max_new_tokens=args.max_new_tokens,
                                   adapter=adapter_for(i),
                                   sampling=sampling_for(i))
                for i, p in enumerate(prompts)]
        # in-process elastic: the factory IS the spawner (controller
        # falls back to router.add_replica()); topology present, so
        # the controller may also rebalance the prefill:decode split
        drive_router(router, make_controller(router))
        router_trace_out(router)
        h = router.health()
        print(f"model={args.model} quant={args.quant} disagg "
              f"{p_n}:{d_n}: {h['done']} done / {h['failed']} failed, "
              f"{h['kv_handoffs']} KV handoffs "
              f"({h['handoff_failures']} retried)")
        for name, rh in h["replicas"].items():
            print(f"  {name} [{rh['role']}]: breaker={rh['breaker']} "
                  f"pages_free={rh.get('pages_free')}")
        for i, u in enumerate(uids):
            o = router.result(u)
            print(f"  request {i}: {prompts[i].size} -> {o.size} "
                  f"tokens, tail {o[-4:].tolist()}")
        if srv is not None:
            srv.shutdown()
        return
    if args.replicas > 1:
        # fault-tolerant fleet: N replicas behind the health-checked
        # router — failover, quarantine, and (optionally) a mid-stream
        # zero-downtime weight hot-swap
        from paddle_tpu.inference.router import EngineRouter

        def factory():
            return ContinuousBatchingEngine(
                model, max_len=g["max_len"], page_size=g["page"],
                max_batch=max(2, g["bs"]), quant=quant,
                quant_scales=quant_scales, weight_dtype=weight_dtype,
                decode_block=args.decode_block, **tp_kw, **tier_kw,
                **ad_kw)

        router = EngineRouter(factory, replicas=args.replicas,
                              prefix_routing=args.prefix_routing,
                              telemetry=want_tel)
        srv = metrics_endpoint(router)
        deploy_adapters(router)
        rng = np.random.RandomState(0)
        prompts = [rng.randint(0, g["cfg"].vocab_size, (t,))
                   .astype(np.int64) for t in (16, 9, 5, 12)]
        if args.prefix_routing:
            # a shared system prompt: requests 1-3 reuse request 0's
            # published pages — and the index steers them to its replica
            prompts = [np.concatenate([prompts[0], p[:4]])
                       for p in prompts[:3]] + [prompts[3]]
        if args.prefix_routing:
            # let request 0 finish (and publish its prompt pages +
            # index claims) before the prefix-sharing follow-ups
            # arrive — that is the traffic shape the index steers
            uids = [router.add_request(
                prompts[0], max_new_tokens=args.max_new_tokens,
                sampling=sampling_for(0))]
            router.drain()
            uids += [router.add_request(
                p, max_new_tokens=args.max_new_tokens,
                sampling=sampling_for(i))
                for i, p in enumerate(prompts[1:], start=1)]
        else:
            uids = [router.add_request(
                p, max_new_tokens=args.max_new_tokens,
                adapter=adapter_for(i),
                sampling=sampling_for(i))
                for i, p in enumerate(prompts)]
        for _ in range(2):
            router.step()                    # replicas mid-flight
        if args.hot_swap:
            if not os.path.isdir(args.hot_swap):
                # round-trip demo: snapshot the live weights first
                router.save_weights_snapshot(args.hot_swap, step=0)
            print(f"  hot-swap: {router.hot_swap(args.hot_swap)}")
        drive_router(router, make_controller(router))
        router_trace_out(router)
        h = router.health()
        print(f"model={args.model} quant={args.quant} "
              f"router: {len(uids)} requests over {args.replicas} "
              f"replicas, {h['done']} done / {h['failed']} failed, "
              f"{h['failovers']} failovers, {h['hot_swaps']} hot-swaps")
        if args.prefix_routing:
            fleet_hits = sum(rep.engine._prefix.hits
                             for rep in router._replicas)
            print(f"  prefix routing: {h['prefix_routed']} steered, "
                  f"{h['prefix_ships']} page ships, {fleet_hits} fleet "
                  f"prefix-page hits, index={h['prefix_index']}")
        if args.kv_tier:
            print("  kv tier:", {rep.name: {
                "demotions": rep.engine.demotions,
                "restores": rep.engine.restores}
                for rep in router._replicas})
        for name, rh in h["replicas"].items():
            print(f"  {name}: breaker={rh['breaker']} "
                  f"pages_free={rh.get('pages_free')}")
        for i, u in enumerate(uids):
            o = router.result(u)
            print(f"  request {i}: {prompts[i].size} -> {o.size} "
                  f"tokens, tail {o[-4:].tolist()}")
        if srv is not None:
            srv.shutdown()
        return

    if args.scheduler:
        from paddle_tpu.inference.scheduler import (EngineBusyError,
                                                    RequestFailedError)
        tel = None
        if want_tel:
            from paddle_tpu.inference.telemetry import Telemetry
            tel = Telemetry()
        engine = ContinuousBatchingEngine(
            model, max_len=g["max_len"], page_size=g["page"],
            max_batch=max(2, g["bs"]), quant=quant,
            quant_scales=quant_scales, weight_dtype=weight_dtype,
            queue_limit=args.queue_limit,
            default_deadline_ms=args.deadline_ms,
            decode_block=args.decode_block,
            speculate=args.speculate or None,
            drafter=args.drafter,
            # --megakernel composes with --speculate and --tp now
            # (PR 12): no downgrade, no conflict gate — the engine runs
            # the tq>1 verify schedule / per-shard segments itself
            megakernel={"auto": None, "off": False}.get(args.megakernel,
                                                        args.megakernel),
            telemetry=tel, **tp_kw, **tier_kw, **ad_kw)
        deploy_adapters(engine)
        rng = np.random.RandomState(0)
        # ragged prompts; 1 shares 0's prefix (once 0 finishes prefill,
        # the cache turns the shared pages into refcounted read-only
        # references — request 1 skips that prefill work entirely;
        # adapter-carrying requests never share — their KV carries the
        # adapter's deltas)
        base = rng.randint(0, g["cfg"].vocab_size, (16,)).astype(np.int64)
        prompts = [base, base[:9],
                   rng.randint(0, g["cfg"].vocab_size, (5,))
                   .astype(np.int64)]
        submitted = [(0, engine.add_request(
            prompts[0], max_new_tokens=args.max_new_tokens,
            adapter=adapter_for(0), sampling=sampling_for(0)))]
        while engine._requests[submitted[0][1]].state in ("queued",
                                                          "prefill"):
            engine.step()            # request 0 publishes its pages
        for i, p in enumerate(prompts[1:], start=1):
            try:
                submitted.append((i, engine.add_request(
                    p, max_new_tokens=args.max_new_tokens,
                    adapter=adapter_for(i), sampling=sampling_for(i))))
            except EngineBusyError as e:
                # bounded queue: backpressure is a client-visible signal,
                # not an engine crash
                print(f"  request {i} shed by backpressure: {e}")
        if args.metrics_every:
            # metered drain: the telemetry plane's periodic snapshot —
            # histogram p50/p99s, counters, and rate-converted health()
            # deltas (docs/observability.md)
            n = 0
            while engine.step():
                n += 1
                if n % args.metrics_every == 0:
                    tel.sample(engine.health())
                    print(f"  metrics@{n}: {json.dumps(tel.summary())}")
        else:
            engine.drain()
        fused = (f"{engine.fused_blocks} fused blocks "
                 f"({engine.chained_blocks} pipelined), "
                 if args.decode_block > 1 else "")
        fused += f"megakernel={engine.health()['megakernel']}, "
        if args.speculate >= 2:
            h = engine.health()
            fused += (f"speculate={h['speculate']}/{h['drafter']}: "
                      f"{h['spec_emitted']} tokens in "
                      f"{h['spec_passes']} verify passes "
                      f"({h['spec_tokens_per_pass']:.2f}/pass, "
                      f"accept {h['spec_accept_rate']:.2f}), ")
        print(f"model={args.model} quant={args.quant} scheduler: "
              f"{len(submitted)} ragged requests in "
              f"{engine.steps} steps ({engine.prefill_steps} prefill / "
              f"{engine.decode_steps} decode), {fused}"
              f"{engine._prefix.hits} prefix-page hits, "
              f"{engine.cow_copies} copy-on-writes")
        for i, u in submitted:
            try:
                o = engine.result(u)
                print(f"  request {i}: {prompts[i].size} -> {o.size} "
                      f"tokens, tail {o[-4:].tolist()}")
            except RequestFailedError as e:
                # deadline expiry (and any per-request fault) is a typed
                # record on THAT request; the others completed normally
                print(f"  request {i}: failed — {e.failure}")
        h = engine.health()
        print(f"  health: {h['done']} done / {h['failed']} failed, "
              f"{h['pages_free']}/{h['pages_total']} pages free")
        if adapter_list:
            a = h["adapters"]
            print(f"  adapters: {a['loaded']} loaded "
                  f"({a['pages_total'] - a['pages_free']}/"
                  f"{a['pages_total']} pool pages), per-adapter "
                  f"requests {a['requests']}, tokens {a['tokens']}")
        if args.kv_tier:
            print(f"  kv tier ({h['kv_tier']}): {h['demotions']} "
                  f"demotions / {h['restores']} restores "
                  f"({h['restore_failures']} failed), tier={h['tier']}")
        if tel is not None:
            print(f"  telemetry: {json.dumps(tel.summary())}")
            if args.trace_out:
                tel.export_chrome_trace(args.trace_out)
                print(f"  trace written: {args.trace_out} "
                      f"({len(tel.done_traces())} request span chains; "
                      "load in Perfetto / chrome://tracing)")
        return

    engine = LLMEngine(model, max_len=g["max_len"], page_size=g["page"],
                       max_batch=g["bs"],
                       quant=quant, quant_scales=quant_scales,
                       weight_dtype=weight_dtype, **tp_kw)

    rng = np.random.RandomState(0)
    prompts = rng.randint(0, g["cfg"].vocab_size,
                          (g["bs"], 12)).astype(np.int64)
    # device_loop=True: one lax.scan dispatch for the whole generation —
    # the per-token host round trip (the latency killer through any
    # networked accelerator) is paid ONCE per generation
    sample_kw = {}
    if args.temperature is not None:
        # the static LLMEngine keeps the legacy whole-batch knobs (its
        # generate() has no per-request surface to hang SamplingParams
        # on); the continuous-batching modes above take sampling_for(i)
        sample_kw = dict(do_sample=True, temperature=args.temperature,
                         top_k=args.top_k, top_p=args.top_p)
    out = engine.generate(prompts, max_new_tokens=args.max_new_tokens,
                          device_loop=True, **sample_kw)
    print(f"model={args.model} quant={args.quant} "
          f"prompt={prompts.shape} -> generated={out.shape}")
    print("first sequence tail:", out[0, -args.max_new_tokens:].tolist())


if __name__ == "__main__":
    main()
