#!/usr/bin/env python
"""The quickest proof that the system still starts on the chip.

  python chip_smoke.py             # one chip: train, then serve
  python chip_smoke.py --chips 4   # the four-chip host: hybrid meshes,
                                   # tp serving, one chip per child

Drives both hot paths once through the entry points a user calls, at
the full width of models the repo ships, with random weights from a
seed:

  train  the 1.3B Llama geometry (h2048 L24 16x128 ffn5504 v32000; bf16
         params and moments, full recompute, LazyGuard) through
         fleet.init -> build_mesh -> SpmdTrainer.init_state/step, five
         steps on one fixed batch of 8 x 1024. Losses finite, last below
         first.
  serve  LlamaConfig.llama_7b(), LazyGuard, int8 weights, through
         ContinuousBatchingEngine with NO mode knobs and a real KV pool
         (max_len 1024, page 128, max_batch 8): eight ragged requests,
         prompts 40-700 tokens, 32 new tokens each, add_request/step/
         drain. 8 done / 0 failed, exact token counts, no page leak,
         kernels compiled (not interpreted). Then the same requests
         through an engine with megakernel=False, ragged_kernel=False on
         the same chip: wherever the two token streams part, the default
         engine's token must be a near-tie under the reference engine's
         own logits (see TIE_TOL).

The parent imports neither jax nor paddle_tpu: each phase is a child
process, one at a time, so exactly one process holds the chip. Every
child first requires jax's default backend to be a TPU — a machine
without one is a failure, not a skip — and prints the device and the
installed versions. Any child failing fails the run. On success the
last line of stdout is
  {"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}.

Times printed here are smoke output stamped with the device. They are
not benchmark numbers and belong under no metric name.
"""
import argparse
import json
import os
import signal
import subprocess
import sys
import threading
import time

RESULT_TAG = "CHIP_SMOKE_RESULT "
TOTAL_BUDGET_S = 1150          # the driver allows 1200 s, compile included
HERE = os.path.abspath(__file__)

# serve-phase requests: prompt lengths straddle page (128) boundaries
PROMPT_LENS = (40, 127, 128, 129, 300, 511, 640, 700)
NEW_TOKENS = 32
# Where the default engine and the kernels-off engine first disagree on
# a request they have the SAME context, so the default engine's token,
# scored by the reference engine's logits, must sit within TIE_TOL *
# |top logit| of the top. Measured on the v5e (PR 21): the largest such
# margin is 0.0086 at 4 layers and 0.0215 at 32 (bf16 rounding in
# another order, layer after layer; one bf16 step of the logit itself is
# up to 0.0078). 2^-4 is three times the full-depth maximum. A wrong kernel does not come
# close: for Gaussian logits over a 32000-token vocabulary the runner-up
# sits about 0.06 below the top and an arbitrary token about 0.8
# (estimates from that model, not measurements).
TIE_TOL = 2.0 ** -4


# ---------------------------------------------------------------- children --
def _enter(require_chip):
    """Every child: compile cache first, then the device check."""
    from paddle_tpu.chip import (device_stamp, enable_compile_cache,
                                 require_tpu)
    chosen = enable_compile_cache()
    stamp = require_tpu() if require_chip else device_stamp()
    print(f"[chip_smoke] platform={stamp['platform']} "
          f"device_kind={stamp['kind']} count={stamp['count']} "
          f"jax={stamp['jax']} jaxlib={stamp['jaxlib']} "
          f"libtpu={stamp['libtpu']} compile_cache="
          f"{chosen or os.environ.get('JAX_COMPILATION_CACHE_DIR')}",
          flush=True)
    return stamp


class _CompileClock:
    """Seconds jax spent obtaining executables (compiling, or reading
    the persistent cache) and how often the cache answered."""

    def __init__(self):
        import jax.monitoring as mon
        self.seconds = 0.0
        self.hits = 0
        self.misses = 0
        mon.register_event_duration_secs_listener(self._duration)
        mon.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def report(self):
        return {"compile_seconds": round(self.seconds, 2),
                "cache_hits": self.hits, "cache_misses": self.misses}


def _device_memory():
    """Per-device (bytes_in_use, peak_bytes_in_use); None where the
    backend does not report (CPU)."""
    import jax
    out = []
    for d in jax.devices():
        ms = d.memory_stats()
        out.append(None if ms is None else
                   (ms["bytes_in_use"], ms["peak_bytes_in_use"]))
    return out


def llama_1p3b():
    from paddle_tpu.models import LlamaConfig
    return LlamaConfig(vocab_size=32000, hidden_size=2048,
                       intermediate_size=5504, num_hidden_layers=24,
                       num_attention_heads=16,
                       max_position_embeddings=1024)


def phase_train(cfg=None, degrees=None, batch=8, seq=1024, steps=5,
                require_chip=True, **trainer_kw):
    """`steps` optimizer steps on one fixed batch; returns the record
    the parent prints. degrees: mesh axis sizes (default one chip)."""
    stamp = _enter(require_chip)
    clock = _CompileClock()
    import numpy as np
    import jax
    import paddle_tpu as paddle
    from paddle_tpu.distributed import fleet
    from paddle_tpu.distributed.mesh import build_mesh, set_global_mesh
    from paddle_tpu.models import LlamaForCausalLM
    from paddle_tpu.models.train_step import SpmdTrainer

    cfg = cfg or llama_1p3b()
    degrees = dict(degrees or {"data": 1, "pipe": 1, "sharding": 1,
                               "model": 1})
    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": degrees["data"],
                               "mp_degree": degrees["model"],
                               "pp_degree": degrees["pipe"],
                               "sharding_degree": degrees["sharding"]}
    fleet.init(is_collective=True, strategy=strategy)
    mesh = build_mesh(degrees)
    set_global_mesh(mesh)
    paddle.seed(0)
    with paddle.LazyGuard():
        model = LlamaForCausalLM(cfg)
    trainer = SpmdTrainer(model, mesh, lr=1e-4, param_dtype="bfloat16",
                          recompute=True, moment_dtype="bfloat16",
                          recompute_policy="full", ce_chunk=2048,
                          **trainer_kw)
    t0 = time.perf_counter()
    state = trainer.init_state()
    jax.block_until_ready(state)
    init_s = time.perf_counter() - t0

    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size, (batch, seq)).astype(np.int64)
    labels = np.roll(ids, -1, axis=1)
    losses, walls = [], []
    for _ in range(steps):
        t0 = time.perf_counter()
        state, loss = trainer.step(state, ids, labels)
        losses.append(float(jax.block_until_ready(loss)))
        walls.append(round(time.perf_counter() - t0, 3))
    mem = _device_memory()
    rec = {"phase": "train", "device": stamp, "mesh": degrees,
           "batch": batch, "seq": seq, "layers": cfg.num_hidden_layers,
           "losses": [round(x, 4) for x in losses], "step_wall_s": walls,
           "init_s": round(init_s, 2), "device_memory": mem,
           **clock.report()}
    print(f"[chip_smoke] train mesh={degrees} losses={rec['losses']} "
          f"step_wall_s={walls} (first includes compile) "
          f"compile_seconds={rec['compile_seconds']} "
          f"device_memory(bytes_in_use, peak)={mem}", flush=True)
    if not all(np.isfinite(losses)):
        raise AssertionError(f"non-finite loss: {losses}")
    if not losses[-1] < losses[0]:
        raise AssertionError(f"loss did not fall: {losses}")
    return rec


def _make_probe_engine(base, candidates):
    """The reference engine class: `base` plus a record, per emitted
    token, of how far each CANDIDATE stream's token sits below this
    engine's top logit — taken only while the candidate's tokens so far
    equal this engine's (same context, comparable logits).
    candidates: {name: {uid: token list}}; the class's `first_split`
    fills as {name: {uid: (position, margin / |top logit|)}}."""
    import numpy as np

    from paddle_tpu.inference.sampling import (SamplingParams,
                                               TokenMaskAutomaton)

    class ProbeEngine(base):
        first_split = {name: {} for name in candidates}

        def add_request(self, ids, max_new_tokens=32, **kw):
            # a greedy step program leaves its logits on the device; a
            # neutral processor chain keeps this engine on the arm that
            # materializes them, token for token the same
            kw.setdefault("sampling", SamplingParams(
                grammar=TokenMaskAutomaton.trivial(self.cfg.vocab_size)))
            return super().add_request(ids, max_new_tokens, **kw)

        def _select_tokens(self, rows, positions, mode, logits=None, **kw):
            toks = super()._select_tokens(rows, positions, mode,
                                          logits=logits, **kw)
            lg = np.asarray(logits, np.float32)
            for i, r in enumerate(rows):
                if r is None:
                    continue
                j = len(r.out)
                for name, streams in candidates.items():
                    theirs = streams[r.uid]
                    split = self.first_split[name]
                    if (r.uid in split or j >= len(theirs)
                            or list(theirs[:j]) != list(r.out)):
                        continue
                    if theirs[j] != toks[i]:
                        top = float(lg[i, toks[i]])
                        split[r.uid] = (j, (top - float(lg[i, theirs[j]]))
                                        / max(abs(top), 1e-6))
            return toks

    return ProbeEngine


def _serve_stream(eng, prompts, new_tokens):
    """add_request / step / drain; returns {uid: generated tokens}."""
    uids = [eng.add_request(p, max_new_tokens=new_tokens) for p in prompts]
    eng.drain()
    h = eng.health()
    fails = eng.failures()
    if fails or h["failed"] or h["done"] != len(prompts):
        raise AssertionError(
            f"{h['done']} done / {h['failed']} failed of {len(prompts)}: "
            f"{[str(f) for f in fails.values()]}")
    out = {}
    for uid, p in zip(uids, prompts):
        full = eng.result(uid)
        if len(full) != len(p) + new_tokens:
            raise AssertionError(
                f"request {uid}: {len(full) - len(p)} tokens generated, "
                f"expected {new_tokens}")
        out[uid] = [int(t) for t in full[len(p):]]
    if h["pages_free"] + h["prefix_pages"] != h["pages_total"]:
        raise AssertionError(
            f"page leak: free {h['pages_free']} + prefix "
            f"{h['prefix_pages']} != total {h['pages_total']}")
    return out, h


def phase_serve(cfg=None, layers=None, variants=None, max_len=1024,
                page_size=128, max_batch=8, prompt_lens=PROMPT_LENS,
                new_tokens=NEW_TOKENS, require_chip=True):
    """Each engine variant ({name: extra engine kwargs}; default the
    one knob-free engine), then the kernels-off tp=1 reference engine
    scoring every stream's first split. layers: depth cut (width is
    never cut)."""
    stamp = _enter(require_chip)
    clock = _CompileClock()
    import gc
    import numpy as np
    import jax
    import paddle_tpu as paddle
    from paddle_tpu.inference import ContinuousBatchingEngine
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    cfg = cfg or LlamaConfig.llama_7b()
    if layers:
        cfg.num_hidden_layers = int(layers)
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, cfg.vocab_size, (n,)).astype(np.int64)
               for n in prompt_lens]
    pool = dict(max_len=max_len, page_size=page_size, max_batch=max_batch,
                quant="int8", weight_dtype="bfloat16")

    def build(cls, **kw):
        # the same seed under LazyGuard gives every engine the same
        # weights, materialized leaf by leaf straight to int8/bf16
        paddle.seed(0)
        with paddle.LazyGuard():
            model = LlamaForCausalLM(cfg)
        t0 = time.perf_counter()
        eng = cls(model, **pool, **kw)
        jax.block_until_ready(eng.weights)
        return eng, round(time.perf_counter() - t0, 2)

    streams, runs = {}, []
    for name, kw in (variants or {"tp1": {}}).items():
        eng, build_s = build(ContinuousBatchingEngine, **kw)
        if require_chip and eng.interpret is not False:
            raise AssertionError("engine resolved interpret=True on chip")
        t0 = time.perf_counter()
        toks, h = _serve_stream(eng, prompts, new_tokens)
        run = {"engine": name, "tp": h["tp"],
               "megakernel": h["megakernel"],
               "interpret": eng.interpret, "build_s": build_s,
               "serve_wall_s": round(time.perf_counter() - t0, 2),
               "steps": h["steps"], "done": h["done"],
               "failed": h["failed"], "device_memory": _device_memory()}
        print(f"[chip_smoke] serve {name} {kw}: {h['done']} done / "
              f"{h['failed']} failed, megakernel={h['megakernel']} "
              f"interpret={eng.interpret} steps={h['steps']} "
              f"build_s={build_s} serve_wall_s={run['serve_wall_s']} "
              f"(compiles included) device_memory(bytes_in_use, peak)="
              f"{run['device_memory']}", flush=True)
        streams[name] = toks
        runs.append(run)
        del eng
        gc.collect()

    Probe = _make_probe_engine(ContinuousBatchingEngine, streams)
    ref, _ = build(Probe, megakernel=False, ragged_kernel=False)
    ref_toks, _ = _serve_stream(ref, prompts, new_tokens)

    agreement = {}
    for name, toks in streams.items():
        split = Probe.first_split[name]
        same = sum(toks[u] == ref_toks[u] for u in toks)
        matched = sum(split[u][0] if u in split else len(toks[u])
                      for u in toks)
        worst = max((m for _, m in split.values()), default=0.0)
        agreement[name] = {
            "identical_requests": same, "requests": len(toks),
            "tokens_matched_before_split": matched,
            "tokens_total": sum(len(t) for t in toks.values()),
            "splits": len(split), "max_tie_margin": round(worst, 5),
            "tie_tol": TIE_TOL}
        print(f"[chip_smoke] serve agreement {name} vs kernels-off: "
              f"{agreement[name]}", flush=True)
        for u, t in toks.items():
            if t != ref_toks[u] and u not in split:
                raise AssertionError(
                    f"{name} request {u} differs from the reference but "
                    "no split was scored")
        if worst > TIE_TOL:
            raise AssertionError(
                f"{name}: a token {worst:.4f} x |top logit| below the "
                f"reference engine's top (> {TIE_TOL}) — not a near-tie")
    return {"phase": "serve", "device": stamp,
            "layers": cfg.num_hidden_layers, "runs": runs,
            "agreement": agreement, **clock.report()}


def _report_device(out_dir, n):
    """spawn target: what one child sees, written where the parent of
    the children (which holds no chip) can read it. The child then
    keeps its chip until all n have reported: a chip opens for one
    process at a time, so n children holding one each at the same
    moment hold n distinct chips."""
    import jax
    rank = int(os.environ["PADDLE_TRAINER_ID"])
    devs = jax.devices()
    x = jax.numpy.ones((128, 128)) @ jax.numpy.ones((128, 128))
    rec = {"rank": rank, "platform": devs[0].platform,
           "kind": devs[0].device_kind, "count": len(devs),
           "visible": os.environ.get("TPU_VISIBLE_CHIPS"),
           "sum": float(x.sum())}
    tmp = os.path.join(out_dir, f"rank{rank}.tmp")
    with open(tmp, "w") as f:
        json.dump(rec, f)
    os.replace(tmp, os.path.join(out_dir, f"rank{rank}.json"))
    deadline = time.monotonic() + 180
    while not all(os.path.exists(os.path.join(out_dir, f"rank{r}.json"))
                  for r in range(n)):
        if time.monotonic() > deadline:
            raise TimeoutError("the other children never reported")
        time.sleep(0.2)


def phase_spawn(n=4, require_chip=True):
    """n children through paddle_tpu.distributed.spawn, each bound to
    its own chip; this process never initializes a backend."""
    import tempfile
    from paddle_tpu.distributed.spawn import spawn
    with tempfile.TemporaryDirectory(prefix="chip_smoke_spawn_") as out_dir:
        procs = spawn(_report_device, args=(out_dir, n), nprocs=n,
                      join=True)
        codes = [p.exitcode for p in procs]
        if any(codes):
            raise AssertionError(f"spawned children exited {codes}")
        recs = []
        for rank in range(n):
            with open(os.path.join(out_dir, f"rank{rank}.json")) as f:
                recs.append(json.load(f))
    print(f"[chip_smoke] spawn: {recs}", flush=True)
    # (that every child held its chip while this process and its
    # siblings were alive is the proof this process held none and no
    # two children shared one)
    if require_chip and any(r["platform"] != "tpu" or r["count"] != 1
                            for r in recs):
        raise AssertionError(f"a child did not get ONE tpu: {recs}")
    stamp = {"platform": recs[0]["platform"], "kind": recs[0]["kind"],
             "count": n}
    return {"phase": "spawn", "device": stamp, "children": recs}


def _child_main(args):
    if args.phase == "train":
        degrees = json.loads(args.mesh) if args.mesh else None
        kw = json.loads(args.trainer) if args.trainer else {}
        rec = phase_train(degrees=degrees, **kw)
    elif args.phase == "serve":
        rec = phase_serve(layers=args.layers, variants={
            f"tp{t}": ({"tp": int(t)} if int(t) > 1 else {})
            for t in args.tp.split(",")})
    elif args.phase == "spawn":
        rec = phase_spawn()
    else:
        raise SystemExit(f"unknown phase {args.phase!r}")
    print(RESULT_TAG + json.dumps(rec), flush=True)


# ------------------------------------------------------------------ parent --
def _run_phase(argv, timeout):
    """One child, its stdout passed through; returns (rc, result)."""
    proc = subprocess.Popen([sys.executable, HERE] + argv,
                            stdout=subprocess.PIPE, text=True,
                            start_new_session=True)

    def kill():
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass

    timer = threading.Timer(timeout, kill)
    timer.start()
    result = None
    try:
        for line in proc.stdout:
            if line.startswith(RESULT_TAG):
                result = json.loads(line[len(RESULT_TAG):])
            else:
                sys.stdout.write(line)
                sys.stdout.flush()
        rc = proc.wait()
    finally:
        timer.cancel()
        kill()       # grandchildren too: the whole process group
    return rc, result


# one chip: what the driver runs. Depth of the serve phase: see PERF.md.
PHASES_1 = [
    ("train", ["--phase", "train"], 420),
    ("serve", ["--phase", "serve"], 900),
]
# the four-chip host (2x2): both hybrid meshes against the one-chip
# step, tp serving against tp=1, and one chip per spawned child
_Z2MP2 = {"data": 1, "pipe": 1, "sharding": 2, "model": 2}
_PP2MP2 = {"data": 1, "pipe": 2, "sharding": 1, "model": 2}
PHASES_4 = [
    ("spawn", ["--phase", "spawn"], 240),
    ("train 1 chip", ["--phase", "train"], 420),
    ("train sharding2 x model2 (stage 2)",
     ["--phase", "train", "--mesh", json.dumps(_Z2MP2),
      "--trainer", json.dumps({"sharding_stage": 2})], 420),
    ("train pipe2 x model2 (1F1B)",
     ["--phase", "train", "--mesh", json.dumps(_PP2MP2),
      "--trainer", json.dumps({"pp_schedule": "1f1b",
                               "micro_batch_size": 2})], 420),
    ("serve tp 2,4 vs 1", ["--phase", "serve", "--tp", "2,4"], 900),
]
LOSS_TOL = 2e-2     # first loss, hybrid mesh vs one chip: bf16 matmuls
#                     reduced in another order move ln(32000)=10.37 in
#                     the third decimal; a mis-sharded layer moves it
#                     by tenths


def _check_four_chip(results):
    """Cross-phase checks: first losses agree with one chip, every
    device holds state, and the per-device peak is below one chip's."""
    trains = [r for r in results if r["phase"] == "train"]
    one = next(r for r in trains if set(r["mesh"].values()) == {1})
    one_peak = one["device_memory"][0][1]
    for r in trains:
        if r is one:
            continue
        d = abs(r["losses"][0] - one["losses"][0])
        used = [m[0] for m in r["device_memory"]]
        peak = max(m[1] for m in r["device_memory"])
        print(f"[chip_smoke] mesh={r['mesh']}: first loss "
              f"{r['losses'][0]} vs one chip {one['losses'][0]} "
              f"(|d|={d:.4f}); bytes_in_use per device {used}; "
              f"per-device peak {peak} vs one chip {one_peak}")
        if d > LOSS_TOL:
            raise AssertionError(f"first loss off by {d} on {r['mesh']}")
        if len(used) != 4 or not all(used):
            raise AssertionError(f"a device holds no state: {used}")
        if not peak < 0.75 * one_peak:
            raise AssertionError(
                f"per-device peak {peak} not well under one chip's "
                f"{one_peak} on {r['mesh']}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--phase", default=None)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--layers", type=int, default=None,
                    help="serve depth cut (width is never cut)")
    ap.add_argument("--tp", default="1")
    ap.add_argument("--mesh", default=None)
    ap.add_argument("--trainer", default=None)
    args = ap.parse_args()
    if args.phase:
        return _child_main(args)

    t_start = time.monotonic()
    phases = PHASES_1 if args.chips == 1 else PHASES_4
    results = []
    for name, argv, cap in phases:
        if args.layers and argv[1] == "serve":
            argv = argv + ["--layers", str(args.layers)]
        left = TOTAL_BUDGET_S - (time.monotonic() - t_start)
        if args.chips == 1:
            cap = min(cap, left)
        t0 = time.monotonic()
        rc, res = _run_phase(argv, max(cap, 1))
        clock = {k: (res or {}).get(k) for k in
                 ("compile_seconds", "cache_hits", "cache_misses")}
        print(f"[chip_smoke] phase {name!r}: rc={rc} "
              f"wall={time.monotonic() - t0:.1f}s {clock}", flush=True)
        if rc != 0 or res is None:
            print(f"[chip_smoke] FAILED in phase {name!r}",
                  file=sys.stderr)
            sys.exit(1)
        results.append(res)
    if args.chips == 4:
        _check_four_chip(results)
    dev = results[-1]["device"]
    print(f"[chip_smoke] all phases passed in "
          f"{time.monotonic() - t_start:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev["platform"], "kind": dev["kind"],
        "count": dev["count"]}}), flush=True)


if __name__ == "__main__":
    main()
