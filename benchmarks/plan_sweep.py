#!/usr/bin/env python
"""Plan-sweep harness: measured vs predicted cost for the planner's
top-k plans (docs/distributed_perf.md "Plan search").

For each top-k plan out of cost_model.search_plan this script BUILDS
the real thing (engine via fleet.build_engine_from_spec, trainer via
SpmdTrainer(plan=...)), measures the per-stage wall-clock the model
predicts (serving: TTFT + TPOT; training: step time), and emits one
MLPerf-style BENCH JSON line per plan:

  {"metric": "plan_sweep_serving", "plan": {...},
   "predicted_ttft_ms": ..., "measured_ttft_ms": ...,
   "predicted_tpot_ms": ..., "measured_tpot_ms": ...,
   "rank_predicted": 0, "rank_measured": 1}

then the ranking verdict (the CPU claim this harness exists to check —
the model's ORDER must survive contact with the machine even where its
absolute numbers are nominal):

  {"metric": "plan_sweep_ranking", "mode": "serving",
   "top1_predicted_measured_rank": 1, "pass": true}

and finally feeds the measured/predicted ratios back as calibration
(benchmarks/calib/residuals.json, loaded by cost_model.Calibration) so
the next prediction is anchored to this machine.

CPU micro sweep (the tier-1 evidence): 8 virtual devices, tiny model.
On a TPU host the same sweep is the "fast as the hardware allows"
check against real HBM/ICI.
"""
import json
import os
import socket
import sys
import time

import numpy as np

# runnable from anywhere: the script dir (benchmarks/) is what lands on
# sys.path, not the repo root
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

N_CPU_DEVICES = 8
CALIB_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "calib")


def _emit(payload):
    import jax
    payload.setdefault("jax_version", jax.__version__)
    payload.setdefault("backend", jax.default_backend())
    payload.setdefault("hostname", socket.gethostname())
    print(json.dumps(payload))
    sys.stdout.flush()


def _measure_serving(spec, prompt_len, gen_tokens):
    """Build the engine the spec describes (the SAME factory the fleet
    uses) and measure TTFT / TPOT on one request, after a full warmup
    request has paid compilation."""
    from paddle_tpu.inference.fleet import build_engine_from_spec
    engine = build_engine_from_spec(spec)
    rng = np.random.RandomState(0)
    vocab = engine.cfg.vocab_size

    def one_request():
        prompt = rng.randint(0, vocab, (prompt_len,)).astype(np.int64)
        t0 = time.perf_counter()
        uid = engine.add_request(prompt, max_new_tokens=gen_tokens)
        while engine._requests[uid].state in ("queued", "prefill"):
            engine.step()
        t_first = time.perf_counter()
        engine.drain()
        t_end = time.perf_counter()
        out = engine.result(uid)
        decoded = max(1, out.size - prompt_len - 1)
        return ((t_first - t0) * 1e3,
                (t_end - t_first) * 1e3 / decoded)

    # two warmups: tp>1 engines pay a SECOND prefill compile on the
    # first post-warmup request (page-table layout differs once the
    # pool has history) — measured numbers must be steady-state
    one_request()
    one_request()
    ttft, tpot = one_request()
    return ttft, tpot


def _measure_training(plan, model_cfg, global_batch, seq, steps=3):
    """Build the trainer the plan describes (mesh from plan.mesh_axes,
    knobs from plan=) and measure the steady-state step time."""
    import jax
    import paddle_tpu as paddle
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.models.train_step import SpmdTrainer
    from paddle_tpu.distributed.mesh import set_global_mesh
    from paddle_tpu.distributed import fleet

    mesh = plan.build_mesh()
    set_global_mesh(mesh)
    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {
        "dp_degree": plan.dp, "mp_degree": plan.mp,
        "pp_degree": plan.pp, "sharding_degree": plan.sharding}
    fleet.init(is_collective=True, strategy=strategy)
    paddle.seed(11)
    model = LlamaForCausalLM(model_cfg)
    trainer = SpmdTrainer(model, mesh, plan=plan)
    state = trainer.init_state()
    rng = np.random.RandomState(0)
    ids = rng.randint(0, model_cfg.vocab_size,
                      (global_batch, seq)).astype(np.int64)
    labels = np.roll(ids, -1, axis=1)
    key = jax.random.PRNGKey(7)
    state, _ = trainer.step(state, ids, labels, key=key)  # compile
    t0 = time.perf_counter()
    for _ in range(steps):
        state, loss = trainer.step(state, ids, labels, key=key)
    jax.block_until_ready(loss)
    return (time.perf_counter() - t0) / steps * 1e3


def _rank_check(mode, rows, key_pred, key_meas):
    """The harness's claim: predicted order survives measurement —
    top-1 predicted must land in the top-2 measured.  Near-tie escape:
    when the sweep's candidates are predicted within noise of each
    other, rank among them is a coin flip — the check still passes if
    the predicted winner MEASURES within 25% of the best, because the
    planner then lost nothing by picking it."""
    by_meas = sorted(range(len(rows)), key=lambda i: rows[i][key_meas])
    meas_rank = {i: r for r, i in enumerate(by_meas)}
    top1_rank = meas_rank[0]           # rows arrive predicted-ordered
    best = rows[by_meas[0]][key_meas]
    regret = rows[0][key_meas] / max(best, 1e-9)
    ok = top1_rank <= 1 or regret <= 1.25
    _emit({"metric": "plan_sweep_ranking", "mode": mode,
           "plans": len(rows),
           "top1_predicted_measured_rank": top1_rank,
           "top1_measured_regret": round(regret, 4),
           "pass": bool(ok)})
    return ok


def _write_residuals(serving_rows, training_rows, path, calib):
    """measured/predicted ratios -> the calibration feedback file
    cost_model.Calibration multiplies into its next predictions.
    Geometric mean (ratios are multiplicative corrections), COMPOUNDED
    onto the residual the predictions already carried — the file always
    holds the total correction relative to the uncalibrated model, so
    repeated sweeps converge instead of oscillating."""
    def gmean(vals):
        vals = [v for v in vals if v > 0]
        if not vals:
            return 1.0
        return float(np.exp(np.mean(np.log(vals))))

    # merge onto the existing file: a training-only sweep must not
    # drop the serving residuals (and vice versa)
    resid = {}
    if os.path.exists(path):
        try:
            with open(path) as f:
                resid = json.load(f).get("residuals", {}) or {}
        except (OSError, ValueError):
            resid = {}
    if serving_rows:
        resid["serving"] = {
            "tpot": round(calib.residual("serving", "tpot")
                          * gmean([r["measured_tpot_ms"]
                                   / max(r["predicted_tpot_ms"], 1e-9)
                                   for r in serving_rows]), 4),
            "ttft": round(calib.residual("serving", "ttft")
                          * gmean([r["measured_ttft_ms"]
                                   / max(r["predicted_ttft_ms"], 1e-9)
                                   for r in serving_rows]), 4)}
    if training_rows:
        resid["training"] = {
            "step": round(calib.residual("training", "step")
                          * gmean([r["measured_step_ms"]
                                   / max(r["predicted_step_ms"], 1e-9)
                                   for r in training_rows]), 4)}
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    with open(path, "w") as f:
        json.dump({"source": "plan_sweep.py", "residuals": resid},
                  f, indent=1, sort_keys=True)
    _emit({"metric": "plan_sweep_residuals", "path": path, **resid})


def main():
    argv = sys.argv[1:]
    mode = "serving"
    if "--mode" in argv:
        mode = argv[argv.index("--mode") + 1]
        if mode not in ("serving", "training", "both"):
            raise SystemExit(f"--mode must be serving/training/both, "
                             f"got {mode!r}")
    top_k = int(argv[argv.index("--top-k") + 1]) if "--top-k" in argv \
        else 4
    write_residuals = "--no-residuals" not in argv

    # the virtual multi-device CPU mesh must be pinned BEFORE the jax
    # backend initializes
    import jax
    if os.environ.get("JAX_PLATFORMS", "") == "cpu":
        jax.config.update("jax_num_cpu_devices", N_CPU_DEVICES)
    from paddle_tpu.chip import enable_compile_cache
    enable_compile_cache()
    from paddle_tpu.cost_model import (Calibration, EngineSpec,
                                       search_plan)
    from paddle_tpu.models import LlamaConfig

    n_dev = len(jax.devices())
    calib = Calibration.load()
    cfg = LlamaConfig.tiny()
    prompt_len, gen_tokens = 16, 24
    _emit({"metric": "plan_sweep_config", "devices": n_dev,
           "calibration": calib.source, "top_k": top_k, "mode": mode})

    serving_rows, training_rows = [], []
    if mode in ("serving", "both"):
        base = EngineSpec(model={"preset": "tiny", "seed": 0},
                          max_len=64, page_size=16, max_batch=2)
        # single-engine sweep: replicas stay 1 (a K-process fleet per
        # candidate would measure spawn cost, not the plan), tp ranges
        # over the device count — the knobs whose cost the model claims
        # to order.  Searching each tp-sized sub-mesh keeps exactly the
        # replicas==1 slice of the full space.
        cands = []
        for tp in (t for t in range(1, n_dev + 1) if n_dev % t == 0):
            cands += [r for r in search_plan(
                cfg, tp, mode="serving", top_k=None, base_spec=base,
                calib=calib, prompt_len=prompt_len,
                gen_tokens=gen_tokens) if r.plan.replicas == 1]
        cands.sort(key=lambda r: r.cost.total_ms)
        ranked = cands[:top_k]
        for i, r in enumerate(ranked):
            ttft, tpot = _measure_serving(r.plan, prompt_len,
                                          gen_tokens)
            row = {"plan": r.plan.to_json(),
                   "predicted_ttft_ms": round(r.cost.meta["ttft_ms"], 4),
                   "measured_ttft_ms": round(ttft, 4),
                   "predicted_tpot_ms": round(r.cost.meta["tpot_ms"], 4),
                   "measured_tpot_ms": round(tpot, 4),
                   "predicted_total_ms": round(r.cost.total_ms, 4),
                   "measured_total_ms": round(ttft + gen_tokens * tpot,
                                              4),
                   "dominant": r.cost.dominant,
                   "rank_predicted": i}
            serving_rows.append(row)
        by_meas = sorted(range(len(serving_rows)),
                         key=lambda i: serving_rows[i]
                         ["measured_total_ms"])
        for r, i in enumerate(by_meas):
            serving_rows[i]["rank_measured"] = r
        for row in serving_rows:
            _emit({"metric": "plan_sweep_serving", **row})
        ok = _rank_check("serving", serving_rows, "predicted_total_ms",
                         "measured_total_ms")
    else:
        ok = True

    if mode in ("training", "both"):
        global_batch, seq = 8, 32
        ranked = search_plan(cfg, n_dev, mode="training", top_k=top_k,
                             calib=calib, global_batch=global_batch,
                             seq=seq)
        for i, r in enumerate(ranked):
            step_ms = _measure_training(r.plan, cfg, global_batch, seq)
            row = {"plan": r.plan.to_json(),
                   "predicted_step_ms": round(r.cost.total_ms, 4),
                   "measured_step_ms": round(step_ms, 4),
                   "dominant": r.cost.dominant,
                   "rank_predicted": i}
            training_rows.append(row)
        by_meas = sorted(range(len(training_rows)),
                         key=lambda i: training_rows[i]
                         ["measured_step_ms"])
        for r, i in enumerate(by_meas):
            training_rows[i]["rank_measured"] = r
        for row in training_rows:
            _emit({"metric": "plan_sweep_training", **row})
        ok = _rank_check("training", training_rows,
                         "predicted_step_ms", "measured_step_ms") and ok

    if write_residuals and (serving_rows or training_rows):
        _write_residuals(serving_rows, training_rows,
                         os.path.join(CALIB_DIR, "residuals.json"),
                         calib)
    if not ok:
        sys.exit(1)


if __name__ == "__main__":
    main()
