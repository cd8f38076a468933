#!/usr/bin/env python3
"""Can `correct` fail in `serve-mla-sparse-longdoc` (or, with `--workload
serve-sparse-gqa-longctx`, in that cell: `CONTROLS` has each family's
list)? The harness's own comparison (`perf/systems/serve_engine.check_against_reference`: the
cell's engine, the check's prompts, the runner's `TIE_TOL`) against the
reference as the benchmark builds it, against each of the reference's four
deliberate faults (no selection, no gate, no rescale, a window one short)
and against the reference computed in bf16 and on float8 weights.

  chip:  chiprun --timeout 1800 -- python3 docs/probes/latent_check_controls.py \
             [--seed N] [--prompt-min A --prompt-max B --requests R]
  here:  JAX_PLATFORMS=cpu python3 docs/probes/latent_check_controls.py \
             --rehearse perf/rehearse_latent_sparse.json \
             --workload tiny-dots3-serve-closed

A probe, run by hand: no benchmark cell runs it, no test imports it. A
control that reads `ok: True` says the check cannot see that fault at these
prompt lengths. One JSON line a control to stdout and to
chiprun_out/check_controls.<workload>.json.

`--logits` compares LOGITS instead of tokens, which the harness cannot (it
drives the engine through its public API): every logits row the engine
chose a token from (caught at `_select_tokens`, as tests/test_dots3_note.py
does) against the control's logits for the same context, as the largest
|difference| over the vocabulary held / the largest |reference logit|, worst
and median over the rows, and the share of rows whose top token agrees.
"""
import argparse
import functools
import json
import os
import sys
import types

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "perf")]
CELL = "serve-mla-sparse-longdoc"
# by the configuration's `reference`: what its Reference(cfg, **kw) takes
CONTROLS = {
    "dots3_note": [("reference", {}),
                   ("no_selection", {"variant": "no_selection"}),
                   ("no_gate", {"variant": "no_gate"}),
                   ("no_rescale", {"variant": "no_rescale"}),
                   ("window_off_by_one", {"variant": "window_off_by_one"}),
                   ("bfloat16", {"precision": "bfloat16"}),
                   ("float8", {"precision": "float8"})],
    "keye_vl2": [("reference", {}),
                 ("no_selection", {"variant": "no_selection"}),
                 ("bf16_indexer", {"variant": "bf16_indexer"}),
                 ("no_qk_norm", {"variant": "no_qk_norm"}),
                 ("sigmoid_router", {"variant": "sigmoid_router"}),
                 ("float8_kv", {"variant": "float8_kv"}),
                 ("bfloat16", {"precision": "bfloat16"})]}


def engine_logits(eng, cfg, seed):
    """The check's own prompts through the engine: (ids [requests, width]
    padded, [(request row, position)], logits [rows, vocabulary held]
    float32 the engine chose position + 1's token from)."""
    import numpy as np
    spec = cfg["serving"]["check"]
    rng = np.random.default_rng([seed, 7])
    lens = rng.integers(spec["prompt_min"], spec["prompt_max"] + 1,
                        int(spec["requests"]))
    prompts = [rng.integers(0, cfg["vocab_size"], int(n)) for n in lens]
    seen, select = [], eng._select_tokens

    def spy(rows, positions, mode, logits=None, **kw):
        for i, r in enumerate(rows):
            if r is not None:
                seen.append((r.uid, int(positions[i]) - 1,
                             np.asarray(logits[i], np.float32)))
        return select(rows, positions, mode, logits=logits, **kw)

    eng._select_tokens = spy
    # (a greedy step program leaves its logits on the device: a neutral
    # processor chain serves these on the arm that materializes them)
    from paddle_tpu.inference.sampling import (SamplingParams,
                                               TokenMaskAutomaton)
    anything = SamplingParams(
        grammar=TokenMaskAutomaton.trivial(cfg["vocab_size"]))
    uids = [eng.add_request(p, max_new_tokens=int(spec["new_tokens"]),
                            sampling=anything)
            for p in prompts]
    eng.drain()
    eng._select_tokens = select
    ids = np.zeros((len(uids), int(spec["prompt_max"])
                    + int(spec["new_tokens"])), np.int64)
    for row, uid in enumerate(uids):
        full = np.asarray(eng.result(uid))
        ids[row, :full.size] = full
    where = [(uids.index(uid), pos) for uid, pos, _ in seen]
    return ids, where, np.stack([lg for _, _, lg in seen])


def logit_distance(ref, weights, ids, where, got):
    import jax
    import jax.numpy as jnp
    import numpy as np
    x = ref.hidden(weights, ids)
    r_idx, s_idx = (jnp.asarray(a) for a in zip(*where))
    with jax.default_matmul_precision("highest"):
        want = np.asarray(jnp.dot(x[r_idx, s_idx],
                                  weights["head"].astype(jnp.float32)))
    dist = np.max(np.abs(got - want), axis=1) / np.max(np.abs(want), axis=1)
    return {"rows": len(where), "logit_distance_worst": float(dist.max()),
            "logit_distance_median": float(np.median(dist)),
            "top_token_agrees": float(np.mean(
                got.argmax(1) == want.argmax(1)))}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=3000000121)
    ap.add_argument("--prompt-min", type=int)
    ap.add_argument("--prompt-max", type=int)
    ap.add_argument("--requests", type=int)
    ap.add_argument("--only", nargs="+")
    ap.add_argument("--logits", action="store_true")
    ap.add_argument("--rehearse", help="a rehearsal manifest, for the CPU")
    ap.add_argument("--workload", default=CELL)
    args = ap.parse_args(argv)
    from harness import manifest
    import run as perf_run
    perf_run.keep_every_executable()
    cell = manifest.Cell(manifest.load_json(os.path.join(
        ROOT, args.rehearse or "BENCHMARK.json")), args.workload)
    check = cell.config["serving"]["check"]
    for key in ("prompt_min", "prompt_max", "requests"):
        if getattr(args, key) is not None:
            check[key] = getattr(args, key)
    ctx = perf_run.Context(
        cell, types.SimpleNamespace(seed=args.seed, seconds=40,
                                    rehearse=args.rehearse),
        perf_run.Tracer(False, os.devnull))
    runner = manifest.load_plugin("systems", "serve_engine")
    eng, family = runner.build(ctx)
    out = os.path.join(ROOT, "chiprun_out",
                       f"check_controls.{args.workload}.json")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    true_reference = family.Reference
    rows = engine_logits(eng, cell.config, args.seed) if args.logits else None
    with open(out, "a") as f:
        for name, kw in CONTROLS[cell.config["reference"]]:
            if args.only and name not in args.only:
                continue
            if rows is not None:
                got = logit_distance(true_reference(cell.config, **kw),
                                     family.weights_from_engine(eng), *rows)
            else:
                family.Reference = functools.partial(true_reference, **kw)
                got = runner.check_against_reference(ctx, eng, family)
            line = json.dumps({"control": name, "seed": args.seed,
                               "check": dict(check), **got})
            print(line, flush=True)
            f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
