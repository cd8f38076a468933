#!/usr/bin/env python3
"""How close does a sound engine come to the runner's `TIE_TOL` in a
serving cell? The cell's engine built ONCE, then the harness's reference
check (`perf/systems/serve_engine.check_against_reference`'s prompts and
scoring) repeated over `--checks` prompt seeds, keeping EVERY scored
token's margin, not only the worst: the per-check worst margins are what
`correct` sees, the pooled non-zero margins say how heavy their tail is.

  chip:  chiprun --timeout 1800 -- python3 docs/probes/check_margin_tail.py \
             --workload serve-sparse-gqa-longctx --checks 24
  here:  JAX_PLATFORMS=cpu python3 docs/probes/check_margin_tail.py \
             --rehearse perf/rehearse_sparse_gqa.json \
             --workload tiny-keye-serve-closed --checks 2

A probe, run by hand: no cell runs it, no test imports it. One JSON line
to stdout and to chiprun_out/check_margin_tail.<workload>.json. The
weights are one seed's (`--seed`); the prompts differ by check.
"""
import argparse
import json
import os
import sys
import types

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "perf")]


def margins(eng, family, ref, cfg, seed):
    """The check's requests through the engine under prompt seed `seed`:
    (top - picked) / |top| of every emitted token, by the reference."""
    import numpy as np
    import jax.numpy as jnp
    spec = cfg["serving"]["check"]
    rng = np.random.default_rng([seed, 7])
    n_new = int(spec["new_tokens"])
    lens = rng.integers(spec["prompt_min"], spec["prompt_max"] + 1,
                        int(spec["requests"]))
    prompts = [rng.integers(0, cfg["vocab_size"], int(n)) for n in lens]
    uids = [eng.add_request(p, max_new_tokens=n_new) for p in prompts]
    eng.drain()
    ids = np.zeros((len(prompts), int(spec["prompt_max"]) + n_new), np.int64)
    rows, tokens = [], []
    for r, (uid, p) in enumerate(zip(uids, prompts)):
        full = np.asarray(eng.result(uid))
        ids[r, :full.size] = full
        for j in range(n_new):
            rows.append((r, p.size - 1 + j))
            tokens.append(int(full[p.size + j]))
    weights = family.weights_from_engine(eng)
    x = ref.hidden(weights, ids)
    r_idx, s_idx = (jnp.asarray(a) for a in zip(*rows))
    _, top, picked = ref.score(weights, x[r_idx, s_idx], tokens)
    return (top - picked) / np.maximum(np.abs(top), 1e-6)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=3200000101)
    ap.add_argument("--checks", type=int, default=24)
    ap.add_argument("--rehearse", help="a rehearsal manifest, for the CPU")
    args = ap.parse_args(argv)
    import numpy as np
    from harness import manifest
    import run as perf_run
    if not args.rehearse:
        perf_run.keep_every_executable()
    cell = manifest.Cell(manifest.load_json(os.path.join(
        ROOT, args.rehearse or "BENCHMARK.json")), args.workload)
    ctx = perf_run.Context(
        cell, types.SimpleNamespace(seed=args.seed, seconds=40,
                                    rehearse=args.rehearse),
        perf_run.Tracer(False, os.devnull))
    runner = manifest.load_plugin("systems", "serve_engine")
    eng, family = runner.build(ctx)
    ref = family.Reference(cell.config)
    worst, nonzero = [], []
    for i in range(args.checks):
        m = margins(eng, family, ref, cell.config, args.seed + 1 + i)
        worst.append(float(np.max(m)))
        nonzero += [float(v) for v in m if v > 0]
        print(f"check {i}: worst {worst[-1]:.4f}, tokens off the "
              f"reference's top {int(np.sum(m > 0))} of {m.size}",
              file=sys.stderr, flush=True)
    out = {"workload": args.workload, "seed": args.seed,
           "tie_tol": runner.TIE_TOL, "tokens_per_check": int(m.size),
           "worst_by_check": sorted(worst),
           "nonzero_margins": sorted(nonzero)}
    line = json.dumps(out)
    path = os.path.join(ROOT, "chiprun_out",
                        f"check_margin_tail.{args.workload}.json")
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "a") as f:
        f.write(line + "\n")
    print(line)
    return 0


if __name__ == "__main__":
    sys.exit(main())
