#!/usr/bin/env python3
"""Where ONE per-layer decode megakernel call spends its time at the dense
serving cell's geometry (InternLM2-7B widths, 32 slots x 8 pages of 128,
bf16 activations).

  chip:  chiprun -- python3 docs/probes/mk_layer_probe.py [--budgets]
  here:  JAX_PLATFORMS=cpu python3 docs/probes/mk_layer_probe.py --aot

A probe, run by hand: no benchmark cell runs it, no test imports it. It
sets the module's budget constant, swaps the shared tile body and takes
a phase out of the schedule, which is what a probe may do and an engine
may not. It reads only names every tree since PR 27 has, so the same
file copied into a checkout of an older commit measures that commit
(parent against change, one chip call). `--aot` compiles every variant
for a DESCRIBED v5e and runs nothing (what the TPU compiler refuses
here, VMEM above all, it would refuse on the chip). On the chip each
variant is a scan of CALLS layer calls in one program, timed by the host
clock around `block_until_ready`, best of REPS: ms a call, and us a grid
step of the call's schedule.

Default plan (PR 29): the attention phase. LIVE pages a slot over
0 (every slot inactive), 2 (the cell's mean by length), 4 and 8 (the
whole table), and once with the phase out of the schedule: what is left
is the matmul phases, and `attention_share` of a variant is
1 - that / its own time.

`--budgets` (PR 27): the block budget of the tile plan
(`decode_megakernel.MM_BLOCK_BYTES`; 0.25 MiB is the 512 x 512 int8 tile
the kernel had before PR 27), the product in or taken out (`walk`: the
block is still fetched, nothing is computed from it but one row;
`convert`: the int8 -> bf16 conversion and a float32 sum of the tile on
the VPU, no MXU: more vector work than the product needs, so no floor
under it), and dense bf16 weights at the settled budget to show whether
bytes or steps set the time. Lines go to stdout as JSON and to
chiprun_out/mk_layer_probe.json.
"""
import inspect
import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", ".."))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import paddle_tpu  # noqa: E402,F401
from paddle_tpu import chip  # noqa: E402
from paddle_tpu.ops.pallas import decode_megakernel as dm  # noqa: E402
from paddle_tpu.ops.pallas import quantized_matmul as qm  # noqa: E402

H, F, NH, NKV, HD, R, P, MP = 4096, 14336, 32, 8, 128, 32, 128, 8
CALLS, REPS = 16, 5
LIVE = 2                       # live pages a slot, as in the cell
MiB = 1 << 20
bf16, f32 = jnp.bfloat16, jnp.float32
key = jax.random.PRNGKey(0)

TILES = {
    "product": qm.dot_tile_f32,
    "convert": lambda x, w, dt=None: jnp.sum(
        w.astype(bf16).astype(f32).reshape(-1, x.shape[0], w.shape[1]),
        axis=0),
    "walk": lambda x, w, dt=None: jnp.broadcast_to(
        w[:8, :].astype(f32)[:1], (x.shape[0], w.shape[1])),
}
# (budget, tile body, dense bf16 weights, live pages a slot; None = the
# attention phase out of the schedule)
ATTN_PLAN = [(2 * MiB, "product", False, live)
             for live in (None, 0, 2, 4, MP)]
BUDGET_PLAN = [(b, t, False, live)
               for b in (MiB // 4, MiB, 2 * MiB, 4 * MiB)
               for t, live in (("product", LIVE), ("product", 0),
                               ("walk", 0))]
BUDGET_PLAN += [(2 * MiB, "convert", False, 0),
                (2 * MiB, "product", False, MP),
                (MiB // 2, "product", True, 0),
                (2 * MiB, "product", True, 0)]
FULL = dm.SEG_PHASES["full"]


def weight(k, n, i, dense):
    kk = jax.random.fold_in(key, i)
    if dense:
        return jax.random.normal(kk, (k, n), bf16) * 0.02
    return (jax.random.randint(kk, (k, n), -127, 128, jnp.int8),
            jnp.full((n,), 1e-3, f32))


def layer(dense):
    shapes = dict(wq=(H, H), wk=(H, NKV * HD), wv=(H, NKV * HD), wo=(H, H),
                  wg=(H, F), wu=(H, F), wd=(F, H))
    ws = {k: weight(*kn, i, dense) for i, (k, kn) in enumerate(shapes.items())}
    ws.update(ln1=jnp.ones((H,), bf16), ln2=jnp.ones((H,), bf16))
    return ws


def main():
    aot = "--aot" in sys.argv
    plan = BUDGET_PLAN if "--budgets" in sys.argv else ATTN_PLAN
    if aot:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding
        one = SingleDeviceSharding(topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices[0])
    else:
        chip.require_tpu()
    n_pages = R * MP
    kp = jax.random.normal(jax.random.fold_in(key, 8),
                           (n_pages, P, NKV, HD), bf16)
    vp = jax.random.normal(jax.random.fold_in(key, 9),
                           (n_pages, P, NKV, HD), bf16)
    tbl = jnp.arange(n_pages, dtype=jnp.int32).reshape(R, MP)
    h0 = jax.random.normal(jax.random.fold_in(key, 10), (R, H), bf16)
    cos, sin = jnp.ones((R, HD // 2), f32), jnp.zeros((R, HD // 2), f32)
    packs = {d: dm.pack_decode_layer(layer(d), cdtype=bf16)
             for d in sorted({p[2] for p in plan})}
    recs, no_attn_ms = [], None
    # before PR 29 the plan took the table's width: a step a (slot, page)
    by_table = "pages" in inspect.signature(dm.layer_tile_plan).parameters
    for budget, tile, dense, live in plan:
        jax.clear_caches()      # the call is a jit of its own since PR 29:
        #                         what is swapped here is not in its key
        dm.MM_BLOCK_BYTES = budget
        dm.dot_tile_f32 = TILES[tile]
        dm.SEG_PHASES["full"] = (FULL if live is not None else tuple(
            ph for ph in FULL if ph != dm.PH_ATTN))
        mk = packs[dense]
        steps = dict((dm.layer_tile_plan(mk, R, MP) if by_table
                      else dm.layer_tile_plan(mk, R))["layer_steps"])
        if live is None:
            steps["attention"] = 0
        lens = jnp.full((R,), max((live or 0) * P - 1, 0), jnp.int32)
        act = jnp.full((R,), 1 if live else 0, jnp.int32)

        @jax.jit
        def f(h, mk, kp, vp):
            def body(h, _):
                h2, kn, vn = dm.decode_megakernel(
                    h, mk, kp, vp, tbl, lens, act, cos, sin, nh=NH,
                    nh_kv=NKV, hd=HD, eps=1e-6)
                return (h2 * 0.5).astype(bf16), (kn[0, 0], vn[0, 0])
            return jax.lax.scan(body, h, None, length=CALLS)

        rec = {"block_mib": budget / MiB, "tile_body": tile,
               "dense_bf16_weights": dense, "live_pages_a_slot": live,
               "steps_matmul": steps["matmul"],
               "steps_attention": steps["attention"]}
        try:
            if aot:
                f.lower(*jax.tree.map(lambda a: jax.ShapeDtypeStruct(
                    a.shape, a.dtype, sharding=one),
                    (h0, mk, kp, vp))).compile()
                rec["aot"] = "ok"
            else:
                jax.block_until_ready(f(h0, mk, kp, vp))
                ts = []
                for _ in range(REPS):
                    t = time.perf_counter()
                    jax.block_until_ready(f(h0, mk, kp, vp))
                    ts.append((time.perf_counter() - t) / CALLS * 1e3)
                n = steps["matmul"] + steps["attention"]
                rec.update(ms_a_call=min(ts), ms_a_call_all=ts,
                           us_a_grid_step=min(ts) * 1e3 / n)
                if live is None:
                    no_attn_ms = min(ts)
                elif no_attn_ms is not None:
                    rec["attention_share"] = 1 - no_attn_ms / min(ts)
        except Exception as e:      # a variant Mosaic refuses is a finding
            rec["error"] = repr(e)[:600]
        print(json.dumps(rec), flush=True)
        recs.append(rec)
    if not aot:
        os.makedirs("chiprun_out", exist_ok=True)
        with open("chiprun_out/mk_layer_probe.json", "w") as fh:
            json.dump(recs, fh, indent=1)


if __name__ == "__main__":
    main()
