"""System runner: SpmdTrainer's compiled step on one chip or one mesh,
through the entry points a user calls (fleet.init -> build_mesh ->
SpmdTrainer.init_state / step / gather_params).

The window counts whole optimizer steps: it opens on an idle device,
every step gets a fresh seeded batch, and it closes on
`block_until_ready` of the last loss, so tokens / elapsed has no
rounding. One step is kept in flight ahead of the host so that making
the next batch never leaves the device waiting.
"""
import time

import numpy as np

from harness import manifest


def build(ctx):
    import jax
    from paddle_tpu.distributed import fleet
    from paddle_tpu.distributed.mesh import build_mesh, set_global_mesh
    from paddle_tpu.models.train_step import SpmdTrainer
    cfg = ctx.cell.config
    training = cfg["training"]
    degrees = dict(training["mesh"])
    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": degrees["data"],
                               "mp_degree": degrees["model"],
                               "pp_degree": degrees["pipe"],
                               "sharding_degree": degrees["sharding"]}
    fleet.init(is_collective=True, strategy=strategy)
    mesh = build_mesh(degrees, devices=jax.devices()[:ctx.cell.chips])
    set_global_mesh(mesh)
    family = manifest.load_plugin("references", cfg["reference"])
    model = family.build_model(cfg, ctx.seed)
    trainer = SpmdTrainer(model, mesh, **training["trainer"])
    state = trainer.init_state()
    jax.block_until_ready(state)
    return trainer, state, family


def run(ctx):
    import jax
    cell = ctx.cell
    cfg = cell.config
    span = ctx.tracer.span
    trainer, state, family = build(ctx)
    gen = manifest.load_plugin("generators", cell.traffic["generator"])
    batches = gen.make(cell.traffic["params"], ctx.seed, cfg["vocab_size"])
    ref = family.Reference(cfg)

    def ref_loss(state, i):
        ids, labels = batches.batch(i)
        return ref.loss(family.weights_from_trainer(trainer, state),
                        ids, labels)

    def step(state, i):
        with span("bench.make_batch"):
            ids, labels = batches.batch(i)
        with span("bench.step"):
            return trainer.step(state, ids, labels)

    # set-up: the reference's loss at the initial weights, then two steps
    ref_first = ref_loss(state, 0)
    state, loss = step(state, 0)
    got_first = float(jax.block_until_ready(loss))
    state, loss = step(state, 1)
    losses = [got_first, float(jax.block_until_ready(loss))]
    ctx.log(f"first loss {got_first:.5f}, reference {ref_first:.5f}")

    # the window
    n_warm = len(losses)
    t_ws = time.monotonic()
    pending = []                 # losses dispatched and not yet waited for
    seen_done = 0
    i = n_warm
    while time.monotonic() - t_ws < ctx.seconds:
        if seen_done:            # trace the last three steps, about
            per_step = (t_seen - t_ws) / seen_done
            if ctx.seconds - (time.monotonic() - t_ws) <= 3.5 * per_step:
                ctx.tracer.start()
        state, loss = step(state, i)
        pending.append(loss)
        i += 1
        if len(pending) > 1:     # wait for the step BEFORE this one
            with span("bench.wait"):
                losses.append(float(jax.block_until_ready(pending.pop(0))))
            seen_done += 1
            t_seen = time.monotonic()
    with span("bench.wait"):
        losses.extend(float(jax.block_until_ready(x)) for x in pending)
    t_we = time.monotonic()
    ctx.tracer.stop()
    n_steps = i - n_warm

    # after the window: the same comparison at the trained weights
    ref_last = ref_loss(state, i)
    state, loss = step(state, i)
    got_last = float(jax.block_until_ready(loss))
    ctx.log(f"last loss {got_last:.5f}, reference {ref_last:.5f}; "
            f"{n_steps} steps in {t_we - t_ws:.3f} s")

    window_losses = losses[n_warm:]
    finite = bool(np.all(np.isfinite(losses + [got_last])))
    # both tolerances depend on the tokens in a batch, so the traffic file
    # holds them, each with its reason
    loss_tol = float(cell.traffic["loss_tol"])
    rise_tol = float(cell.traffic["loss_rise_tol"])
    held = bool(np.mean(window_losses[-5:]) < losses[0] + rise_tol)
    agree = bool(abs(got_first - ref_first) <= loss_tol
                 and abs(got_last - ref_last) <= loss_tol)
    return {
        "kind": "train", "window": (t_ws, t_we), "n_steps": n_steps,
        "tokens_per_step": batches.tokens_per_step, "seq": batches.shape[1],
        "chips": cell.chips, "model": cfg,
        "attempted": n_steps,
        "failed": int(sum(not np.isfinite(x) for x in window_losses)),
        "correct": finite and held and agree,
        "checks": {"first": (got_first, ref_first),
                   "last": (got_last, ref_last), "loss_tol": loss_tol,
                   "finite": finite, "not_risen": held,
                   "loss_rise_tol": rise_tol,
                   "loss_first": losses[0],
                   "loss_mean_last5": float(np.mean(window_losses[-5:]))},
    }
