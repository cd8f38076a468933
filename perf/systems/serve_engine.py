"""System runner: one ContinuousBatchingEngine on one chip, driven through
its public API only (add_request / step / status / result / cancel /
health / headroom / export_weights and the public step counters).

The loop is the harness's own and single-threaded: add every request that
is due, `eng.step()`, then read `status(uid)` of what is in flight. A
request's first token is stamped at the first step after which its state
is decode or done, its end at done; all stamps are `time.monotonic()`.
This file records raw facts; every metric is a reader of its own under
perf/end_to_end/ or perf/layer_metrics/.
"""
import time

import numpy as np

from harness import manifest

# A token the engine emitted must sit within this share of |top logit|
# below the float32 reference's top logit under the same context. The
# engine multiplies in bf16 and the reference in float32, so near-ties
# part: on the v5e two bf16 engines parted by at most 0.0215 at 32 layers
# (PERF.md, PR 21); 2^-4 is three times that. A wrong kernel does not come
# close: with Gaussian logits over 92544 tokens an arbitrary token sits
# about 1.0 x |top| below the top and the runner-up about 0.05.
TIE_TOL = 2.0 ** -4


def engine_kwargs(cfg):
    """The pool of the configuration file and no mode knobs, unless the
    file pins `engine_overrides`, each with the compiler message that
    forced it."""
    serving = cfg["serving"]
    kw = dict(serving["engine"])
    kw.update({k: v["value"]
               for k, v in serving.get("engine_overrides", {}).items()})
    return kw


def build(ctx):
    """The engine as a user builds it, at the configuration's sizes."""
    import jax
    from paddle_tpu.inference import ContinuousBatchingEngine
    cfg = ctx.cell.config
    family = manifest.load_plugin("references", cfg["reference"])
    model = family.build_model(cfg, ctx.seed)
    eng = ContinuousBatchingEngine(model, **engine_kwargs(cfg))
    jax.block_until_ready(eng.weights)
    if not ctx.rehearse and eng.interpret is not False:
        raise RuntimeError("the engine resolved interpret=True on the chip")
    return eng, family


def check_against_reference(ctx, eng, family):
    """A few seeded requests through the engine, every emitted token
    scored by the float32 reference under the same context (teacher
    forcing: no dependence on which of two near-tied tokens was taken)."""
    import jax.numpy as jnp
    cfg = ctx.cell.config
    spec = cfg["serving"]["check"]
    rng = np.random.default_rng([ctx.seed, 7])
    n_new = int(spec["new_tokens"])
    lens = rng.integers(spec["prompt_min"], spec["prompt_max"] + 1,
                        int(spec["requests"]))
    prompts = [rng.integers(0, cfg["vocab_size"], int(n)) for n in lens]
    uids = [eng.add_request(p, max_new_tokens=n_new) for p in prompts]
    eng.drain()
    width = int(spec["prompt_max"]) + n_new
    ids = np.zeros((len(prompts), width), np.int64)
    rows, tokens = [], []
    for r, (uid, p) in enumerate(zip(uids, prompts)):
        full = np.asarray(eng.result(uid))
        if full.size != p.size + n_new:
            return {"ok": False, "why": f"request {uid} returned "
                    f"{full.size - p.size} tokens, asked {n_new}"}
        ids[r, :full.size] = full
        for j in range(n_new):           # position t0-1+j predicts token j
            rows.append((r, p.size - 1 + j))
            tokens.append(int(full[p.size + j]))
    ref = family.Reference(cfg)
    weights = family.weights_from_engine(eng)
    x = ref.hidden(weights, ids)
    r_idx, s_idx = (jnp.asarray(a) for a in zip(*rows))
    _, top, picked = ref.score(weights, x[r_idx, s_idx], tokens)
    margin = (top - picked) / np.maximum(np.abs(top), 1e-6)
    worst = float(np.max(margin))
    return {"ok": bool(worst <= TIE_TOL), "worst_margin": worst,
            "tie_tol": TIE_TOL, "tokens_scored": len(tokens),
            "tokens_equal_to_reference_top": int(np.sum(margin <= 0))}


def cover_shapes(eng):
    """Run every program the window can reach, once: `max_batch` short
    requests submitted together seat in slots 0..max_batch-1 one prefill
    at a time with a decode step between, so the highest live slot walks
    through every slot bucket; all prefill chunks share one program."""
    n = eng.max_batch
    rng = np.random.default_rng(0)
    for _ in range(n):
        eng.add_request(rng.integers(0, eng.cfg.vocab_size, 16),
                        max_new_tokens=min(2 * n, eng.max_len - 16))
    eng.drain()


class Driver:
    """The loop. `turn()` is one iteration; records accumulate in
    `requests` (one dict per request, absolute monotonic stamps) and
    `steps` (start, end, class, running, queued, pages_free)."""

    def __init__(self, eng, stream, tracer):
        self.eng, self.stream, self.tracer = eng, stream, tracer
        self.requests, self.steps = [], []
        self.live = {}
        self.injecting = True
        self.t0 = time.monotonic()

    def turn(self):
        eng, span = self.eng, self.tracer.span
        now = time.monotonic()
        if self.injecting:
            with span("bench.inject"):
                for req in self.stream.due(now - self.t0):
                    self._add(req)
        with span("bench.step"):
            before = (eng.prefill_steps, eng.decode_steps)
            t_a = time.monotonic()
            moved = eng.step()
            t_b = time.monotonic()
        with span("bench.poll"):
            if moved:
                kind = ("prefill" if eng.prefill_steps > before[0] else
                        "decode" if eng.decode_steps > before[1] else "other")
                room = eng.headroom()
                self.steps.append((t_a, t_b, kind, room["running"],
                                   room["queued"], room["pages_free"]))
            self._poll(t_b)
        if not moved:
            with span("bench.idle"):
                nxt = self.stream.next_due() if self.injecting else None
                wait = 0.001 if nxt is None else \
                    self.t0 + nxt - time.monotonic()
                if wait > 0:
                    time.sleep(min(wait, 0.001))
        return time.monotonic()

    def _add(self, req):
        rec = {"index": req.index, "t_due": self.t0 + req.t_due,
               "n_prompt": int(req.prompt.size), "max_new": req.max_new,
               "t_seat": None, "t_first": None, "t_done": None,
               "n_out": 0, "state": "queued", "req": req}
        self.requests.append(rec)
        try:
            uid = self.eng.add_request(req.prompt,
                                       max_new_tokens=req.max_new)
        except (ValueError, RuntimeError) as e:   # refused at the door
            # (too long, queue full): a failed request, not a crash
            rec.update(state="failed", t_add=time.monotonic(),
                       t_done=time.monotonic(), error=repr(e))
            self.stream.done(req, time.monotonic() - self.t0)
            return
        rec["t_add"] = time.monotonic()
        rec["uid"] = uid
        self.live[uid] = rec

    def _poll(self, t):
        for uid in list(self.live):
            rec = self.live[uid]
            state = self.eng.status(uid)
            if state == rec["state"]:
                continue
            rec["state"] = state
            if rec["t_seat"] is None and state != "queued":
                rec["t_seat"] = t
            if rec["t_first"] is None and state in ("decode", "done"):
                rec["t_first"] = t
            if state in ("done", "failed", "cancelled"):
                rec["t_done"] = t
                if state == "done":
                    rec["n_out"] = int(len(self.eng.result(uid))
                                       - rec["n_prompt"])
                del self.live[uid]
                self.stream.done(rec["req"], t - self.t0)

    def tokens_emitted(self):
        """Tokens the engine has pushed to requests so far (its public
        per-tenant counter; every request here is tenant 'default')."""
        t = self.eng.health()["tenants"].get("default")
        return 0 if t is None else int(t["tokens"])

    def abandon(self, t):
        """Cancel whatever is still in flight; each counts as failed."""
        for uid, rec in list(self.live.items()):
            self.eng.cancel(uid)
            rec.update(state="abandoned", t_done=t)
            del self.live[uid]


def run(ctx):
    cell = ctx.cell
    params = cell.traffic["params"]
    eng, family = build(ctx)
    ctx.log(f"engine built: megakernel={eng.health()['megakernel']} "
            f"interpret={eng.interpret} slots={eng.max_batch}")
    check = check_against_reference(ctx, eng, family)
    ctx.log(f"reference check: {check}")
    cover_shapes(eng)
    gen = manifest.load_plugin("generators", cell.traffic["generator"])
    stream = gen.make(params, ctx.seed, cell.config["vocab_size"])
    drv = Driver(eng, stream, ctx.tracer)

    # warm-up traffic: the cell's own stream until occupancy is steady
    warm_until = drv.t0 + float(cell.traffic["warmup_s"])
    now = drv.turn()
    while now < warm_until:
        now = drv.turn()
    t_ws = now
    tokens_0 = drv.tokens_emitted()
    trace_from = t_ws + ctx.seconds - min(5.0, ctx.seconds / 2.0)
    while now - t_ws < ctx.seconds:
        if now >= trace_from:
            ctx.tracer.start()
        now = drv.turn()
    t_we = now
    ctx.tracer.stop()
    tokens_1 = drv.tokens_emitted()

    # after the window nothing new is sent. An open loop waits (at most
    # `drain_s`) for the requests that fell due inside the window, and
    # what is not done by then has failed; a closed loop's callers are
    # simply cut off.
    drv.injecting = False
    if not stream.closed:
        give_up = t_we + float(cell.traffic.get("drain_s", 10.0))
        while now < give_up and any(
                t_ws <= r["t_due"] < t_we for r in drv.live.values()):
            now = drv.turn()
    drv.abandon(now)
    while eng.step():
        pass
    h = eng.health()
    leak = h["pages_free"] + h["prefix_pages"] != h["pages_total"]
    if leak:
        ctx.log(f"page leak: {h['pages_free']} free + {h['prefix_pages']} "
                f"prefix != {h['pages_total']}")

    if stream.closed:       # judged on what completed inside the window
        counted = [r for r in drv.requests
                   if r["t_done"] is not None and t_ws <= r["t_done"] < t_we
                   and r["state"] != "abandoned"]
    else:                   # judged on what fell due inside the window
        counted = [r for r in drv.requests if t_ws <= r["t_due"] < t_we]
    failed = [r for r in counted if r["state"] != "done"]
    ctx.log("requests [due s, prompt, output, ttft ms, done ms, state]: "
            + str([[round(r["t_due"] - t_ws, 2), r["n_prompt"], r["n_out"],
                    None if r["t_first"] is None else
                    round((r["t_first"] - r["t_due"]) * 1e3, 1),
                    None if r["t_done"] is None else
                    round((r["t_done"] - r["t_due"]) * 1e3, 1),
                    r["state"]] for r in counted]))
    for r in drv.requests:
        r.pop("req")
    return {
        "kind": "serve", "window": (t_ws, t_we), "t_give_up": now,
        "requests": counted, "steps": [s for s in drv.steps
                                       if t_ws <= s[0] < t_we],
        "tokens_in_window": tokens_1 - tokens_0,
        "slots_total": eng.max_batch, "pages_total": h["pages_total"],
        "model": cell.config, "closed_loop": stream.closed,
        "attempted": len(counted), "failed": len(failed),
        "correct": bool(check["ok"] and not leak),
        "checks": {"reference": check, "page_leak": leak},
    }
