"""Dispatch ahead (ISSUE 31): on the decode_block == 1 path the engine
dispatches program n+1 BEFORE it fetches and books program n's tokens.

The contract, one parametrised test per property:
  - the token streams of an engine that runs ahead equal, byte for byte,
    those of the same engine forced to resolve first, per kind of step
    program (the op chain, the per-layer megakernel, a description with
    routed experts) and per way a request can end or move (budget, EOS in
    the middle, EOS on the first token, a prompt of several chunks
    joining a full batch, cancel while a program is in flight, a
    preempted request, prefix sharing with copy-on-write);
  - no page leaks after drain();
  - status(), headroom(), pending(), len() and health() never resolve;
    drain(), result(), cancel(), the exports do;
  - a participant whose token decides something on the host (processors,
    a stop sequence, sampling, a deadline, an armed fault point) makes the
    step resolve first, and `ahead.resolved_first` says why;
  - `ahead.overrun_rows` counts the row that ran one step past its EOS;
  - no host array handed to a dispatch is aliased.

Micro geometries: the claim is the ORDER of host work around unchanged
programs.
"""
import jax
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import failsafe, profiler
from paddle_tpu.inference.sampling import SamplingParams
from paddle_tpu.inference.scheduler import (ContinuousBatchingEngine,
                                            _Dispatched)
from paddle_tpu.models import (LlamaConfig, LlamaForCausalLM, MiMoV2Config,
                               MiMoV2ForCausalLM)

KINDS = ("op_chain", "megakernel", "experts")
TENANTS = {"lo": {"priority": 0}, "hi": {"priority": 1}}


@pytest.fixture(scope="module")
def models():
    paddle.seed(11)
    llama = LlamaForCausalLM(LlamaConfig.tiny(
        num_hidden_layers=1, num_key_value_heads=2))
    return {"llama": llama, "mimo": MiMoV2ForCausalLM(MiMoV2Config.tiny())}


def build(models, kind, **kw):
    kw = dict(dict(max_len=64, page_size=8, max_batch=3, prefill_chunk=8,
                   tenants=TENANTS), **kw)
    if kind != "op_chain":
        # one compiled width: these programs are the slow ones to build
        # (the op chain keeps every bucket: widths change between steps)
        kw.setdefault("slot_buckets", (kw["max_batch"],))
    if kind == "experts":
        # (window 8, pages of 8: prompts run past the window)
        return ContinuousBatchingEngine(
            models["mimo"], prefix_cache=False, **dict(kw, max_len=96))
    if kind == "megakernel":
        return ContinuousBatchingEngine(models["llama"], megakernel="layer",
                                        **kw)
    return ContinuousBatchingEngine(models["llama"], **kw)


def force_resolve_first(eng):
    """The same engine in today's order: every step fetches and books its
    own tokens before the next program is chosen."""
    eng._resolve_first = lambda rows: "forced"
    return eng


def prompts(eng, seed, lens):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, eng.cfg.vocab_size, (n,)).astype(np.int64)
            for n in lens]


def steps(eng, n):
    for _ in range(n):
        eng.step()


def leak_free(eng):
    h = eng.health()
    return h["pages_free"] + h["prefix_pages"] == h["pages_total"]


# ---------------------------------------------------------- the scenarios --
def s_budget(eng):
    ps = prompts(eng, 1, [5, 11, 3, 9, 14])
    uids = [eng.add_request(p, b) for p, b in zip(ps, [6, 3, 1, 8, 5])]
    eng.drain()
    return [eng.result(u) for u in uids]


def _with_eos(eng, seed, pick):
    """Serve the prompts once to learn their streams, then again with
    each request's EOS set to the token `pick` chooses from its own."""
    ps = prompts(eng, seed, [6, 10, 4, 13])
    outs = eng.generate_many(ps, max_new_tokens=9)
    uids = [eng.add_request(p, 9, eos_token_id=int(pick(o[p.size:])))
            for p, o in zip(ps, outs)]
    eng.drain()
    res = [eng.result(u) for u in uids]
    assert any(r.size < o.size for r, o in zip(res, outs))
    return res


def s_eos_mid(eng):
    return _with_eos(eng, 2, lambda gen: gen[len(gen) // 2])


def s_eos_first(eng):
    return _with_eos(eng, 3, lambda gen: gen[0])


def s_chunks_join(eng):
    short = prompts(eng, 4, [4, 6, 5, 7])
    long_, = prompts(eng, 5, [21])           # three chunks of 8
    uids = [eng.add_request(p, 12) for p in short[:3]]
    steps(eng, 7)                            # every slot decodes
    uids.append(eng.add_request(long_, 5))
    uids.append(eng.add_request(short[3], 4))
    eng.drain()
    return [eng.result(u) for u in uids]


def s_cancel_in_flight(eng):
    ps = prompts(eng, 6, [5, 9, 7, 6])
    uids = [eng.add_request(p, 10) for p in ps[:3]]
    steps(eng, 6)
    assert eng.cancel(uids[1]) is True       # a program holds its row
    uids.append(eng.add_request(ps[3], 6))   # takes the freed seat
    eng.drain()
    assert eng.status(uids[1]) == "cancelled"
    return [eng.result(u) for u in uids if u != uids[1]]


def s_preempted(eng):
    ps = prompts(eng, 7, [6, 8, 5, 7])
    before = eng.preemptions
    uids = [eng.add_request(p, 10, tenant="lo") for p in ps[:3]]
    steps(eng, 6)
    uids.append(eng.add_request(ps[3], 4, tenant="hi"))
    eng.drain()
    assert eng.preemptions == before + 1
    return [eng.result(u) for u in uids]


def s_prefix_cow(eng):
    first, other = prompts(eng, 8, [16, 5])  # two full pages: the warm
    # copy diverges INSIDE the last shared page
    fork = np.concatenate([first[:8], other])
    before = eng.cow_copies
    out = [eng.generate_many([first], max_new_tokens=4)[0]]
    uids = [eng.add_request(first, 6), eng.add_request(fork, 5)]
    eng.drain()
    assert eng.cow_copies > before and eng.health()["prefix_hits"] > 0
    return out + [eng.result(u) for u in uids]


SCENARIOS = {"budget": s_budget, "eos_mid": s_eos_mid,
             "eos_first": s_eos_first, "chunks_join": s_chunks_join,
             "cancel_in_flight": s_cancel_in_flight,
             "preempted": s_preempted, "prefix_cow": s_prefix_cow}
# prefix sharing is refused for a description with several page groups
CASES = [(k, s) for k in KINDS for s in SCENARIOS
         if not (k == "experts" and s == "prefix_cow")]


@pytest.fixture(scope="module")
def served(models):
    """(kind, scenario) -> what both orders gave: a pair of engines a
    kind, built and warmed here (a fixture's set-up is not a test's
    time), every scenario served by both, in turn."""
    engines, cache = {}, {}
    for kind in KINDS:
        engines[kind] = (build(models, kind),
                         force_resolve_first(build(models, kind)))
        for eng in engines[kind]:
            eng.generate_many(prompts(eng, 0, [9, 3, 5]), max_new_tokens=3)

    def get(kind, scenario):
        if (kind, scenario) not in cache:
            res = []
            for eng in engines[kind]:
                was = dict(eng.health()["ahead"])
                outs = SCENARIOS[scenario](eng)
                now = eng.health()["ahead"]
                res.append({"outs": outs, "leak_free": leak_free(eng),
                            "dispatched": now["dispatched"]
                            - was["dispatched"],
                            "resolved_first": now["resolved_first"]})
            cache[kind, scenario] = res
        return cache[kind, scenario]
    return get


@pytest.mark.parametrize("kind,scenario", CASES)
def test_streams_equal_the_resolve_first_order(served, kind, scenario):
    ahead, first = served(kind, scenario)
    assert len(ahead["outs"]) == len(first["outs"])
    for i, (a, b) in enumerate(zip(ahead["outs"], first["outs"])):
        np.testing.assert_array_equal(a, b, err_msg=f"request {i}")
    # the one ran ahead and never had to wait for a token; the other
    # never did
    assert ahead["dispatched"] > 0 and first["dispatched"] == 0
    assert set(first["resolved_first"]) == {"forced"}
    assert set(ahead["resolved_first"]) <= {"preempt"}


@pytest.mark.parametrize("kind,scenario", CASES)
def test_no_page_leaks_after_drain(served, kind, scenario):
    ahead, first = served(kind, scenario)
    assert ahead["leak_free"] and first["leak_free"]


def test_generate_many_equals_one_at_a_time_generate(models):
    from paddle_tpu.inference import LLMEngine
    eng = build(models, "op_chain")
    ps = prompts(eng, 9, [7, 12, 4])
    ref = LLMEngine(models["llama"], max_len=64, page_size=8, max_batch=1)
    outs = eng.generate_many(ps, max_new_tokens=6)
    assert eng.health()["ahead"]["dispatched"] > 0
    for p, o in zip(ps, outs):
        np.testing.assert_array_equal(
            o, np.asarray(ref.generate(p[None, :], max_new_tokens=6))[0])


# --------------------------------------------- which calls resolve, which --
@pytest.fixture()
def in_flight(models):
    """An engine with two requests decoding and one program in flight."""
    eng = build(models, "op_chain")
    uids = [eng.add_request(p, 12) for p in prompts(eng, 10, [5, 7])]
    steps(eng, 6)
    assert isinstance(eng._pending, _Dispatched)
    return eng, uids


POLLS = {"status": lambda eng, uid: eng.status(uid),
         "headroom": lambda eng, uid: eng.headroom(),
         "pending": lambda eng, uid: eng.pending(),
         "len": lambda eng, uid: len(eng),
         "health": lambda eng, uid: eng.health(),
         "failures": lambda eng, uid: eng.failures()}


@pytest.mark.parametrize("call", POLLS)
def test_polling_never_resolves(in_flight, call):
    eng, uids = in_flight
    pending, emitted = eng._pending, len(eng._requests[uids[0]].out)
    POLLS[call](eng, uids[0])
    # the in-flight count stays 1 across a poll, and nothing was booked
    assert eng._pending is pending
    assert len(eng._requests[uids[0]].out) == emitted
    # step() still says True while a program is unresolved
    assert eng.step() is True


def _result_unfinished(eng, uid):
    from paddle_tpu.inference.scheduler import RequestNotFinishedError
    with pytest.raises(RequestNotFinishedError):
        eng.result(uid)


def _install_weights_busy(eng, uid):
    from paddle_tpu.inference.scheduler import EngineBusyError
    with pytest.raises(EngineBusyError):
        eng.install_weights(eng.export_weights())


RESOLVERS = {
    "drain": lambda eng, uid: eng.drain(),
    "result": _result_unfinished,
    "cancel": lambda eng, uid: eng.cancel(uid),
    "export_kv_pages": lambda eng, uid: eng.abort_handoff(
        eng.export_kv_pages(uid)["spec"]["uid"]),
    "export_request": lambda eng, uid: eng.export_request(uid),
    "export_inflight": lambda eng, uid: eng.export_inflight(),
    "install_weights": _install_weights_busy,
}


@pytest.mark.parametrize("call", RESOLVERS)
def test_calls_that_read_or_hand_over_state_resolve_first(in_flight, call):
    eng, uids = in_flight
    r = eng._requests[uids[0]]
    emitted = len(r.out)
    RESOLVERS[call](eng, uids[0])
    assert eng._pending is None
    assert len(r.out) > emitted or r.state != "decode"
    eng.drain()
    assert leak_free(eng)


def test_export_holds_the_token_that_was_in_flight(models):
    """The fold of an export continues byte-identically elsewhere only if
    it holds EVERY token emitted, the one in flight included."""
    eng = build(models, "op_chain")
    p, = prompts(eng, 12, [9])
    whole = eng.generate_many([p], max_new_tokens=10)[0]
    uid = eng.add_request(p, 10)
    steps(eng, 6)
    spec = eng.export_request(uid)
    eng.cancel(uid)
    other = build(models, "op_chain")
    uid2 = other.submit_resume(spec)
    other.drain()
    np.testing.assert_array_equal(other.result(uid2), whole)
    assert spec["generated"] + spec["max_new_tokens"] == 10


# -------------------------------------------- when the step resolves first --
def _armed(eng, p):
    with failsafe.inject("ckpt.commit", nth=10 ** 6):   # armed, never fires
        uid = eng.add_request(p, 6)
        eng.drain()
    return uid


WHY = {
    "proc": lambda eng, p: eng.add_request(
        p, 6, sampling=SamplingParams(repetition_penalty=1.3)),
    "stop": lambda eng, p: eng.add_request(
        p, 6, sampling=SamplingParams(stop=[[1, 2, 3]])),
    "sampled": lambda eng, p: eng.add_request(
        p, 6, sampling=SamplingParams(do_sample=True, temperature=0.8,
                                      top_k=4, seed=5)),
    "deadline": lambda eng, p: eng.add_request(p, 6, ttl_steps=1000),
    "faults": _armed,
}


@pytest.mark.parametrize("why", WHY)
def test_a_participant_the_host_must_see_makes_the_step_resolve_first(
        models, why):
    eng = build(models, "op_chain")
    p, q = prompts(eng, 13, [6, 9])
    plain = eng.generate_many([q], max_new_tokens=6)[0]
    was = eng.health()["ahead"]
    assert was["dispatched"] > 0 and not was["resolved_first"]
    uid = WHY[why](eng, p)
    eng.drain()
    now = eng.health()["ahead"]
    # every program that request rode was resolved before the next was
    # dispatched, and the counter says why
    assert set(now["resolved_first"]) == {why}
    assert now["resolved_first"][why] >= 6
    assert now["dispatched"] == was["dispatched"]
    assert eng.result(uid).size == p.size + 6
    # and the engine runs ahead again once it has left
    np.testing.assert_array_equal(
        eng.generate_many([q], max_new_tokens=6)[0], plain)
    assert eng.health()["ahead"]["dispatched"] > now["dispatched"]
    sample = profiler.counter_history("engine")[-1][1]
    assert sample[f"ahead.resolved_first.{why}"] \
        == sample["ahead.resolved_first"] == now["resolved_first"][why]
    assert sample["ahead.dispatched"] == eng.ahead_dispatched


@pytest.mark.parametrize("why", ["proc", "sampled"])
def test_a_greedy_row_goes_on_from_the_token_the_host_selected(models, why):
    """While a participant the host must see shares its batch, a greedy
    row's tokens are selected on the host; once that one has left, the
    row runs ahead again from the device's vector, into which the host's
    last token is merged first."""
    eng = build(models, "op_chain")
    p, q = prompts(eng, 17, [7, 5])
    alone = eng.generate_many([p], max_new_tokens=12)[0]
    was = eng.health()["ahead"]["dispatched"]
    uid = eng.add_request(p, 12)
    other = WHY[why](eng, q)                # (6 tokens: it leaves first)
    steps(eng, 4)                           # both decode, in one batch
    assert not eng._tok_on_dev[eng._requests[uid].slot]
    eng.drain()
    np.testing.assert_array_equal(eng.result(uid), alone)
    assert eng.result(other).size == q.size + 6
    now = eng.health()["ahead"]
    assert set(now["resolved_first"]) == {why}
    assert now["dispatched"] > was
    assert leak_free(eng)


def test_overrun_rows_counts_the_row_that_ran_past_its_eos(models):
    eng = build(models, "op_chain")
    p, q = prompts(eng, 14, [6, 8])
    gen = eng.generate_many([p], max_new_tokens=8)[0][p.size:]
    assert eng.health()["ahead"]["overrun_rows"] == 0   # budgets: none
    k = 3
    eos = int(gen[k])
    stop_at = int(np.argmax(gen == eos))    # its first occurrence
    uid = eng.add_request(p, 8, eos_token_id=eos)
    other = eng.add_request(q, 8)           # keeps the engine stepping
    eng.drain()
    np.testing.assert_array_equal(eng.result(uid)[p.size:],
                                  gen[:stop_at + 1])
    assert eng.result(other).size == q.size + 8
    # the EOS was in flight when the next step was dispatched: that row
    # ran once more, and its token was discarded
    assert eng.health()["ahead"]["overrun_rows"] == 1
    assert leak_free(eng)


def test_a_failure_while_resolving_aborts_in_flight_and_the_engine_serves_on(
        models):
    """A program that died on the device says so when its tokens are
    fetched, one step() after its dispatch: everything in flight fails
    typed (stage "engine"), the pools and the device token vector (which
    may be that program's result) are rebuilt, and the engine serves on."""
    eng = build(models, "op_chain")
    ps = prompts(eng, 16, [6, 9])
    ref = eng.generate_many(ps, max_new_tokens=6)
    uids = [eng.add_request(p, 6) for p in ps]
    steps(eng, 4)

    class Dead:
        def __array__(self, *a, **k):
            raise RuntimeError("the program died on the device")

    eng._pending.toks = Dead()
    assert np.asarray(eng._tok_dev).any()
    with pytest.raises(RuntimeError, match="died on the device"):
        eng.step()
    assert eng._pending is None
    assert not eng._tok_on_dev.any() and not np.asarray(eng._tok_dev).any()
    assert {eng.failures()[u].stage for u in uids} == {"engine"}
    assert leak_free(eng)
    for a, b in zip(eng.generate_many(ps, max_new_tokens=6), ref):
        np.testing.assert_array_equal(a, b)


# ------------------------------------------------------- the host buffers --
@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_host_arrays_handed_to_a_dispatch_are_not_aliased(models, program):
    """Nothing waits for a program before the engine's host rows change
    again, so what a dispatch is handed must be a copy: scribble over the
    engine's arrays right after each call and wait for the program with
    the scribble in place; the streams must not notice."""
    eng = build(models, "op_chain", slot_buckets=(3,))
    ps = prompts(eng, 15, [5, 19, 7])
    ref = eng.generate_many(ps, max_new_tokens=6)
    calls = []

    def scribbling(fn):
        def call(*args):
            out = fn(*args)
            host = (eng._tables_np, eng._lens_np, eng._tok_np)
            for a in args:
                if isinstance(a, jax.Array) and a.ndim and a.size < 4096:
                    assert not any(np.shares_memory(np.asarray(a), h)
                                   for h in host)
            saved = [h.copy() for h in host]
            for h in host:
                h[...] = 7
            jax.block_until_ready(out)
            for h, s in zip(host, saved):
                h[...] = s
            calls.append(program)
            return out
        return call

    if program == "decode":
        eng._cb_step_fns = {k: scribbling(f)
                            for k, f in eng._cb_step_fns.items()}
    else:
        eng._cb_prefill_fn = scribbling(eng._cb_prefill_fn)
    if eng._prefix is not None:
        eng._prefix.clear(eng.allocator)    # prefill every chunk again
    outs = eng.generate_many(ps, max_new_tokens=6)
    assert calls
    for a, b in zip(outs, ref):
        np.testing.assert_array_equal(a, b)
