"""The serving engine's host spans (ISSUE 24): always on, on the clock of
ANY jax.profiler session, counted in profiler.span_totals().

Under a plain `jax.profiler.start_trace` (no paddle_tpu.profiler.Profiler)
the host plane must hold one `cb.step` per engine iteration with the
phases of docs/observability.md nested inside it, in order; with no
session at all the cumulative totals must grow by exactly the steps taken
and `setup.first_call` must fire once per step program built; and a
session must not change a token. Micro 1-layer geometry: spans are host
work around unchanged calls.
"""
import glob

import jax
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import profiler
from paddle_tpu.inference.scheduler import ContinuousBatchingEngine
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

ENGINE_KW = dict(max_len=64, page_size=8, max_batch=2, prefill_chunk=8)
# a 12-token prompt at chunk 8: a mid-prompt chunk, then the last chunk
PROMPT_LEN, NEW_TOKENS = 12, 4


@pytest.fixture(scope="module")
def tiny():
    paddle.seed(3)
    cfg = LlamaConfig.tiny(num_hidden_layers=1, hidden_size=32,
                           intermediate_size=64, num_attention_heads=2)
    return LlamaForCausalLM(cfg), cfg


def _prompt(cfg, seed=19):
    rng = np.random.RandomState(seed)
    return rng.randint(0, cfg.vocab_size, (PROMPT_LEN,)).astype(np.int64)


def _host_spans(trace_dir):
    """[(name, start_ns, end_ns, stats)] of the engine's spans on the
    profiler's host plane, sorted by start (outer before inner)."""
    from jax.profiler import ProfileData
    path, = glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb")
    spans = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend(
                    (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
                     dict(ev.stats))
                    for ev in line.events
                    if ev.name.startswith(("cb.", "setup.")))
    return sorted(spans, key=lambda sp: (sp[1], -sp[2]))


def _tree(spans):
    """[(name, stats, [children...])]: the spans as they nest."""
    roots, stack = [], []
    for name, s, e, stats in spans:
        while stack and s >= stack[-1][0]:
            stack.pop()
        node = (name, stats, [])
        (stack[-1][1] if stack else roots).append(node)
        stack.append((e, node[2]))
    return roots


def _names(nodes):
    return [(name, _names(kids)) if kids else name
            for name, _, kids in nodes]


@pytest.fixture(scope="module")
def traced(tiny, tmp_path_factory):
    """One request served under a PLAIN jax.profiler session by an engine
    whose programs are already built, and the same request served by the
    same engine with no session."""
    model, cfg = tiny
    # no prefix cache: the second serving of the prompt must prefill both
    # chunks again
    eng = ContinuousBatchingEngine(model, prefix_cache=False, **ENGINE_KW)
    eng.generate_many([_prompt(cfg, seed=5)], max_new_tokens=2)   # warm
    uid = eng.add_request(_prompt(cfg), max_new_tokens=NEW_TOKENS)
    eng.drain()
    plain = np.asarray(eng.result(uid))
    out = str(tmp_path_factory.mktemp("engine_spans"))
    uid = eng.add_request(_prompt(cfg), max_new_tokens=NEW_TOKENS)
    first = eng.steps
    jax.profiler.start_trace(out)
    try:
        while eng.step():
            pass
    finally:
        jax.profiler.stop_trace()
    steps = [n for n in _tree(_host_spans(out)) if n[0] == "cb.step"]
    return {"steps": steps, "first_step": first, "plain": plain,
            "traced": np.asarray(eng.result(uid))}


# the table of docs/observability.md, per kind of step. Since PR 31 a
# step dispatches its own program and THEN fetches and books the one the
# step before it dispatched, so the fetch of a step's tokens sits in the
# following `cb.step`
PHASES = {
    "prefill_mid_prompt": (0, [
        "cb.admit", "cb.prefill.prepare", "cb.prefill_chunk"]),
    "prefill_last_chunk": (1, [
        "cb.admit", "cb.prefill.prepare", "cb.prefill_chunk"]),
    "decode_after_last_chunk": (2, [
        "cb.admit", "cb.decode.prepare",
        ("cb.decode_step", ["cb.decode.dispatch"]),
        "cb.prefill.first_token"]),
    "decode": (3, [
        "cb.admit", "cb.decode.prepare",
        ("cb.decode_step", ["cb.decode.dispatch"]),
        "cb.decode.fetch", "cb.decode.push"]),
    "nothing_to_dispatch": (-2, [
        "cb.admit", "cb.decode.fetch", "cb.decode.push"]),
    "nothing_to_do": (-1, ["cb.admit"]),
}


@pytest.mark.parametrize("kind", PHASES)
def test_plain_jax_profiler_session_sees_the_phases_nested_in_order(
        traced, kind):
    index, want = PHASES[kind]
    name, stats, kids = traced["steps"][index]
    assert name == "cb.step"
    assert _names(kids) == want
    # the engine's step counter (programs dispatched so far) rides on
    # cb.step as annotation metadata
    n = len(traced["steps"])
    programs = 2 + (NEW_TOKENS - 1)
    assert stats["step"] == traced["first_step"] + min(index % n, programs)


def test_a_session_sees_every_step_and_changes_no_token(traced):
    # 2 prefill chunks, NEW_TOKENS - 1 decode steps (the first token comes
    # out of the last chunk), the step that has nothing to dispatch and
    # books the last token, and the step that finds nothing to do
    assert len(traced["steps"]) == 2 + (NEW_TOKENS - 1) + 1 + 1
    assert traced["plain"].size == PROMPT_LEN + NEW_TOKENS
    np.testing.assert_array_equal(traced["plain"], traced["traced"])


def _delta(before, name):
    after = profiler.span_totals().get(name, (0, 0.0))
    was = before.get(name, (0, 0.0))
    assert after[1] >= was[1]
    return after[0] - was[0]


@pytest.mark.parametrize("kw", [{}, {"decode_block": 4}],
                         ids=["per_step", "fused_blocks"])
def test_totals_count_steps_and_first_calls_with_no_session(tiny, kw):
    model, cfg = tiny
    before = profiler.span_totals()
    eng = ContinuousBatchingEngine(model, **dict(ENGINE_KW, **kw))
    eng.add_request(_prompt(cfg), max_new_tokens=NEW_TOKENS)
    calls = 1
    while eng.step():
        calls += 1
    assert _delta(before, "setup.engine") == 1
    assert _delta(before, "setup.engine.weights") == 1
    assert _delta(before, "setup.engine.kv_pool") == 1
    assert _delta(before, "cb.step") == calls
    built = (len(eng._cb_step_fns) + (eng._cb_prefill_fn is not None)
             + len(eng._cb_fused_fns))
    assert built >= 2
    assert _delta(before, "setup.first_call") == built
    if kw:
        assert _delta(before, "cb.block") \
            + _delta(before, "cb.block_chain") == eng.fused_blocks > 0
        return
    assert _delta(before, "cb.admit") == calls
    assert _delta(before, "cb.prefill_chunk") == eng.prefill_steps == 2
    assert _delta(before, "cb.prefill.first_token") == 1
    for name in ("cb.decode.prepare", "cb.decode_step",
                 "cb.decode.dispatch", "cb.decode.fetch",
                 "cb.decode.push"):
        assert _delta(before, name) == eng.decode_steps == NEW_TOKENS - 1


def test_record_event_is_a_decorator_with_a_span_per_call():
    @profiler.RecordEvent("spans_test.decorated")
    def twice(x):
        return 2 * x

    before = profiler.span_totals()
    assert twice(twice(3)) == 12
    assert _delta(before, "spans_test.decorated") == 2
