"""Every operation of a step program sits under a model phase
(`paddle_tpu.profiler.PHASES`; docs/observability.md "Device phases").

The programs of the four tiny serving configurations and the two tiny
training configurations under perf/configs/ are LOWERED, never compiled
or run: `lower(...).as_text(debug_info=True)` holds each operation's name
stack in its location. jax lowers a jitted helper, a checkpointed body
and a scan's closed call into private functions whose operations carry a
stack RELATIVE to the call, so an operation's whole stack is its callers'
(every call site's) followed by its own; the phase and the pass are then
read by the rules the trace's reader uses (perf/harness/phase_times.py).

This is the guard that keeps the next model family's op chain from
arriving unphased: a new op chain goes under a phase or this fails.
"""
import collections
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PERF = os.path.join(ROOT, "perf")
if PERF not in sys.path:
    sys.path.insert(0, PERF)

from harness import manifest, phase_times  # noqa: E402
from paddle_tpu.profiler import PHASES, phase  # noqa: E402

SERVING = ("tiny-serve", "tiny-mimo-serve", "tiny-dots3-serve",
           "tiny-keye-serve", "tiny-cohere2-moe-serve")
TRAINING = ("tiny-train", "tiny-train-hybrid")
# the operations that carry a program's time: none may be unphased
HEAVY = re.compile(r"dot_general|scatter|gather|sort|top_k|custom_call")
MIN_PHASED = 0.97
# a `lax.scan` over the layers slices the stacked tensors and stacks the
# per-layer gradients itself, outside the body any scope can reach: those
# operations, directly in the loop and of these kinds, belong to no phase
# and are left out of the count (on the chip they were 1.5 % of the dense
# training step: PERF.md 5)
SCAN_OWN = re.compile(r"(^|/)while(/(body|cond))?/[\w-]+:?$")
SCAN_KINDS = {"dynamic_slice", "dynamic_update_slice", "reshape",
              "broadcast_in_dim", "add", "subtract", "compare", "convert",
              "select", "while", "call", "multiply"}

_LOCDEF = re.compile(r"^#loc(\d*) = loc\((.*)\)$", re.M)
_OP = re.compile(r'^\s*(?:%[\w:#, ]+ = )?"?'
                 r'((?:stablehlo|chlo|func|mhlo)\.[\w.]+|call)"?[ (]')
_FUNC = re.compile(r"^\s*func\.func (?:public|private) @([\w.]+)\(")
_REF = re.compile(r"loc\((#loc\d*)\)\s*$")
_CALLEE = re.compile(r"call @([\w.]+)\(")
_NO_WORK = {"stablehlo.return", "func.return", "stablehlo.constant"}


def operations(text):
    """[(function, operation, its own name stack, callee or None)] of a
    lowered module's text."""
    locs = {"#loc" + m.group(1): m.group(2) for m in _LOCDEF.finditer(text)}

    def stack(ref):
        d = locs.get(ref, "")
        m = re.match(r'"([^"]*)"', d)
        if m:
            return m.group(1)
        m = re.match(r"callsite\((#loc\d+) at", d)
        return stack(m.group(1)) if m else ""

    ops, func, open_ops = [], None, []
    for line in text.split("\n"):
        f = _FUNC.match(line)
        if f:
            func = f.group(1)
            continue
        ref, m = _REF.search(line), _OP.match(line)
        if m and m.group(1) not in _NO_WORK:
            callee = _CALLEE.search(line)
            ops.append([func, m.group(1), stack(ref.group(1)) if ref else "",
                        callee.group(1) if callee else None])
            if not ref:         # an operation with regions: its location
                open_ops.append(ops[-1])            # follows their end
        elif ref and open_ops and line.lstrip().startswith("}"):
            open_ops.pop()[2] = stack(ref.group(1))
    return ops


def placed(text):
    """[(operation, {(phase, pass)} over every way it is reached, its own
    stack)]."""
    ops = operations(text)
    sites = collections.defaultdict(list)
    for func, _, own, callee in ops:
        if callee:
            sites[callee].append((func, own))
    memo = {}

    def prefixes(func, seen=()):
        if func not in memo:
            if func == "main" or func in seen or func not in sites:
                return {""}
            memo[func] = {p + "/" + own for caller, own in sites[func]
                          for p in prefixes(caller, seen + (func,))}
        return memo[func]

    out = []
    for func, kind, own, _ in ops:
        whole = {p + "/" + own for p in prefixes(func)}
        out.append((kind, {(phase_times.phase_of(s), phase_times.pass_of(s))
                           for s in whole}, own))
    return out


def _shapes(tree):
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)
        if hasattr(a, "shape") else a, tree)


@pytest.fixture(scope="module")
def engines():
    cache = {}

    def build(name):
        if name not in cache:
            from paddle_tpu.inference import ContinuousBatchingEngine
            cfg = manifest.load_json(f"perf/configs/{name}.json")
            family = manifest.load_plugin("references", cfg["reference"])
            runner = manifest.load_plugin("systems", cfg["system"])
            cache[name] = ContinuousBatchingEngine(
                family.build_model(cfg, 0), **runner.engine_kwargs(cfg))
        return cache[name]

    return build


def _lower_serving(eng, program):
    W, kp, vp = (_shapes(t) for t in (eng.weights, eng.k_pages,
                                      eng.v_pages))

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32)

    mp = eng.max_pages_per_seq
    if program == "prefill":
        fn = eng._build_cb_prefill(eng.prefill_chunk)
        low = fn.lower(W, i32(1, eng.prefill_chunk), kp, vp, i32(1, mp),
                       i32(), i32())
    else:
        w = eng._slot_buckets[-1]
        fn = eng._build_cb_step(w)
        low = fn.lower(W, i32(eng.max_batch), kp, vp, i32(w, mp), i32(w),
                       jax.ShapeDtypeStruct((w,), jnp.bool_))
    return low.as_text(debug_info=True)


def _lower_training(name):
    from jax.sharding import NamedSharding, PartitionSpec as P
    from paddle_tpu.distributed.mesh import build_mesh, set_global_mesh
    from paddle_tpu.models.train_step import SpmdTrainer
    cfg = manifest.load_json(f"perf/configs/{name}.json")
    family = manifest.load_plugin("references", cfg["reference"])
    degrees = cfg["training"]["mesh"]
    mesh = build_mesh(degrees, devices=jax.devices()[
        :int(np.prod(list(degrees.values())))])
    set_global_mesh(mesh)
    trainer = SpmdTrainer(family.build_model(cfg, 0), mesh,
                          **cfg["training"]["trainer"])
    shape = (2, 32)
    batch = P(tuple(a for a in ("data", "sharding")
                    if mesh.shape[a] > 1) or None)
    ids = jax.ShapeDtypeStruct(shape, jnp.int32,
                               sharding=NamedSharding(mesh, batch))
    rep = NamedSharding(mesh, P())
    key = jax.ShapeDtypeStruct((), jax.random.key(0).dtype, sharding=rep)
    lr = jax.ShapeDtypeStruct((), jnp.float32, sharding=rep)
    return trainer._build(shape).lower(
        trainer.abstract_state(), ids, ids, key, lr).as_text(
            debug_info=True)


def _hold(text, expected):
    """The rules every program is held to; returns the (phase, pass)
    pairs found."""
    ops = placed(text)
    assert len(ops) > 100, "the lowered text was not parsed"
    unphased = [(kind, own) for kind, pp, own in ops
                if any(ph == phase_times.UNPHASED for ph, _ in pp)]
    heavy = collections.Counter(k for k, _ in unphased if HEAVY.search(k))
    assert not heavy, f"outside every phase: {dict(heavy)}"
    outside = [k for k, own in unphased
               if not (SCAN_OWN.search(own)
                       and k.split(".")[-1] in SCAN_KINDS)]
    share = 1 - len(outside) / len(ops)
    assert share >= MIN_PHASED, (
        f"{share:.3f} of {len(ops)} operations sit under a phase; "
        f"outside: {collections.Counter(outside)}")
    found = {pair for _, pp, _ in ops for pair in pp}
    missing = set(expected) - {ph for ph, _ in found}
    assert not missing, f"no operation under {sorted(missing)}"
    return found


@pytest.mark.parametrize("program", ["step", "prefill"])
@pytest.mark.parametrize("name", SERVING)
def test_serving_program_is_phased(engines, name, program):
    found = _hold(_lower_serving(engines(name), program),
                  ("embed", "attn_proj", "kv_write", "attend", "ffn",
                   "head"))
    assert {pa for _, pa in found} == {"forward"}


@pytest.mark.parametrize("name", TRAINING)
def test_training_program_is_phased_in_every_pass(name):
    found = _hold(_lower_training(name),
                  ("embed", "attn_proj", "attend", "ffn", "loss",
                   "grad_sync", "optimizer"))
    for ph in ("attn_proj", "attend", "ffn"):
        passes = {pa for p, pa in found if p == ph}
        assert passes == {"forward", "recompute", "backward"}, (ph, passes)
    assert {pa for p, pa in found if p == "optimizer"} == {"forward"}, \
        "the update is neither recomputed nor a transpose"


def test_the_vocabulary_is_closed():
    with phase("attend"):
        pass
    with pytest.raises(ValueError, match="nonsense"):
        phase("nonsense")
    assert len(set(PHASES)) == len(PHASES) == 9
    assert phase_times.PHASES is PHASES      # one source
