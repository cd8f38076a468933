"""TPU-dialect lowering of every Pallas kernel, run on CPU.

`jax.export(..., platforms=["tpu"])` traces each kernel and lowers it to
the Mosaic TPU dialect, so anything Pallas itself rejects for TPU —
unsupported primitives, bad block shapes or index maps, i64 leaking in
through the package-wide x64 — surfaces here without a chip. That is
all it proves. On the installed jax the export only SERIALISES the
Mosaic module (jax/_src/tpu_custom_call.py runs mosaic-serde and nothing
else); layout inference, tiling checks and VMEM allocation — Mosaic
proper — run inside libtpu when XLA compiles, i.e. only on the chip.
A kernel can pass here and still be refused there (PR 21: the decode
megakernel's dynamic row index into a bf16 scratch, the ragged kernel's
scoped-VMEM footprint at 7B width). benchmarks/kernel_sweep.py is the
on-chip check; this suite only keeps a refactor from losing even the
lowering."""
import functools

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax import export as jexport

import paddle_tpu  # noqa: F401  (config init)


def _lower_tpu(fn, *avals):
    """Export for TPU: traces every Pallas call and lowers it to the
    Mosaic dialect (no layout, tiling or VMEM checks — see above)."""
    return jexport.export(jax.jit(fn), platforms=["tpu"])(*avals)


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


class TestFlashAttentionLowering:
    @pytest.mark.parametrize("d,dtype", [
        (64, jnp.bfloat16),    # fallback [b*h, s, d] layout
        (128, jnp.bfloat16),   # transpose-free lane-blocked fast path
        (128, jnp.float32),    # f32 + d=128: VMEM geometry must shrink
    ])
    def test_fwd_bwd(self, d, dtype):
        from paddle_tpu.ops.pallas.flash_attention import \
            make_flash_attention
        flash = make_flash_attention()
        b, s, h = 2, 512, 4
        q = _sds((b, s, h, d), dtype)

        def fwd(q_, k_, v_):
            return flash(q_, k_, v_, True, 0.088)

        _lower_tpu(fwd, q, q, q)

        def bwd(q_, k_, v_):
            return jax.grad(lambda a, b_, c: jnp.sum(
                fwd(a, b_, c).astype(jnp.float32) ** 2),
                argnums=(0, 1, 2))(q_, k_, v_)

        _lower_tpu(bwd, q, q, q)

    @pytest.mark.parametrize("mask_shape", [
        (1, 1, 512, 512),   # shared
        (2, 1, 512, 512),   # per-batch
        (2, 4, 512, 512),   # per-head
    ])
    @pytest.mark.parametrize("d", [64, 128])
    def test_masked_fwd_bwd(self, mask_shape, d):
        from paddle_tpu.ops.pallas.flash_attention import \
            make_flash_attention
        flash = make_flash_attention()
        b, s, h = 2, 512, 4
        q = _sds((b, s, h, d), jnp.bfloat16)
        m = _sds(mask_shape, jnp.float32)

        def fwd(q_, k_, v_, m_):
            return flash.masked(q_, k_, v_, m_, False, 0.088)

        _lower_tpu(fwd, q, q, q, m)

        def bwd(q_, k_, v_, m_):
            return jax.grad(lambda a, b_, c: jnp.sum(
                fwd(a, b_, c, m_).astype(jnp.float32) ** 2),
                argnums=(0, 1, 2))(q_, k_, v_)

        _lower_tpu(bwd, q, q, q, m)

    @pytest.mark.parametrize("d", [64, 128])
    def test_native_dropout_fwd_bwd(self, d):
        """The native-dropout kernels were interpret-proven only (their
        hash path never ran under Mosaic before round 5)."""
        from paddle_tpu.ops.pallas.flash_attention import \
            make_flash_attention
        flash = make_flash_attention(dropout_p=0.1)
        b, s, h = 2, 512, 4
        q = _sds((b, s, h, d), jnp.bfloat16)
        seed = _sds((), jnp.int32)

        def fwd(q_, k_, v_, s_):
            return flash.dropout(q_, k_, v_, s_, True, 0.088)

        _lower_tpu(fwd, q, q, q, seed)

        def bwd(q_, k_, v_, s_):
            return jax.grad(lambda a, b_, c: jnp.sum(
                fwd(a, b_, c, s_).astype(jnp.float32) ** 2),
                argnums=(0, 1, 2))(q_, k_, v_)

        _lower_tpu(bwd, q, q, q, seed)

    def test_uneven_seq_and_gqa_expanded(self):
        from paddle_tpu.ops.pallas.flash_attention import \
            make_flash_attention
        flash = make_flash_attention()
        q = _sds((2, 300, 4, 128), jnp.bfloat16)  # pads to 512

        def fwd(q_, k_, v_):
            return flash(q_, k_, v_, True, 0.088)

        _lower_tpu(fwd, q, q, q)


    @pytest.mark.parametrize("blocks", [None, (512, 1024), (1024, 512)])
    def test_training_cells_shape(self, blocks):
        """Both training cells' call, (2, 4096, 16, 128) bf16 causal, at
        the geometry the rule draws there (PR 33: 1024 x 1024, one slice
        a step) and at two explicit ones with bq != bk."""
        from paddle_tpu.ops.pallas.flash_attention import (
            flash_geometry, make_flash_attention)
        q = _sds((2, 4096, 16, 128), jnp.bfloat16)
        if blocks is None:
            flash = make_flash_attention()
            assert flash_geometry(q.shape, q.dtype) == (1024, 1024, 1, 4096)
        else:
            flash = make_flash_attention(bq=blocks[0], bk=blocks[1])
            assert flash_geometry(q.shape, q.dtype, bq=blocks[0],
                                  bk=blocks[1]) == blocks + (2, 4096)

        def fwd(q_, k_, v_):
            return flash(q_, k_, v_, True, 0.088)

        _lower_tpu(fwd, q, q, q)
        _lower_tpu(lambda q_, k_, v_: jax.grad(lambda a, b_, c: jnp.sum(
            fwd(a, b_, c).astype(jnp.float32) ** 2),
            argnums=(0, 1, 2))(q_, k_, v_), q, q, q)


class TestOtherKernelsLowering:
    def test_rms_norm_fwd_bwd(self):
        from paddle_tpu.ops.pallas.rms_norm import make_rms_norm
        rms = make_rms_norm()
        x = _sds((512, 1024), jnp.float32)
        w = _sds((1024,), jnp.float32)

        _lower_tpu(lambda x_, w_: rms(x_, w_, 1e-6), x, w)
        _lower_tpu(
            lambda x_, w_: jax.grad(
                lambda a, b_: jnp.sum(rms(a, b_, 1e-6) ** 2),
                argnums=(0, 1))(x_, w_), x, w)

    @pytest.mark.parametrize(
        "b,cols,h,h_kv,d,dv,p,flat,window,sinks", [
            (4, 8, 8, 8, 128, 128, 16, False, None, False),
            # serve-parallel-window-ragdoc: 128Q / 8KV x 128, its full
            # layer and its window layers
            (48, 128, 128, 8, 128, 128, 128, False, None, False),
            (48, 128, 128, 8, 128, 128, 128, False, 4096, False),
            # serve-moe-window-mixedlen: 64Q x 192 / 128, flat keys
            (128, 32, 64, 4, 192, 128, 128, True, None, True),
            (128, 32, 64, 8, 192, 128, 128, True, 128, True),
            # pools whose pages Mosaic cannot slice out of HBM (a 64-wide
            # head, a lone bf16 KV head): the pipelined transport
            (8, 8, 16, 4, 64, 64, 128, False, None, False),
            (8, 8, 16, 1, 128, 128, 128, False, 256, True),
        ], ids=["small", "ragdoc_full", "ragdoc_window4096",
                "mixedlen_full_4kv", "mixedlen_window128_8kv",
                "blocks_head64", "blocks_one_kv_head"])
    def test_paged_attention_decode(self, b, cols, h, h_kv, d, dv, p, flat,
                                    window, sinks):
        """One grid step a slot, the pools in HBM, the kernel's own page
        copies and strided loads of a head's rows out of the page buffers
        (PR 37), at the geometries of the two cells that run this
        kernel; and the pipelined transport of the pools it cannot
        copy."""
        from paddle_tpu.ops.pallas.paged_attention import paged_attention
        n_pages = 64
        q = _sds((b, h, d), jnp.bfloat16)
        kp = _sds((n_pages, p, h_kv * d) if flat else (n_pages, p, h_kv, d),
                  jnp.bfloat16)
        vp = _sds((n_pages, p, h_kv, dv), jnp.bfloat16)
        table = _sds((b, cols), jnp.int32)
        lens = _sds((b,), jnp.int32)

        _lower_tpu(lambda q_, k_, v_, t_, l_, a_, s_: paged_attention(
            q_, k_, v_, t_, l_, active=a_, window=window,
            sinks=s_ if sinks else None, k_flat=flat),
            q, kp, vp, table, lens, lens, _sds((h,), jnp.float32))

    def test_quantized_matmul_int8(self):
        from paddle_tpu.ops.pallas.quantized_matmul import quantized_matmul
        x = _sds((256, 1024), jnp.bfloat16)
        w = _sds((1024, 1024), jnp.int8)
        s = _sds((1024,), jnp.float32)

        _lower_tpu(quantized_matmul, x, w, s)

    def test_quantized_matmul_int8_f32_activations(self):
        """float32 activations keep the f32 x f32 tile product (PR 25
        moved only bf16 activations to bf16 MXU operands)."""
        from paddle_tpu.ops.pallas.quantized_matmul import quantized_matmul
        x = _sds((256, 1024), jnp.float32)
        w = _sds((1024, 1024), jnp.int8)
        s = _sds((1024,), jnp.float32)

        _lower_tpu(quantized_matmul, x, w, s)

    def test_paged_attention_gqa_decode(self):
        """GQA-native cache (h_kv < h_q) must lower for TPU too."""
        from paddle_tpu.ops.pallas.paged_attention import paged_attention
        b, h, h_kv, d, p, n_pages, max_pages = 4, 32, 4, 128, 16, 32, 8
        q = _sds((b, h, d), jnp.bfloat16)
        pages = _sds((n_pages, p, h_kv, d), jnp.bfloat16)
        table = _sds((b, max_pages), jnp.int32)
        lens = _sds((b,), jnp.int32)
        _lower_tpu(paged_attention, q, pages, pages, table, lens)

    def test_ragged_and_spec_verify_attention(self):
        from paddle_tpu.ops.pallas.paged_attention import (
            ragged_paged_attention, spec_verify_attention)
        b, h, d, p, n_pages, max_pages = 4, 8, 128, 128, 32, 8
        pages = _sds((n_pages, p, h, d), jnp.bfloat16)
        table = _sds((b, max_pages), jnp.int32)
        lens = _sds((b,), jnp.int32)
        _lower_tpu(ragged_paged_attention,
                   _sds((b, 128, h, d), jnp.bfloat16), pages, pages, table,
                   lens, lens)
        _lower_tpu(spec_verify_attention,
                   _sds((b, 4, h, d), jnp.bfloat16), pages, pages, table,
                   lens)


class TestHybridBlockKernelsLowering:
    """The kernels the hybrid block adds or widens, at its published
    widths (64 query heads, key width 192, value width 128, window 128
    with a sink over 8 KV heads, full over 4; 16 held experts of width
    2048)."""

    @pytest.mark.parametrize("h_kv,window,sink", [(4, None, False),
                                                  (8, 128, True)])
    def test_decode_attention_window_sink_key_width(self, h_kv, window,
                                                    sink):
        from paddle_tpu.ops.pallas.paged_attention import paged_attention
        b, h, dk, dv, p, n_pages, mp = 8, 64, 192, 128, 128, 64, 32
        args = [_sds((b, h, dk), jnp.bfloat16),
                _sds((n_pages, p, h_kv, dk), jnp.bfloat16),
                _sds((n_pages, p, h_kv, dv), jnp.bfloat16),
                _sds((b, mp), jnp.int32), _sds((b,), jnp.int32)]
        if sink:
            _lower_tpu(lambda q, k, v, t, ln, s: paged_attention(
                q, k, v, t, ln, window=window, sinks=s), *args,
                _sds((h,), jnp.float32))
        else:
            _lower_tpu(lambda q, k, v, t, ln: paged_attention(
                q, k, v, t, ln, window=window), *args)
        # how the engine keeps a 192-wide key: a page [p, h_kv * 192]
        args[1] = _sds((n_pages, p, h_kv * dk), jnp.bfloat16)
        _lower_tpu(lambda q, k, v, t, ln: paged_attention(
            q, k, v, t, ln, window=window, k_flat=True), *args)

    def test_ragged_attention_window_sink_key_width(self):
        from paddle_tpu.ops.pallas.paged_attention import \
            ragged_paged_attention
        b, tq, h, h_kv, dk, dv, p, n_pages, mp = 4, 8, 64, 8, 192, 128, \
            128, 64, 32
        _lower_tpu(lambda q, k, v, t, c, st, s: ragged_paged_attention(
            q, k, v, t, c, st, window=128, sinks=s),
            _sds((b, tq, h, dk), jnp.bfloat16),
            _sds((n_pages, p, h_kv, dk), jnp.bfloat16),
            _sds((n_pages, p, h_kv, dv), jnp.bfloat16),
            _sds((b, mp), jnp.int32), _sds((b,), jnp.int32),
            _sds((b,), jnp.int32), _sds((h,), jnp.float32))

    @pytest.mark.parametrize("m,k,n", [(1024, 4096, 4096),     # gate | up
                                       (1024, 2048, 4096),     # down
                                       (4096, 4096, 4096)])    # a chunk
    def test_grouped_matmul(self, m, k, n):
        from paddle_tpu.ops.pallas.grouped_matmul import grouped_matmul
        _lower_tpu(grouped_matmul, _sds((m, k), jnp.bfloat16),
                   _sds((16, k, n), jnp.bfloat16), _sds((16,), jnp.int32))


class TestParallelBlockKernelsLowering:
    """The kernels of the parallel block's cell at its published widths:
    128 query heads over 8 KV heads x 128, a 4,096-token window beside a
    full layer, pages of 128, a 1,024-token chunk."""

    @pytest.mark.parametrize("window,sink", [(None, False), (4096, False),
                                             (4096, True)])
    def test_chunk_attention_through_the_page_table(self, window, sink):
        from paddle_tpu.ops.pallas.chunk_attention import (
            paged_chunk_attention, query_block)
        chunk, h, h_kv, d, p, n_pages, mp = 1024, 128, 8, 128, 128, 256, 128
        assert query_block(chunk, h) == 64
        args = [_sds((chunk, h, d), jnp.bfloat16),
                _sds((n_pages, p, h_kv, d), jnp.bfloat16),
                _sds((n_pages, p, h_kv, d), jnp.bfloat16),
                _sds((mp,), jnp.int32), _sds((), jnp.int32),
                _sds((), jnp.int32)]
        if sink:
            _lower_tpu(lambda q, k, v, t, a, b, s: paged_chunk_attention(
                q, k, v, t, a, b, window=window, sinks=s), *args,
                _sds((h,), jnp.float32))
        else:
            _lower_tpu(lambda q, k, v, t, a, b: paged_chunk_attention(
                q, k, v, t, a, b, window=window), *args)

    @pytest.mark.parametrize("window", [None, 4096])
    def test_decode_attention_sixteen_to_one(self, window):
        from paddle_tpu.ops.pallas.paged_attention import paged_attention
        b, h, h_kv, d, p, n_pages, mp = 48, 128, 8, 128, 128, 256, 128
        _lower_tpu(lambda q, k, v, t, ln: paged_attention(
            q, k, v, t, ln, window=window),
            _sds((b, h, d), jnp.bfloat16),
            _sds((n_pages, p, h_kv, d), jnp.bfloat16),
            _sds((n_pages, p, h_kv, d), jnp.bfloat16),
            _sds((b, mp), jnp.int32), _sds((b,), jnp.int32))


class TestLatentChunkKernelLowering:
    """The latent chunk kernel at the two layer geometries of the latent
    sparse cell (dots3-note-prev): a full layer of 128 heads over rows of
    640 (values the leading 512) masked by its selection, a window layer
    of 64 heads over rows of 1,152 (values 1,024) behind a window of 513;
    pages of 128, a chunk of 512, 144 table columns."""
    GEOMETRY = {"full": (128, 640, 512, None, 9216),
                "window": (64, 1152, 1024, 513, 388)}

    @pytest.mark.parametrize("kind", ["full", "window"])
    def test_chunk_attention_through_the_page_table(self, kind):
        from paddle_tpu.inference.latent import selection_width
        from paddle_tpu.ops.pallas.chunk_attention import (
            latent_plan, paged_latent_chunk_attention)
        h, row, rank, window, n_pages = self.GEOMETRY[kind]
        chunk, p, mp = 512, 128, 144
        width = None if window else selection_width(mp, p)
        plan = latent_plan(chunk, h, row, rank, p, jnp.bfloat16, window,
                           width)
        assert plan is not None and plan["tq"] >= 16
        args = [_sds((chunk, h, row), jnp.bfloat16),
                _sds((n_pages, p, row), jnp.bfloat16),
                _sds((mp,), jnp.int32), _sds((), jnp.int32),
                _sds((), jnp.int32)]
        if window is None:
            _lower_tpu(lambda q, r, t, a, b, c: paged_latent_chunk_attention(
                q, r, t, a, b, rank, 0.07, chosen=c), *args,
                _sds((chunk, width), jnp.bool_))
        else:
            _lower_tpu(lambda q, r, t, a, b: paged_latent_chunk_attention(
                q, r, t, a, b, rank, 0.06, window=window), *args)

    def test_health_names_the_kernel_at_the_cells_geometries(self):
        """`health()["latent_prefill"]` of the cell's engine, from its
        configuration file's layers and the engine's shapes (no weights):
        the kernel for both geometries, with its plan."""
        import types
        from paddle_tpu.inference import latent
        from paddle_tpu.inference.description import ModelDescription
        from paddle_tpu.inference.serving import PageGroup
        cfg = _perf_config("dots3-note-prev-ep16-serve")
        conf = _perf_reference(cfg["reference"]).model_config(cfg)
        desc = ModelDescription(
            hidden_size=conf.hidden_size, vocab_size=conf.vocab_size,
            eps=conf.rms_norm_eps,
            layers=tuple(conf.layer_spec(l)
                         for l in range(conf.num_hidden_layers)))
        e = cfg["serving"]["engine"]
        p, chunk = e["page_size"], e["prefill_chunk"]
        mp = e["max_len"] // p
        groups = [PageGroup(gi, key, [li for li, g in enumerate(
            desc.layer_group) if g == gi], p, e["max_batch"], mp, chunk,
            plain=False) for gi, key in enumerate(desc.groups)]
        eng = types.SimpleNamespace(
            desc=desc, groups=groups, page_size=p, pages_per_seq=mp,
            prefill_chunk=chunk, kv_dtype=jnp.bfloat16, interpret=False)
        facts = latent.prefill_facts(eng, latent.prefill_plans(eng))
        assert set(facts) == {"full", "window"}
        for kind, fact in facts.items():
            assert fact["kernel"] == "paged_latent_chunk_attention", kind
            assert chunk % fact["tq"] == 0 and fact["pages_per_step"] >= 1
            assert 16 << 20 <= fact["vmem_limit_bytes"] <= 100 << 20

    def test_the_kernel_sits_under_attend_and_its_scope(self):
        """Lowered for the chip from `latent.prefill_layer` itself (lane-
        filling widths, the engine steered off interpret): the kernel's
        custom call carries the `attend` phase and the `sparse_attend` /
        `window_latent_attend` scope in its name stack, which is what
        `prefill_attend_ms`, `latent_prefill_attn_share` and
        `latent_prefill_chunk_ms` read it by."""
        import re
        from paddle_tpu.inference import ContinuousBatchingEngine, latent
        from paddle_tpu.models import Dots3NoteConfig, Dots3NoteForCausalLM
        from harness import phase_times
        model = Dots3NoteForCausalLM(Dots3NoteConfig.tiny(
            num_hidden_layers=2, layers_kept=[0, 2], num_attention_heads=8,
            kv_lora_rank=128, swa_num_attention_heads=8,
            swa_kv_lora_rank=128))
        model.eval()
        eng = ContinuousBatchingEngine(model, max_len=128, page_size=16,
                                       max_batch=1, prefill_chunk=32,
                                       prefix_cache=False)
        eng.interpret = False
        shape = (lambda a: _sds(a.shape, a.dtype)
                 if hasattr(a, "shape") else a)
        W = jax.tree_util.tree_map(shape, eng.weights)
        for li, scope in ((0, "sparse_attend"), (1, "window_latent_attend")):
            def layer(W, h, rows, ix, tab, pos, t_end, li=li):
                return latent.prefill_layer(eng, W, W["layers"][li], h, rows,
                                            ix, tab, pos, t_end, li)[0]
            text = jax.jit(layer).trace(
                W, _sds((1, 32, 64), jnp.float32), shape(eng.k_pages[li]),
                shape(eng.v_pages[li]), _sds((8,), jnp.int32),
                _sds((32,), jnp.int32), _sds((), jnp.int32)).lower(
                    lowering_platforms=("tpu",)).as_text(debug_info=True)
            locs = dict(re.findall(r"^(#loc\d+) = loc\((.*)\)$", text,
                                   re.M))
            calls = re.findall(r"tpu_custom_call.*loc\((#loc\d+)\)\s*$",
                               text, re.M)
            assert len(calls) == 1, (li, len(calls))
            stack = re.match(r'"([^"]*)"', locs[calls[0]]).group(1)
            assert f"/{scope}/paged_latent_chunk_attention" in stack, stack
            assert phase_times.phase_of(stack) == "attend", stack


def _perf_config(name):
    import json
    import os
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "perf", "configs", f"{name}.json")) as f:
        return json.load(f)


def _perf_reference(name):
    import importlib.util
    import os
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if os.path.join(root, "perf") not in sys.path:
        sys.path.insert(0, os.path.join(root, "perf"))
    spec = importlib.util.spec_from_file_location(
        f"perf_reference_{name}",
        os.path.join(root, "perf", "references", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class TestDecodeMegakernelLowering:
    """decode_megakernel layer/multi x dense/int8 at a lane-aligned
    geometry (what megakernel_supported admits on a chip)."""
    H, NH, HD, FFN, V, P, MP, W = 512, 4, 128, 1024, 1024, 128, 4, 4

    def _layer(self, quant, dtype=jnp.bfloat16):
        from paddle_tpu.ops.pallas.decode_megakernel import \
            pack_decode_layer
        from paddle_tpu.ops.pallas.quantized_matmul import quantize_weights
        H, F = self.H, self.FFN

        def w(k, n):
            a = jnp.ones((k, n), jnp.float32)
            return quantize_weights(a) if quant else a
        ws = dict(ln1=jnp.ones((H,), jnp.float32),
                  ln2=jnp.ones((H,), jnp.float32), wq=w(H, H),
                  wk=w(H, H), wv=w(H, H), wo=w(H, H), wg=w(H, F),
                  wu=w(H, F), wd=w(F, H))
        return pack_decode_layer(ws, cdtype=dtype)

    def _args(self, stacked_layers=None, dtype=jnp.bfloat16):
        n_pages = self.W * self.MP
        pshape = (n_pages, self.P, self.NH, self.HD)
        if stacked_layers:
            pshape = (stacked_layers,) + pshape
        return (_sds((self.W, self.H), dtype),
                _sds(pshape, dtype),
                _sds((self.W, self.MP), jnp.int32),
                _sds((self.W,), jnp.int32),
                _sds((self.W, self.HD // 2), dtype))

    @pytest.mark.parametrize("quant", [False, True])
    def test_layer(self, quant):
        self._lower_layer(quant, jnp.bfloat16)

    def test_layer_int8_f32_activations(self):
        """The float32 engine's layer call: f32 x f32 tile products."""
        self._lower_layer(True, jnp.float32)

    def _lower_layer(self, quant, dtype):
        from paddle_tpu.ops.pallas.decode_megakernel import \
            decode_megakernel
        pack = self._layer(quant, dtype)
        h, pages, table, lens, rope = self._args(dtype=dtype)

        def f(h_, kp, vp, tbl, ln, cos, sin):
            return decode_megakernel(h_, pack, kp, vp, tbl, ln, None, cos,
                                     sin, nh=self.NH, nh_kv=self.NH,
                                     hd=self.HD, eps=1e-6)

        _lower_tpu(f, h, pages, pages, table, lens, rope, rope)

    @pytest.mark.parametrize("quant", [False, True])
    def test_multi_with_head(self, quant):
        from paddle_tpu.ops.pallas.decode_megakernel import (
            decode_megakernel, pack_lm_head, stack_packed)
        from paddle_tpu.ops.pallas.quantized_matmul import quantize_weights
        pack = stack_packed([self._layer(quant)] * 2)
        head = jnp.ones((self.H, self.V), jnp.float32)
        hpack = pack_lm_head(quantize_weights(head) if quant else head,
                             jnp.ones((self.H,), jnp.float32),
                             cdtype=jnp.bfloat16)
        h, pages, table, lens, rope = self._args(stacked_layers=2)

        def f(h_, kp, vp, tbl, ln, cos, sin):
            return decode_megakernel(h_, pack, kp, vp, tbl, ln, None, cos,
                                     sin, nh=self.NH, nh_kv=self.NH,
                                     hd=self.HD, eps=1e-6, head=hpack,
                                     head_v=self.V)

        _lower_tpu(f, h, pages, pages, table, lens, rope, rope)
