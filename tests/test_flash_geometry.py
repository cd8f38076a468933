"""Flash attention's block geometry (PR 33): the rule that draws
(bq, bk, nb) from a call's shape, that the wrapper runs what the rule
reports whatever the mask's kind, the kernels at the blocks the rule draws
at training length, and the validity mask of a block. CPU, interpret
mode."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops.pallas import flash_attention as fa


# (q shape [b, s, h, d], dtype, mask shape, dropout) -> what the rule gives
RULE = {
    # both training cells: 2 sequences of 4,096, 16 heads a chip, d 128
    "train_cells": ((2, 4096, 16, 128), jnp.bfloat16, None, False),
    "train_cells_f32": ((2, 4096, 16, 128), jnp.float32, None, False),
    "batched_mask": ((2, 4096, 16, 128), jnp.bfloat16, (2, 1, 4096, 4096),
                     False),
    "shared_mask": ((2, 4096, 16, 128), jnp.bfloat16, (1, 1, 4096, 4096),
                    False),
    "per_head_mask_f32": ((2, 4096, 16, 128), jnp.float32,
                          (2, 16, 4096, 4096), False),
    "dropout": ((2, 4096, 16, 128), jnp.bfloat16, None, True),
    "masked_dropout_f32": ((2, 2048, 16, 128), jnp.float32,
                           (2, 1, 2048, 2048), True),
    # the fallback layout: heads fold into the batch, nb up to 8
    "d64": ((8, 2048, 16, 64), jnp.bfloat16, None, False),
    "d64_batched_mask": ((8, 2048, 16, 64), jnp.bfloat16,
                         (8, 1, 2048, 2048), False),
    "big_batch": ((32, 1024, 16, 128), jnp.bfloat16, None, False),
    "prefill_2048": ((1, 2048, 32, 128), jnp.bfloat16, None, False),
    # a larger block must not inflate a sequence: 1,100 pads to 1,280
    "uneven_1100": ((1, 1100, 32, 128), jnp.bfloat16, None, False),
    "s_256": ((4, 256, 8, 128), jnp.bfloat16, None, False),
    "s_100": ((4, 100, 8, 64), jnp.float32, None, False),
}


@pytest.mark.parametrize("case", RULE)
def test_rule_fits_the_call(case):
    shape, dtype, mask_shape, dropout = RULE[case]
    b, s, h, d = shape
    bq, bk, nb, s_pad = fa.flash_geometry(shape, dtype, mask_shape,
                                          dropout=dropout)
    # the sequence pads to the unit whatever block is drawn
    assert s_pad == -(-s // fa.BLOCK_UNIT) * fa.BLOCK_UNIT
    assert s_pad % bq == 0 and s_pad % bk == 0
    assert bq <= fa.BLOCK_TARGET[0] and bk <= fa.BLOCK_TARGET[1]
    fast = d % 128 == 0
    B = b if fast else b * h
    assert B % nb == 0
    batched = mask_shape is not None and fa._mask_group(
        fa._mask_rows(mask_shape, b, h, fast), B, h if fast else 1) == 1
    assert fa._step_vmem_bytes(nb, bq, bk, d, jnp.dtype(dtype).itemsize,
                               mask_shape is not None, batched,
                               dropout) <= fa.VMEM_BUDGET
    if s <= fa.BLOCK_UNIT:      # short sequences keep the old blocks
        assert (bq, bk, s_pad) == (256, 256, 256)


def test_rule_at_the_training_shape_and_explicit_blocks():
    # what the sweep on the chip chose (PERF.md section 6, PR 33); two
    # slices a step at these blocks exceed the VMEM budget, and the
    # compiler's limit (the backward needs 20.1 MiB of 16)
    assert fa.flash_geometry((2, 4096, 16, 128), jnp.bfloat16) \
        == (1024, 1024, 1, 4096)
    # explicit blocks are taken as given; the sequence pads to the larger
    assert fa.flash_geometry((2, 1100, 4, 128), jnp.bfloat16,
                             bq=512, bk=1024) == (512, 1024, 2, 2048)
    assert fa.flash_geometry((2, 300, 4, 128), jnp.bfloat16,
                             bq=128, bk=64) == (128, 64, 2, 384)
    # an explicit block larger than the sequence is cut to its padding
    assert fa.flash_geometry((1, 40, 2, 64), jnp.float32,
                             bq=64, bk=64) == (64, 64, 2, 64)


def _qkv(b, s, h, d, seed=0, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return tuple(jax.random.normal(k, (b, s, h, d), dtype) for k in ks)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("bq,bk", [(512, 512), (512, 1024), (1024, 512)])
def test_large_blocks_match_reference(bq, bk, causal):
    """s = 1,100 pads to the larger block's multiple: padding ends inside
    a key block, and with bq > bk one key block is padding altogether. bf16 operands (float32
    ones would have the fitter cut these blocks), against the float32
    reference on the same rounded inputs."""
    b, s, h, d = 1, 1100, 2, 128
    scale = d ** -0.5
    q, k, v = _qkv(b, s, h, d, dtype=jnp.bfloat16)
    flash = fa.make_flash_attention(bq=bq, bk=bk, interpret=True)
    assert fa.flash_geometry(q.shape, q.dtype, bq=bq, bk=bk) \
        == (bq, bk, 1, 1536 if bq == bk else 2048)
    w = jnp.cos(jnp.arange(d, dtype=jnp.float32))

    def loss(fn, *a):
        return jnp.sum(fn(*a, causal, scale).astype(jnp.float32) * w)

    out, grads = jax.jit(lambda *a: (
        flash(*a, causal, scale),
        jax.grad(lambda *x: loss(flash, *x), argnums=(0, 1, 2))(*a)))(
            q, k, v)
    q32, k32, v32 = (x.astype(jnp.float32) for x in (q, k, v))
    ref = fa._xla_ref(q32, k32, v32, causal, scale)
    ref_grads = jax.grad(lambda *x: loss(fa._xla_ref, *x),
                         argnums=(0, 1, 2))(q32, k32, v32)
    np.testing.assert_allclose(np.asarray(out, np.float32), ref,
                               rtol=2e-2, atol=2e-2)
    for g, r in zip(grads, ref_grads):
        np.testing.assert_allclose(np.asarray(g, np.float32), r,
                                   rtol=2e-2, atol=2e-2)


# what `_prep` makes of a mask decides nb; `flash_geometry` says the same
# from the shapes alone: (q shape, mask shape, dropout, blocks) -> expected
MASKED = {
    "none": ((2, 300, 4, 128), None, 0.0, None, (512, 512, 2, 512)),
    "shared": ((2, 300, 4, 128), (1, 1, 300, 300), 0.0, None,
               (512, 512, 2, 512)),
    "batched": ((2, 300, 4, 128), (2, 1, 300, 300), 0.0, None,
                (512, 512, 2, 512)),
    "per_head": ((2, 300, 4, 128), (2, 4, 300, 300), 0.0, None,
                 (512, 512, 2, 512)),
    "key_padding": ((2, 300, 4, 128), (2, 1, 1, 300), 0.0, None,
                    (512, 512, 2, 512)),
    # dropout's two tiles more leave the budget one slice a step
    "batched_dropout": ((2, 300, 4, 128), (2, 1, 300, 300), 0.25, None,
                        (512, 512, 1, 512)),
    # the fallback layout folds 4 heads into the batch: 8 slices, and at
    # small blocks only the mask's kind limits how many share a step
    "d64_none": ((2, 300, 4, 64), None, 0.0, (128, 128), (128, 128, 8, 384)),
    "d64_shared": ((2, 300, 4, 64), (1, 1, 300, 300), 0.0, (128, 128),
                   (128, 128, 8, 384)),
    "d64_batched": ((2, 300, 4, 64), (2, 1, 300, 300), 0.0, (128, 128),
                    (128, 128, 4, 384)),    # a mask a sequence: its 4 heads
    "d64_per_head": ((2, 300, 4, 64), (1, 4, 300, 300), 0.0, (128, 128),
                     (128, 128, 8, 384)),   # a mask row a slice
    "d64_drawn": ((2, 300, 4, 64), (2, 1, 300, 300), 0.0, None,
                  (512, 512, 2, 512)),
}


@pytest.mark.parametrize("case", MASKED)
def test_wrapper_runs_the_geometry_the_rule_reports(monkeypatch, case):
    shape, mask_shape, dropout, blocks, expected = MASKED[case]
    bq, bk = blocks or (None, None)
    ran = []

    def spy(kernel, n_lead):
        def call(*a, **kw):     # ..., bq, bk, nb, s_true, interpret, ...
            ran.append(tuple(a[n_lead:n_lead + 3]) + (a[0].shape[1],))
            return kernel(*a, **kw)
        return call
    monkeypatch.setattr(fa, "_flash_fwd", spy(fa._flash_fwd, 7))
    monkeypatch.setattr(fa, "_flash_bwd", spy(fa._flash_bwd, 10))
    flash = fa.make_flash_attention(bq=bq, bk=bk, interpret=True,
                                    dropout_p=dropout)
    q, k, v = _qkv(*shape, dtype=jnp.bfloat16)
    rest = []
    if mask_shape is not None:
        rest.append(jnp.zeros(mask_shape, jnp.float32))
    if dropout:
        rest.append(jnp.int32(7))
    entry = {(False, False): flash, (True, False): flash.masked,
             (False, True): getattr(flash, "dropout", None),
             (True, True): getattr(flash, "masked_dropout", None)}[
                 mask_shape is not None, bool(dropout)]
    jax.grad(lambda q_: jnp.sum(entry(q_, k, v, *rest, True, 0.1)
                                .astype(jnp.float32)))(q)
    want = fa.flash_geometry(shape, jnp.bfloat16, mask_shape, bq=bq, bk=bk,
                             dropout=bool(dropout))
    assert ran == [want, want]      # the forward's and the backward's
    assert want == expected


@pytest.mark.parametrize("bq,bk,nk,s_true,causal", [
    (256, 256, 4, 1024, True),
    (512, 256, 4, 1024, True),      # bq != bk
    (256, 512, 2, 1024, True),
    (256, 256, 4, 1024, False),     # nothing to mask: no mask is built
    (256, 256, 4, 1000, False),     # padding inside the last key block
    (256, 256, 4, 1000, True),
    (512, 256, 4, 700, True),       # bq > bk: one key block all padding
])
def test_block_valid_by_position(bq, bk, nk, s_true, causal):
    """The compare against the true length is built only where the padded
    length leaves padding, the causal one only under `causal`; a call
    with neither builds no mask at all."""
    s_pad = nk * bk
    for qi in range(s_pad // bq):
        for ki in range(nk):
            got = fa._block_valid(bq=bq, bk=bk, nk=nk, s_true=s_true,
                                  q_start=qi * bq, k_start=ki * bk,
                                  causal=causal)
            rows = np.arange(qi * bq, (qi + 1) * bq)[:, None]
            cols = np.arange(ki * bk, (ki + 1) * bk)[None, :]
            want = (cols < s_true) & ((rows >= cols) | (not causal))
            if not causal and s_pad == s_true:
                assert got is None and want.all()
            else:
                np.testing.assert_array_equal(np.asarray(got), want)
