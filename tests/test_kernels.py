"""Pallas kernel tests (interpret mode on CPU): flash + block-sparse vs the
XLA oracles, forward and backward, with and without pad masks.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dalle_pytorch_tpu.ops import attention as A
from dalle_pytorch_tpu.ops import sparse
from dalle_pytorch_tpu.ops.block_sparse import block_sparse_attention
from dalle_pytorch_tpu.ops.flash_attention import flash_attention


@pytest.fixture
def key():
    return jax.random.PRNGKey(0)


def _qkv(key, b=2, h=2, n=256, d=32):
    ks = jax.random.split(key, 3)
    return tuple(jax.random.normal(k, (b, h, n, d)) for k in ks)


def dense_oracle(q, k, v, scale, causal, mask):
    attn = A.dense_attention_weights(q, k, scale, mask, causal)
    return jnp.einsum("bhij,bhjd->bhid", attn, v)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_matches_dense(key, causal):
    q, k, v = _qkv(key)
    scale = 0.17
    out = flash_attention(q, k, v, scale=scale, causal=causal, block_q=64,
                          block_k=64)
    ref = dense_oracle(q, k, v, scale, causal, None)
    np.testing.assert_allclose(np.array(out), np.array(ref), atol=2e-5)


def test_flash_with_pad_mask_matches_dense_everywhere(key):
    """Exact agreement INCLUDING fully-padded rows (shared two-fill
    semantics)."""
    q, k, v = _qkv(key)
    mask = jnp.ones((2, 256), bool).at[:, 200:].set(False)
    out = flash_attention(q, k, v, scale=0.2, causal=True, mask=mask,
                          block_q=64, block_k=64)
    ref = dense_oracle(q, k, v, 0.2, True, mask)
    np.testing.assert_allclose(np.array(out), np.array(ref), atol=2e-5)


def test_flash_ragged_seq_blocks(key):
    """Sequence not a multiple of the q/k blocks still works (forward)."""
    q, k, v = _qkv(key, n=80)
    out = flash_attention(q, k, v, causal=True, block_q=64, block_k=64)
    ref = dense_oracle(q, k, v, q.shape[-1] ** -0.5, True, None)
    np.testing.assert_allclose(np.array(out), np.array(ref), atol=2e-5)


def test_flash_gradients_match_dense(key):
    q, k, v = _qkv(key, n=128)
    mask = jnp.ones((2, 128), bool).at[:, 100:].set(False)
    tgt = jax.random.normal(key, q.shape)

    def loss_flash(q, k, v):
        o = flash_attention(q, k, v, scale=0.2, causal=True, mask=mask,
                            block_q=64, block_k=64)
        return jnp.sum((o - tgt) ** 2)

    def loss_dense(q, k, v):
        return jnp.sum((dense_oracle(q, k, v, 0.2, True, mask) - tgt) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(np.array(a), np.array(b), atol=5e-4)


def test_flash_bf16_runs(key):
    q, k, v = (x.astype(jnp.bfloat16) for x in _qkv(key, n=128))
    out = flash_attention(q, k, v, causal=True)
    assert out.dtype == jnp.bfloat16
    assert np.isfinite(np.array(out, dtype=np.float32)).all()


@pytest.mark.parametrize("causal", [True, False])
def test_block_sparse_matches_oracle(key, causal):
    q, k, v = _qkv(key, n=256)
    scale = 0.2
    out = block_sparse_attention(q, k, v, scale=scale, causal=causal,
                                 block=16, block_q=64, block_k=64)
    ref = sparse.sparse_attention_ref(q, k, v, scale=scale, causal=causal,
                                     block=16)
    np.testing.assert_allclose(np.array(out), np.array(ref), atol=2e-5)


def test_block_sparse_key_mask_matches_oracle(key):
    q, k, v = _qkv(key, n=128)
    mask = jnp.ones((2, 128), bool).at[:, 112:].set(False)
    out = block_sparse_attention(q, k, v, scale=0.2, causal=True, mask=mask,
                                 block=16, block_q=64, block_k=64)
    ref = sparse.sparse_attention_ref(q, k, v, scale=0.2, causal=True,
                                     mask=mask, block=16)
    np.testing.assert_allclose(np.array(out), np.array(ref), atol=2e-5)


def test_block_sparse_gradients_match_oracle(key):
    q, k, v = _qkv(key, n=128)
    tgt = jax.random.normal(key, q.shape)

    def loss_pallas(q, k, v):
        o = block_sparse_attention(q, k, v, scale=0.2, causal=True,
                                   block=16, block_q=64, block_k=64)
        return jnp.sum((o - tgt) ** 2)

    def loss_ref(q, k, v):
        o = sparse.sparse_attention_ref(q, k, v, scale=0.2, causal=True,
                                        block=16)
        return jnp.sum((o - tgt) ** 2)

    gp = jax.grad(loss_pallas, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gp, gr):
        np.testing.assert_allclose(np.array(a), np.array(b), atol=5e-4)


def test_transformer_attn_impl_flash_matches_xla(key):
    from dalle_pytorch_tpu.ops.transformer import (TransformerConfig,
                                                   transformer_apply,
                                                   transformer_init)
    base = dict(dim=32, depth=2, seq_len=128, heads=2, dim_head=16)
    cfg_x = TransformerConfig(**base)
    cfg_f = TransformerConfig(**base, attn_impl="flash")
    params = transformer_init(key, cfg_x)
    x = jax.random.normal(key, (2, 128, 32))
    mask = jnp.ones((2, 128), bool).at[:, 100:].set(False)
    yx = transformer_apply(params, x, cfg=cfg_x, mask=mask)
    yf = transformer_apply(params, x, cfg=cfg_f, mask=mask)
    np.testing.assert_allclose(np.array(yx), np.array(yf), atol=1e-4)


def test_transformer_sparse_impl_pallas_matches_ref(key):
    from dalle_pytorch_tpu.ops.transformer import (TransformerConfig,
                                                   transformer_apply,
                                                   transformer_init)
    base = dict(dim=32, depth=2, seq_len=128, heads=2, dim_head=16,
                sparse_attn=True, sparse_block=16)
    cfg_r = TransformerConfig(**base)
    cfg_p = TransformerConfig(**base, sparse_impl="pallas")
    params = transformer_init(key, cfg_r)
    x = jax.random.normal(key, (2, 128, 32))
    yr = transformer_apply(params, x, cfg=cfg_r)
    yp = transformer_apply(params, x, cfg=cfg_p)
    np.testing.assert_allclose(np.array(yr), np.array(yp), atol=1e-4)


def test_flash_gradients_ragged_seq(key):
    """Backward at a sequence length NOT a multiple of the block (ADVICE r1:
    the bwd asserted n % block_k == 0 while the forward padded — e.g. DALLE
    text_seq_len=300 -> seq 1324). Grads must match dense exactly."""
    n = 200                                      # 200 % 128 != 0
    q, k, v = _qkv(key, n=n)
    mask = jnp.ones((2, n), bool).at[:, 180:].set(False)
    tgt = jax.random.normal(key, q.shape)

    def loss_flash(q, k, v):
        o = flash_attention(q, k, v, scale=0.2, causal=True, mask=mask,
                            block_q=128, block_k=128)
        return jnp.sum((o - tgt) ** 2)

    def loss_dense(q, k, v):
        return jnp.sum((dense_oracle(q, k, v, 0.2, True, mask) - tgt) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(np.array(a), np.array(b), atol=5e-4)


def test_static_tile_schedule_selection():
    """The schedule factorization itself (r5): exactly which layouts
    admit the python-unrolled tile list, and which fall back."""
    from dalle_pytorch_tpu.ops.block_sparse import _static_tile_schedule
    # the default VariableSparsity layout: diagonal + global tile 0
    assert _static_tile_schedule(128, 128, 16, 64, (0,), True) == [0]
    # multiple global blocks in distinct tiles
    assert _static_tile_schedule(128, 128, 16, 64, (0, 8), True) == [0, 1]
    # non-causal, mismatched tiles, window not dividing: all fall back
    assert _static_tile_schedule(128, 128, 16, 64, (0,), False) is None
    assert _static_tile_schedule(64, 128, 16, 64, (0,), True) is None
    assert _static_tile_schedule(96, 96, 16, 64, (0,), True) is None
    # a global block straddling a tile boundary falls back (window 16
    # divides the 64 tile, so this reaches the straddle check itself:
    # block 48, g=1 spans tokens 48..95 = tiles 0 and 1)
    assert _static_tile_schedule(64, 64, 48, 16, (1,), True) is None


def test_block_sparse_gradients_masked_static_schedule(key):
    """Grads through the STATIC-schedule backward (r5: diagonal piece +
    global strip instead of the key-tile scan) with a pad-key mask —
    n=256 with 128-tiles factors the layout, so this exercises
    _bs_bwd_static; parity vs the dense-masked oracle."""
    n = 256
    q, k, v = _qkv(key, n=n)
    mask = jnp.ones((2, n), bool).at[:, 230:].set(False)
    tgt = jax.random.normal(key, q.shape)

    def loss_pallas(q, k, v):
        o = block_sparse_attention(q, k, v, scale=0.2, causal=True,
                                   mask=mask, block=16, block_q=128,
                                   block_k=128)
        return jnp.sum((o - tgt) ** 2)

    def loss_ref(q, k, v):
        o = sparse.sparse_attention_ref(q, k, v, scale=0.2, causal=True,
                                        mask=mask, block=16)
        return jnp.sum((o - tgt) ** 2)

    gp = jax.grad(loss_pallas, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gp, gr):
        np.testing.assert_allclose(np.array(a), np.array(b), atol=5e-4)


def test_block_sparse_gradients_ragged_seq(key):
    """Same ragged-length regression for the block-sparse backward."""
    n = 160                                      # multiple of block=16 only
    q, k, v = _qkv(key, n=n)
    tgt = jax.random.normal(key, q.shape)

    def loss_pallas(q, k, v):
        o = block_sparse_attention(q, k, v, scale=0.2, causal=True,
                                   block=16, block_q=128, block_k=128)
        return jnp.sum((o - tgt) ** 2)

    def loss_ref(q, k, v):
        o = sparse.sparse_attention_ref(q, k, v, scale=0.2, causal=True,
                                        block=16)
        return jnp.sum((o - tgt) ** 2)

    gp = jax.grad(loss_pallas, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gp, gr):
        np.testing.assert_allclose(np.array(a), np.array(b), atol=5e-4)


def test_flash_gradients_ragged_no_mask_non_causal(key):
    """Ragged + no pad mask + non-causal: padded key columns must still be
    excluded from dq (structural bound added by the bwd itself)."""
    n = 72
    q, k, v = _qkv(key, n=n)
    tgt = jax.random.normal(key, q.shape)

    def loss_flash(q, k, v):
        o = flash_attention(q, k, v, scale=0.3, causal=False,
                            block_q=64, block_k=64)
        return jnp.sum((o - tgt) ** 2)

    def loss_dense(q, k, v):
        return jnp.sum((dense_oracle(q, k, v, 0.3, False, None) - tgt) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(np.array(a), np.array(b), atol=5e-4)


class TestBf16Operands:
    """The kernels keep MXU operands in the input dtype (bf16 at full
    systolic rate) with f32 accumulation; parity vs the f32 oracle must
    stay at bf16 rounding scale (~0.5%), not blow up."""

    def _qkv(self, b=2, h=2, n=256, d=64):
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q, k, v = (jax.random.normal(kk, (b, h, n, d), jnp.bfloat16)
                   for kk in ks)
        mask = jnp.ones((b, n), bool).at[1, 200:].set(False)
        return q, k, v, mask, d ** -0.5

    def test_flash_bf16_fwd_and_grad(self):
        from dalle_pytorch_tpu.ops.attention import dense_attention_weights
        from dalle_pytorch_tpu.ops.flash_attention import flash_attention
        q, k, v, mask, scale = self._qkv()
        o = flash_attention(q, k, v, scale=scale, causal=True, mask=mask)
        w = dense_attention_weights(q.astype(jnp.float32),
                                    k.astype(jnp.float32), scale, mask, True)
        ref = jnp.einsum("bhij,bhjd->bhid", w, v.astype(jnp.float32))
        rel = float(jnp.max(jnp.abs(o.astype(jnp.float32) - ref))
                    / jnp.max(jnp.abs(ref)))
        assert rel < 2e-2, rel

        def loss(fn):
            return lambda *a: (fn(*a).astype(jnp.float32) ** 2).sum()

        g = jax.grad(loss(lambda q, k, v: flash_attention(
            q, k, v, scale=scale, causal=True, mask=mask)),
            argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss(lambda q, k, v: jnp.einsum(
            "bhij,bhjd->bhid",
            dense_attention_weights(q, k, scale, mask, True), v)),
            argnums=(0, 1, 2))(q.astype(jnp.float32), k.astype(jnp.float32),
                               v.astype(jnp.float32))
        grel = max(float(jnp.max(jnp.abs(a.astype(jnp.float32) - b_))
                         / (float(jnp.max(jnp.abs(b_))) + 1e-9))
                   for a, b_ in zip(g, gr))
        assert grel < 3e-2, grel

    def test_block_sparse_bf16_fwd(self):
        from dalle_pytorch_tpu.ops.block_sparse import block_sparse_attention
        from dalle_pytorch_tpu.ops.sparse import sparse_attention_ref
        q, k, v, mask, scale = self._qkv()
        o = block_sparse_attention(q, k, v, scale=scale, causal=True,
                                   mask=mask)
        r = sparse_attention_ref(q.astype(jnp.float32),
                                 k.astype(jnp.float32),
                                 v.astype(jnp.float32), scale=scale,
                                 causal=True, mask=mask)
        rel = float(jnp.max(jnp.abs(o.astype(jnp.float32) - r))
                    / jnp.max(jnp.abs(r)))
        assert rel < 2e-2, rel


@pytest.mark.parametrize("causal", [True, False])
def test_windowed_sparse_matches_oracle(key, causal):
    q, k, v = _qkv(key, n=256)
    out = sparse.sparse_attention_windowed(q, k, v, scale=0.2, causal=causal,
                                           block=16)
    ref = sparse.sparse_attention_ref(q, k, v, scale=0.2, causal=causal,
                                      block=16)
    np.testing.assert_allclose(np.array(out), np.array(ref), atol=2e-5)


def test_windowed_sparse_ragged_and_mask_matches_oracle(key):
    """n not a multiple of the 64-token window (but a block multiple, as
    the transformer guarantees) + ragged pad-key mask."""
    q, k, v = _qkv(key, n=176)                       # 11 blocks, 2.75 windows
    mask = jnp.ones((2, 176), bool).at[0, 150:].set(False) \
                                   .at[1, 16:].set(False)
    out = sparse.sparse_attention_windowed(q, k, v, scale=0.2, causal=True,
                                           mask=mask, block=16)
    ref = sparse.sparse_attention_ref(q, k, v, scale=0.2, causal=True,
                                      mask=mask, block=16)
    np.testing.assert_allclose(np.array(out), np.array(ref), atol=2e-5)


def test_windowed_sparse_gradients_match_oracle(key):
    q, k, v = _qkv(key, n=128)
    tgt = jax.random.normal(key, q.shape)

    def loss_win(q, k, v):
        o = sparse.sparse_attention_windowed(q, k, v, scale=0.2, causal=True,
                                             block=16)
        return jnp.sum((o - tgt) ** 2)

    def loss_ref(q, k, v):
        o = sparse.sparse_attention_ref(q, k, v, scale=0.2, causal=True,
                                        block=16)
        return jnp.sum((o - tgt) ** 2)

    gw = jax.grad(loss_win, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gw, gr):
        np.testing.assert_allclose(np.array(a), np.array(b), atol=5e-4)


def test_windowed_sparse_multiple_global_blocks(key):
    q, k, v = _qkv(key, n=256)
    out = sparse.sparse_attention_windowed(q, k, v, scale=0.2, causal=True,
                                           block=16, global_blocks=(0, 5))
    ref = sparse.sparse_attention_ref(q, k, v, scale=0.2, causal=True,
                                      block=16, global_blocks=(0, 5))
    np.testing.assert_allclose(np.array(out), np.array(ref), atol=2e-5)


class TestPallasBackward:
    """flash_attention(bwd_impl='pallas') — the kernelized backward must
    match the XLA blockwise backward (itself oracle-verified above) on
    every masking combination, interpret mode."""

    def _grads(self, key, bwd_impl, *, causal=True, mask=None, n=256,
               dtype=jnp.float32):
        q, k, v = (x.astype(dtype) for x in _qkv(key, n=n))
        tgt = jax.random.normal(key, q.shape).astype(dtype)

        def loss(q, k, v):
            o = flash_attention(q, k, v, scale=0.2, causal=causal,
                                mask=mask, bwd_impl=bwd_impl)
            return jnp.sum((o.astype(jnp.float32) - tgt.astype(
                jnp.float32)) ** 2)

        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    @pytest.mark.parametrize("impl", ["pallas", "pallas_fused"])
    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_xla_bwd(self, key, causal, impl):
        gp = self._grads(key, impl, causal=causal)
        gx = self._grads(key, "xla", causal=causal)
        for a, b in zip(gp, gx):
            np.testing.assert_allclose(np.array(a), np.array(b), atol=2e-4)

    @pytest.mark.parametrize("impl", ["pallas", "pallas_fused"])
    def test_with_pad_mask(self, key, impl):
        mask = jnp.ones((2, 256), bool).at[0, 200:].set(False) \
                                       .at[1, 10:].set(False)
        gp = self._grads(key, impl, mask=mask)
        gx = self._grads(key, "xla", mask=mask)
        for a, b in zip(gp, gx):
            np.testing.assert_allclose(np.array(a), np.array(b), atol=2e-4)

    @pytest.mark.parametrize("impl", ["pallas", "pallas_fused"])
    def test_ragged_seq(self, key, impl):
        gp = self._grads(key, impl, n=192)   # pads to 256-tile inside
        gx = self._grads(key, "xla", n=192)
        for a, b in zip(gp, gx):
            np.testing.assert_allclose(np.array(a), np.array(b), atol=2e-4)

    @pytest.mark.parametrize("impl", ["pallas", "pallas_fused"])
    def test_bf16_finite(self, key, impl):
        gp = self._grads(key, impl, dtype=jnp.bfloat16)
        for g in gp:
            assert g.dtype == jnp.bfloat16
            assert np.isfinite(np.array(g, dtype=np.float32)).all()

    def test_rejects_unknown_impl(self, key):
        q, k, v = _qkv(key, n=64)
        with pytest.raises(ValueError):
            flash_attention(q, k, v, bwd_impl="cuda")


class TestKernelsUnderAMesh:
    """A Mosaic kernel cannot be auto-partitioned by GSPMD: under a jit
    whose operands are sharded (every multi-chip dp/tp train step) the
    kernels run inside a shard_map over batch and heads
    (ops.core.shard_over_batch_and_heads), the mesh observed from the
    operands' type. Interpreted here; the wrapping is the same code the
    chip compiles through."""

    @pytest.mark.parametrize("kernel", ["flash", "flash_pallas_bwd",
                                        "block_sparse"])
    def test_sharded_operands_match_unsharded(self, kernel):
        from jax.sharding import NamedSharding, PartitionSpec as P

        from dalle_pytorch_tpu.ops.block_sparse import \
            block_sparse_attention
        from dalle_pytorch_tpu.ops.flash_attention import flash_attention
        from dalle_pytorch_tpu.parallel import make_mesh
        mesh = make_mesh({"dp": 4, "tp": 2})
        b, h, n, d = 4, 2, 32, 8
        kq, kk, kv = jax.random.split(jax.random.PRNGKey(0), 3)
        q, k, v = (jax.random.normal(kx, (b, h, n, d)) for kx in
                   (kq, kk, kv))
        mask = jnp.arange(n)[None, :] < jnp.asarray([[n], [n // 2],
                                                     [n], [n - 3]])

        def fn(q, k, v, mask):
            if kernel == "block_sparse":
                return block_sparse_attention(q, k, v, mask=mask)
            return flash_attention(
                q, k, v, mask=mask,
                bwd_impl="pallas" if kernel == "flash_pallas_bwd"
                else "xla")

        def out_and_grads(q, k, v, mask):
            out, vjp = jax.vjp(lambda q, k, v: fn(q, k, v, mask), q, k, v)
            return out, vjp(out)

        want = out_and_grads(q, k, v, mask)
        qkv_s = NamedSharding(mesh, P("dp", "tp"))
        sharded = [jax.device_put(x, qkv_s) for x in (q, k, v)]
        mask_s = jax.device_put(mask, NamedSharding(mesh, P("dp")))
        got = jax.jit(out_and_grads)(*sharded, mask_s)
        # batch and heads stay where the operands put them
        assert got[0].sharding.is_equivalent_to(qkv_s, 4)
        for a, w_ in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(w_),
                                       rtol=1e-5, atol=1e-5)
