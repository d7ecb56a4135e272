"""DALLE tests: vocab/mask contracts, loss construction, KV-cache parity.

Behavioral contracts from SURVEY.md §5: logit space [text | image | EOS],
mask row i governs the token predicted there (token i+1), tied codebook,
labels = [text, image+offset] shifted with EOS appended, top-k keeps the top
(1-thres) fraction. The cache tests prove the jit decode engine reproduces
the full re-forward logits exactly (teacher-forced replay) for sequential,
reversible, and sparse stacks.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dalle_pytorch_tpu.models import dalle as D
from dalle_pytorch_tpu.models import vae as V
from dalle_pytorch_tpu.ops import decode as decode_ops

VCFG = V.VAEConfig(image_size=32, num_tokens=48, codebook_dim=32,
                   num_layers=2, hidden_dim=16)
CFG = D.DALLEConfig(dim=32, depth=2, vae=VCFG, num_text_tokens=100,
                    text_seq_len=16, heads=2, dim_head=16)


@pytest.fixture
def key():
    return jax.random.PRNGKey(0)


@pytest.fixture
def vae_params(key):
    return V.vae_init(jax.random.fold_in(key, 1), VCFG)


@pytest.fixture
def params(key, vae_params):
    return D.dalle_init(key, CFG, vae_params)


class TestTopP:
    """Filtered entries are the codebase's neg_inf fill = -finfo.max
    (reference parity, a FINITE float) — test keep/drop via a threshold,
    not isfinite."""

    @staticmethod
    def _kept(out):
        from dalle_pytorch_tpu.ops import core
        return (np.asarray(out) > float(core.neg_inf(jnp.float32)) / 2)[0]

    def test_tiny_p_keeps_only_argmax(self):
        logits = jnp.asarray([[1.0, 3.0, 2.0, -jnp.inf]])
        out = D.top_p_filter(logits, 1e-6)
        assert float(out[0, 1]) == 3.0
        assert self._kept(out).tolist() == [False, True, False, False]

    def test_p_one_keeps_all_unmasked(self):
        logits = jnp.asarray([[1.0, 3.0, 2.0, -jnp.inf]])
        out = D.top_p_filter(logits, 1.0)
        # masked stays dropped
        assert self._kept(out).tolist() == [True, True, True, False]

    def test_nucleus_cut(self):
        """p=0.6 over probs [.655,.242,.089,...]: the first token holds
        .655 >= .6, second starts at cum .655 >= p -> only argmax kept;
        p=0.7 keeps the first two."""
        logits = jnp.log(jnp.asarray([[0.655, 0.242, 0.089, 0.014]]))
        assert self._kept(D.top_p_filter(logits, 0.6)).tolist() == \
            [True, False, False, False]
        assert self._kept(D.top_p_filter(logits, 0.7)).tolist() == \
            [True, True, False, False]

    def test_generation_with_top_p(self, key, vae_params, params):
        imgs = D.generate_images(params, vae_params,
                                 jax.random.randint(key, (1, 5), 3, 100),
                                 cfg=CFG, rng=jax.random.fold_in(key, 4),
                                 top_p=0.9)
        assert imgs.shape == (1, 32, 32, 3)
        assert bool(jnp.all(jnp.isfinite(imgs)))


class TestGuidance:
    def test_guidance_one_matches_unguided(self, key, vae_params, params):
        """s=1.0 reduces the mix to the conditional logits, and the rng
        key schedule is identical — the guided program must reproduce the
        unguided samples exactly."""
        text = jax.random.randint(jax.random.fold_in(key, 2), (2, 5),
                                  3, 100)
        plain = D.generate_images(params, vae_params, text, cfg=CFG,
                                  rng=jax.random.fold_in(key, 4),
                                  return_img_seq=True)[1]
        guided = D.generate_images(params, vae_params, text, cfg=CFG,
                                   rng=jax.random.fold_in(key, 4),
                                   guidance=1.0, return_img_seq=True)[1]
        np.testing.assert_array_equal(np.asarray(plain),
                                      np.asarray(guided))

    def test_guided_generation_runs(self, key, vae_params, params):
        text = jax.random.randint(jax.random.fold_in(key, 2), (2, 5),
                                  3, 100)
        imgs, seq = D.generate_images(params, vae_params, text, cfg=CFG,
                                      rng=jax.random.fold_in(key, 4),
                                      guidance=3.0, return_img_seq=True)
        assert imgs.shape == (2, 32, 32, 3)        # cond stream only
        assert bool(jnp.all(jnp.isfinite(imgs)))
        assert int(seq.min()) >= 0
        assert int(seq.max()) < CFG.num_image_tokens


def test_rerank_rejects_undersized_clip_vocab(key, vae_params, params):
    """A CLIP vocab smaller than the DALLE's would NaN the rerank scores
    via an out-of-range gather (XLA fills instead of erroring); the
    library raises at trace time instead."""
    from dalle_pytorch_tpu.models import clip as C
    clip_cfg = C.CLIPConfig(
        dim_text=16, dim_image=16, dim_latent=16,
        num_text_tokens=CFG.num_text_tokens // 2,     # undersized
        text_enc_depth=1, text_seq_len=CFG.text_seq_len, text_heads=2,
        visual_enc_depth=1, visual_image_size=CFG.vae.image_size,
        visual_patch_size=8, visual_heads=2)
    clip_params = C.clip_init(jax.random.fold_in(key, 9), clip_cfg)
    text = jax.random.randint(jax.random.fold_in(key, 2), (1, 5), 3, 100)
    with pytest.raises(ValueError, match="num_text_tokens"):
        D.generate_images(params, vae_params, text, cfg=CFG,
                          rng=jax.random.fold_in(key, 4),
                          clip_params=clip_params, clip_cfg=clip_cfg)


def _toy_batch(key, b=2):
    kt, ki = jax.random.split(key)
    text = jax.random.randint(kt, (b, CFG.text_seq_len), 0,
                              CFG.num_text_tokens)
    image_ids = jax.random.randint(ki, (b, CFG.image_seq_len), 0,
                                   CFG.num_image_tokens)
    return text, image_ids


def test_derived_dims():
    assert CFG.image_seq_len == 64          # (32 / 2**2)**2
    assert CFG.seq_len == 16 + 64
    assert CFG.total_tokens == 100 + 48 + 1
    assert CFG.eos_token_id == 148


def test_tied_codebook_seed(params, vae_params):
    np.testing.assert_array_equal(np.array(params["image_emb"]["w"]),
                                  np.array(vae_params["codebook"]["w"]))


def test_tied_codebook_dim_mismatch_raises(key):
    bad = D.DALLEConfig(dim=64, depth=1, vae=VCFG, text_seq_len=8)
    with pytest.raises(ValueError):
        D.dalle_init(key, bad, V.vae_init(key, VCFG))


def test_logits_mask_layout():
    m = np.array(D.logits_mask(CFG))        # True = forbidden
    t, nt = CFG.text_seq_len, CFG.num_text_tokens
    # rows < t-1 predict text: image+EOS forbidden, text allowed
    assert not m[0, :nt].any() and m[0, nt:].all()
    # rows >= t-1 predict image ids: text forbidden
    assert m[t - 1, :nt].all() and not m[t - 1, nt:-1].any()
    # EOS only at the very last row
    assert m[:-1, -1].all() and not m[-1, -1]
    # last row also allows image ids only
    assert m[-1, :nt].all() and not m[-1, nt:-1].any()


def test_forward_logits_shape_and_mask_applied(key, params, vae_params):
    text, image_ids = _toy_batch(key)
    logits = D.dalle_apply(params, text, image_ids, cfg=CFG,
                           vae_params=vae_params)
    assert logits.shape == (2, CFG.seq_len, CFG.total_tokens)
    m = np.array(D.logits_mask(CFG))
    lg = np.array(logits)
    fill = -np.finfo(lg.dtype).max
    assert (lg[:, m] == fill).all()


def test_loss_matches_manual_ce(key, params, vae_params):
    text, image_ids = _toy_batch(key)
    loss = D.dalle_apply(params, text, image_ids, cfg=CFG,
                         vae_params=vae_params, return_loss=True)
    logits = D.dalle_apply(params, text, image_ids, cfg=CFG,
                           vae_params=vae_params)
    labels = np.concatenate(
        [np.array(text), np.array(image_ids) + CFG.num_text_tokens,
         np.full((2, 1), CFG.eos_token_id)], axis=1)[:, 1:]
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    manual = -np.mean(np.take_along_axis(np.array(logp), labels[..., None],
                                         axis=-1))
    np.testing.assert_allclose(float(loss), manual, rtol=1e-5)


def test_raw_image_tokenization_no_vae_grad(key, params, vae_params):
    text, _ = _toy_batch(key)
    imgs = jax.random.uniform(key, (2, 32, 32, 3), minval=-1, maxval=1)

    def loss_fn(p, vp):
        return D.dalle_apply(p, text, imgs, cfg=CFG, vae_params=vp,
                             return_loss=True)

    loss, gvae = jax.value_and_grad(loss_fn, argnums=1)(params, vae_params)
    assert np.isfinite(float(loss))
    # token ids come through stop_gradient: VAE encoder gets NO gradient
    # (reference @torch.no_grad get_codebook_indices, dalle_pytorch.py:120)
    total = sum(float(jnp.abs(g).sum()) for g in jax.tree.leaves(gvae))
    assert total == 0.0


def test_text_mask_padded_over_image_span(key, params, vae_params):
    text, image_ids = _toy_batch(key)
    mask = jnp.ones((2, CFG.text_seq_len), bool).at[:, 10:].set(False)
    loss = D.dalle_apply(params, text, image_ids, cfg=CFG, mask=mask,
                         vae_params=vae_params, return_loss=True)
    assert np.isfinite(float(loss))


def test_top_k_filter_keeps_top_half():
    logits = jnp.asarray(np.random.default_rng(0)
                         .standard_normal((2, 100), dtype=np.float32))
    out = np.array(D.top_k_filter(logits, 0.5))
    kept = np.isfinite(np.maximum(out, -1e30)) & (out > -1e30)
    assert (kept.sum(axis=-1) == 50).all()
    # kept entries are exactly the top-50 of each row
    for i in range(2):
        top = set(np.argsort(np.array(logits[i]))[-50:])
        assert set(np.where(kept[i])[0]) == top


@pytest.mark.parametrize("variant", ["sequential", "reversible", "sparse"])
def test_cache_replay_matches_full_forward(key, vae_params, variant):
    """Teacher-forced replay: stepping the KV-cache decoder over a known
    sequence must reproduce the full forward's logits at every position."""
    kw = {}
    if variant == "reversible":
        kw["reversible"] = True
    if variant == "sparse":
        kw["sparse_attn"] = (True, False)
    cfg = D.DALLEConfig(dim=32, depth=2, vae=VCFG, num_text_tokens=100,
                        text_seq_len=16, heads=2, dim_head=16, **kw)
    params = D.dalle_init(key, cfg, vae_params)
    text, image_ids = _toy_batch(key)

    full_logits = D.dalle_apply(params, text, image_ids, cfg=cfg,
                                vae_params=vae_params)

    tokens = D.embed_prompt(params, cfg, text, image_ids)
    t0 = cfg.text_seq_len
    h, cache = decode_ops.prefill(params["transformer"], tokens[:, :t0],
                                  cfg=cfg.transformer, total_len=cfg.seq_len)
    key_mask = jnp.ones((2, cfg.seq_len), bool)

    # prefill last row == full forward row t0-1 (pre-mask comparison)
    pre = D.to_logits(params, h[:, -1])
    forb = D.logits_mask(cfg)
    pre = jnp.where(forb[t0 - 1][None], -jnp.finfo(pre.dtype).max, pre)
    np.testing.assert_allclose(np.array(pre), np.array(full_logits[:, t0 - 1]),
                               atol=1e-4)

    for p in range(t0, cfg.seq_len):
        h_tok, cache = decode_ops.decode_step(
            params["transformer"], tokens[:, p], jnp.asarray(p), cache,
            cfg=cfg.transformer, key_mask=key_mask)
        lg = D.to_logits(params, h_tok)
        lg = jnp.where(forb[p][None], -jnp.finfo(lg.dtype).max, lg)
        np.testing.assert_allclose(
            np.array(lg), np.array(full_logits[:, p]), atol=1e-4,
            err_msg=f"{variant} mismatch at position {p}")


def test_generate_images_shapes_and_token_ranges(key, params, vae_params):
    text = jax.random.randint(key, (2, CFG.text_seq_len), 3,
                              CFG.num_text_tokens)
    images, img_seq = D.generate_images(params, vae_params, text, cfg=CFG,
                                        rng=key, return_img_seq=True)
    assert images.shape == (2, 32, 32, 3)
    ids = np.array(img_seq)
    assert ids.shape == (2, CFG.image_seq_len)
    assert (ids >= 0).all() and (ids < CFG.num_image_tokens).all()


def test_generate_text_completion_mode(key, params, vae_params):
    """Short unpadded prompt (genDALLE.py:106): the sampler must complete
    the text span with TEXT ids before generating image tokens."""
    t0 = 5
    text = jax.random.randint(key, (1, t0), 3, CFG.num_text_tokens)
    images, img_seq = D.generate_images(params, vae_params, text, cfg=CFG,
                                        rng=key, return_img_seq=True)
    assert images.shape == (1, 32, 32, 3)
    ids = np.array(img_seq)
    assert (ids >= 0).all() and (ids < CFG.num_image_tokens).all()


def test_generate_is_jittable_and_deterministic(key, params, vae_params):
    text = jax.random.randint(key, (1, CFG.text_seq_len), 3,
                              CFG.num_text_tokens)
    f = jax.jit(lambda p, vp, t, r: D.generate_images(
        p, vp, t, cfg=CFG, rng=r, return_img_seq=True)[1])
    a = f(params, vae_params, text, key)
    b = f(params, vae_params, text, key)
    # jaxlint: disable=JL001 — terminal fetch for the equality assertion
    np.testing.assert_array_equal(np.array(a), np.array(b))


def test_oo_wrapper(key):
    vae = V.DiscreteVAE(key, image_size=32, num_tokens=48, codebook_dim=32,
                        num_layers=2, hidden_dim=16)
    model = D.DALLE(dim=32, vae=vae, depth=2, key=key, num_text_tokens=100,
                    text_seq_len=16, heads=2, dim_head=16)
    text = jax.random.randint(jax.random.fold_in(key, 1), (1, 16), 0, 100)
    imgs = jax.random.uniform(jax.random.fold_in(key, 2), (1, 32, 32, 3))
    loss = model(text, imgs, return_loss=True)
    assert np.isfinite(float(loss))
    with pytest.raises(TypeError):
        D.DALLE(dim=32, vae="not a vae", depth=1)


class TestChunkedCE:
    """loss_chunk streams the 12k-vocab head over sequence chunks; the loss
    and gradients must match the dense path (models/dalle._chunked_ce)."""

    def _setup(self, loss_chunk):
        import dataclasses
        from dalle_pytorch_tpu.models import dalle as D
        from dalle_pytorch_tpu.models import vae as V
        vcfg = V.VAEConfig(image_size=16, num_tokens=12, codebook_dim=16,
                           num_layers=2, hidden_dim=8)
        cfg = D.DALLEConfig(dim=16, depth=2, vae=vcfg, num_text_tokens=20,
                            text_seq_len=6, heads=2, dim_head=8,
                            loss_chunk=loss_chunk)
        params = D.dalle_init(jax.random.PRNGKey(0), cfg)
        text = jax.random.randint(jax.random.PRNGKey(1), (2, 6), 0, 20)
        ids = jax.random.randint(jax.random.PRNGKey(2), (2, 16), 0, 12)
        return D, cfg, params, text, ids

    @pytest.mark.parametrize("chunk", [4, 7, 64])
    def test_loss_and_grads_match_dense(self, chunk):
        import dataclasses
        D, cfg, params, text, ids = self._setup(chunk)
        dense_cfg = dataclasses.replace(cfg, loss_chunk=0)

        def loss(p, c):
            return D.dalle_apply(p, text, ids, cfg=c, return_loss=True)

        l_dense, g_dense = jax.value_and_grad(loss)(params, dense_cfg)
        l_chunk, g_chunk = jax.value_and_grad(loss)(params, cfg)
        np.testing.assert_allclose(float(l_chunk), float(l_dense),
                                   rtol=1e-6)
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(a, b, atol=1e-5),
            g_chunk, g_dense)

    def test_logits_path_unaffected(self):
        D, cfg, params, text, ids = self._setup(4)
        logits = D.dalle_apply(params, text, ids, cfg=cfg)
        assert logits.shape == (2, 22, cfg.total_tokens)


def test_north_composition_remat_flash_chunk_matches_plain(key, params):
    """remat='full' + attn_impl='flash' + chunked CE compose in one train
    step (what the train cell runs, at toy widths); loss and grads must
    match the plain dense/xla/un-rematerialized path, since remat and the
    CE streaming are pure memory strategies and flash is an exact
    attention algorithm (not an approximation)."""
    import dataclasses

    north = dataclasses.replace(CFG, remat="full", attn_impl="flash",
                                loss_chunk=16)
    plain = CFG
    text = jax.random.randint(jax.random.fold_in(key, 2), (2, 16), 0, 100)
    ids = jax.random.randint(jax.random.fold_in(key, 3), (2, 64), 0, 48)

    def loss(p, c):
        return D.dalle_apply(p, text, ids, cfg=c, return_loss=True)

    l_p, g_p = jax.value_and_grad(loss)(params, plain)
    l_n, g_n = jax.value_and_grad(loss)(params, north)
    np.testing.assert_allclose(float(l_n), float(l_p), rtol=1e-5)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.array(a), np.array(b), atol=5e-4), g_p, g_n)
