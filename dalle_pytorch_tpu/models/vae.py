"""DiscreteVAE — conv encoder/decoder with a Gumbel-softmax discrete codebook.

Capability parity with the reference DiscreteVAE (reference
dalle_pytorch/dalle_pytorch.py:65-157): images -> per-position token logits ->
Gumbel-softmax relaxed one-hot -> codebook mix -> conv decoder, plus the two
token-space entry points DALLE depends on, ``get_codebook_indices`` (argmax
tokens, reference :120-124) and ``decode`` (tokens -> image, reference
:126-136).

TPU-first design choices:
  * NHWC activations and HWIO kernels end-to-end — the layout XLA:TPU tiles
    onto the MXU without transposes (the reference is NCHW, torch's layout);
  * the codebook mix is one ``(b*h*w, num_tokens) @ (num_tokens, dim)``
    matmul — MXU-shaped — instead of a per-pixel einsum;
  * Gumbel noise comes from an explicit PRNG key (stateless, shardable);
  * ``apply`` is pure and jit/pjit-compatible; the training CLI shards it
    over the batch axis of a device mesh.

Architecture contract (matching reference __init__, :76-117): ``num_layers``
stride-2 4x4 conv+ReLU downsampling stages (so token grid = image_size /
2**num_layers), optional ResNet blocks at the encoder tail / decoder head,
a 1x1 conv to ``num_tokens`` logits, and a mirrored ConvTranspose decoder.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from dalle_pytorch_tpu.ops import core

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class VAEConfig:
    image_size: int = 256
    num_tokens: int = 512
    codebook_dim: int = 512
    num_layers: int = 3
    num_resnet_blocks: int = 0
    hidden_dim: int = 64
    channels: int = 3
    temperature: float = 0.9
    # Reference F.gumbel_softmax default is hard=False (soft relaxation,
    # reference dalle_pytorch.py:149); True gives straight-through.
    straight_through: bool = False

    def __post_init__(self):
        if self.num_layers < 1:
            raise ValueError("number of layers must be >= 1")
        # each layer halves the side: what the grid needs is whole
        # halvings (the reference asks for a power of 2; a 768-pixel
        # image is 96 x 96 codes at 8 x 8 compression)
        if self.image_size < 1 or self.image_size % 2 ** self.num_layers:
            raise ValueError(
                f"image size must be a multiple of 2**num_layers = "
                f"{2 ** self.num_layers}, got {self.image_size}")

    @property
    def grid_size(self) -> int:
        return self.image_size // (2 ** self.num_layers)

    @property
    def image_seq_len(self) -> int:
        return self.grid_size ** 2


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _resblock_init(key: Array, chan: int, dtype) -> dict:
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "c1": core.conv2d_init(k1, chan, chan, 3, dtype=dtype),
        "c2": core.conv2d_init(k2, chan, chan, 3, dtype=dtype),
        "c3": core.conv2d_init(k3, chan, chan, 1, dtype=dtype),
    }


def vae_init(key: Array, cfg: VAEConfig, dtype=jnp.float32) -> dict:
    """Build the parameter pytree. Channel plan mirrors the reference
    (dalle_pytorch.py:88-117): encoder channels [C, h, h, ...], decoder is
    the reverse, decoder input = codebook_dim (or a 1x1 stem when resblocks
    are present)."""
    n = cfg.num_layers
    keys = iter(jax.random.split(key, 4 * n + 2 * cfg.num_resnet_blocks + 8))

    params: dict = {
        "codebook": core.embedding_init(next(keys), cfg.num_tokens,
                                        cfg.codebook_dim, dtype),
    }

    enc_chans = [cfg.channels] + [cfg.hidden_dim] * n
    params["enc_convs"] = [
        core.conv2d_init(next(keys), cin, cout, 4, dtype=dtype)
        for cin, cout in zip(enc_chans[:-1], enc_chans[1:])
    ]
    params["enc_res"] = [
        _resblock_init(next(keys), enc_chans[-1], dtype)
        for _ in range(cfg.num_resnet_blocks)
    ]
    params["enc_out"] = core.conv2d_init(next(keys), enc_chans[-1],
                                         cfg.num_tokens, 1, dtype=dtype)

    has_res = cfg.num_resnet_blocks > 0
    dec_chans = [cfg.hidden_dim] * n
    dec_in = dec_chans[0] if has_res else cfg.codebook_dim
    if has_res:
        params["dec_stem"] = core.conv2d_init(next(keys), cfg.codebook_dim,
                                              dec_chans[0], 1, dtype=dtype)
    params["dec_res"] = [
        _resblock_init(next(keys), dec_chans[0], dtype)
        for _ in range(cfg.num_resnet_blocks)
    ]
    dec_io = list(zip([dec_in] + dec_chans[:-1], dec_chans))
    params["dec_convs"] = [
        core.conv2d_init(next(keys), cin, cout, 4, dtype=dtype)
        for cin, cout in dec_io
    ]
    params["dec_out"] = core.conv2d_init(next(keys), dec_chans[-1],
                                         cfg.channels, 1, dtype=dtype)
    return params


# ---------------------------------------------------------------------------
# apply
# ---------------------------------------------------------------------------

def _resblock(p: dict, x: Array) -> Array:
    h = jax.nn.relu(core.conv2d(p["c1"], x, padding=1))
    h = jax.nn.relu(core.conv2d(p["c2"], h, padding=1))
    return core.conv2d(p["c3"], h) + x


def encode_logits(params: dict, images: Array) -> Array:
    """images (b, H, W, C) in [-1, 1] -> logits (b, h, w, num_tokens)."""
    x = images
    for p in params["enc_convs"]:
        x = jax.nn.relu(core.conv2d(p, x, stride=2, padding=1))
    for p in params["enc_res"]:
        x = _resblock(p, x)
    return core.conv2d(params["enc_out"], x)


def decode_embeds(params: dict, embeds: Array) -> Array:
    """embeds (b, h, w, codebook_dim) -> images (b, H, W, C)."""
    x = embeds
    if "dec_stem" in params:
        x = core.conv2d(params["dec_stem"], x)
    for p in params["dec_res"]:
        x = _resblock(p, x)
    for p in params["dec_convs"]:
        x = jax.nn.relu(core.conv2d_transpose(p, x, stride=2, padding=1))
    return core.conv2d(params["dec_out"], x)


def gumbel_softmax(key: Array, logits: Array, tau: float,
                   straight_through: bool = False) -> Array:
    """Relaxed one-hot over the last axis (token dim). Soft by default, like
    the reference's F.gumbel_softmax(hard=False) (dalle_pytorch.py:149)."""
    g = jax.random.gumbel(key, logits.shape, logits.dtype)
    soft = jax.nn.softmax((logits + g) / tau, axis=-1)
    if straight_through:
        hard = jax.nn.one_hot(jnp.argmax(soft, axis=-1), logits.shape[-1],
                              dtype=soft.dtype)
        soft = soft + jax.lax.stop_gradient(hard - soft)
    return soft


def vae_apply(params: dict, images: Array, *, cfg: VAEConfig,
              rng: Optional[Array] = None,
              temperature: Optional[float] = None,
              return_logits: bool = False,
              return_recon_loss: bool = False):
    """Forward pass (reference DiscreteVAE.forward, dalle_pytorch.py:138-157).

    ``temperature`` overrides cfg.temperature so the training CLI's per-epoch
    schedule (reference trainVAE.py:78,104-105) stays a traced scalar, not a
    recompile.
    """
    logits = encode_logits(params, images)
    if return_logits:
        return logits

    if rng is None:
        raise ValueError("vae_apply needs an explicit PRNG key for the "
                         "Gumbel noise (stateless JAX RNG)")
    tau = cfg.temperature if temperature is None else temperature
    soft = gumbel_softmax(rng, logits, tau, cfg.straight_through)

    # (b, h, w, T) @ (T, d) — one big MXU matmul.
    embeds = jnp.einsum("bhwt,td->bhwd", soft,
                        params["codebook"]["w"].astype(soft.dtype))
    recon = decode_embeds(params, embeds)

    if not return_recon_loss:
        return recon
    return jnp.mean(jnp.square(images - recon))


def get_codebook_indices(params: dict, images: Array) -> Array:
    """(b, H, W, C) -> (b, image_seq_len) int32, argmax over the token dim,
    flattened row-major over the (h, w) grid (reference
    dalle_pytorch.py:120-124). No gradient flows (argmax)."""
    logits = encode_logits(params, images)
    b, h, w, t = logits.shape
    return jnp.argmax(logits, axis=-1).reshape(b, h * w).astype(jnp.int32)


def decode(params: dict, img_seq: Array,
           codebook: Optional[Array] = None) -> Array:
    """Token ids (b, n) -> images (b, H, W, C), assuming a square grid
    (reference dalle_pytorch.py:126-136).

    ``codebook`` optionally overrides the VAE's own table — DALLE training
    updates the tied codebook (reference dalle_pytorch.py:283), so decoding
    after DALLE training must use DALLE's copy.
    """
    table = params["codebook"]["w"] if codebook is None else codebook
    embeds = jnp.take(table, img_seq, axis=0)
    b, n, d = embeds.shape
    g = int(math.isqrt(n))
    assert g * g == n, "image token sequence must form a square grid"
    return decode_embeds(params, embeds.reshape(b, g, g, d))


# ---------------------------------------------------------------------------
# OO wrapper for reference-API parity
# ---------------------------------------------------------------------------

class DiscreteVAE:
    """Thin stateful wrapper over the functional core, mirroring the
    reference class surface (reference dalle_pytorch/dalle_pytorch.py:65-157)
    for users arriving from DALLE-pytorch. All compute delegates to the pure
    functions above; ``self.params`` is the single source of truth and can be
    swapped wholesale (checkpoint restore, optimizer updates)."""

    def __init__(self, key: Optional[Array] = None, *, params: dict = None,
                 dtype=jnp.float32, **cfg_kwargs):
        self.config = VAEConfig(**cfg_kwargs)
        if params is None:
            if key is None:
                key = jax.random.PRNGKey(0)
            params = vae_init(key, self.config, dtype)
        self.params = params

    # reference-parity properties
    @property
    def image_size(self) -> int:
        return self.config.image_size

    @property
    def num_tokens(self) -> int:
        return self.config.num_tokens

    @property
    def num_layers(self) -> int:
        return self.config.num_layers

    @property
    def temperature(self) -> float:
        return self.config.temperature

    def __call__(self, images: Array, rng: Optional[Array] = None, **kw):
        return vae_apply(self.params, images, cfg=self.config, rng=rng, **kw)

    forward = __call__

    def get_codebook_indices(self, images: Array) -> Array:
        return get_codebook_indices(self.params, images)

    def decode(self, img_seq: Array, codebook: Optional[Array] = None):
        return decode(self.params, img_seq, codebook)
