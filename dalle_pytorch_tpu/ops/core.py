"""Primitive init/apply ops: linear, layernorm, embedding, conv.

Parameters are plain nested dicts of ``jnp.ndarray`` (pytrees). Every op is a
pure function ``apply(params, x, ...)`` so it composes with ``jit``, ``scan``,
``vmap``, ``custom_vjp`` and ``shard_map`` without a module system in the way.

Initialisation follows the reference's torch defaults in distribution family
(uniform ±1/sqrt(fan_in) for linear/conv, N(0,1) for embeddings — see
torch.nn.Linear/Conv2d/Embedding resets) so training dynamics are comparable,
though bitwise weight parity with torch is a non-goal.
"""

from __future__ import annotations

import math
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

Array = jax.Array


# ---------------------------------------------------------------------------
# initializers
# ---------------------------------------------------------------------------

def uniform_fan_in(key: Array, shape: Sequence[int], fan_in: int,
                   dtype=jnp.float32) -> Array:
    """torch-style kaiming-uniform(a=sqrt(5)) ≡ U(-1/sqrt(fan_in), 1/sqrt(fan_in))."""
    bound = 1.0 / math.sqrt(max(fan_in, 1))
    return jax.random.uniform(key, tuple(shape), dtype, -bound, bound)


def normal_init(key: Array, shape: Sequence[int], stddev: float = 1.0,
                dtype=jnp.float32) -> Array:
    return jax.random.normal(key, tuple(shape), dtype) * stddev


# ---------------------------------------------------------------------------
# linear
# ---------------------------------------------------------------------------

def linear_init(key: Array, in_dim: int, out_dim: int, *, bias: bool = True,
                dtype=jnp.float32) -> dict:
    kw, kb = jax.random.split(key)
    params = {"w": uniform_fan_in(kw, (in_dim, out_dim), in_dim, dtype)}
    if bias:
        params["b"] = uniform_fan_in(kb, (out_dim,), in_dim, dtype)
    return params


def linear(params: dict, x: Array) -> Array:
    """y = x @ w (+ b). Keeps the contraction in the input dtype so bf16
    activations hit the MXU; accumulation dtype is left to XLA (f32 on TPU).

    Accepts an int8-quantized dict ({"w_q", "scale"} from ops.quant)
    transparently: XLA reads int8 weights from HBM (half the decode-path
    traffic) and the per-output-channel scale multiplies the matmul
    result — exact w.r.t. the quantized weights, since a per-out-channel
    factor commutes with the contraction."""
    if "w_q" in params:
        y = jnp.dot(x, params["w_q"].astype(x.dtype))
        y = y * params["scale"].astype(x.dtype)
    else:
        y = jnp.dot(x, params["w"].astype(x.dtype))
    if "b" in params:
        y = y + params["b"].astype(x.dtype)
    return y


# ---------------------------------------------------------------------------
# layer norm
# ---------------------------------------------------------------------------

def layernorm_init(dim: int, dtype=jnp.float32) -> dict:
    return {"g": jnp.ones((dim,), dtype), "b": jnp.zeros((dim,), dtype)}


@jax.named_scope("norm")
def layernorm(params: dict, x: Array, *, eps: float = 1e-5) -> Array:
    # Normalise in f32 for numerical stability, cast back to input dtype.
    # The two full-size f32 intermediates are tagged with checkpoint_name
    # so remat='save_ln' can drop EXACTLY these from the saved residuals
    # (they dominate the un-rematerialized stack's
    # activation bytes — 2 x 4 bytes/elt vs the bf16 compute stream) while
    # keeping every matmul output saved. checkpoint_name is an identity
    # outside jax.checkpoint.
    xf = checkpoint_name(x.astype(jnp.float32), "ln_f32_in")
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mean), axis=-1, keepdims=True)
    y = (xf - mean) * lax.rsqrt(var + eps)
    y = y * params["g"].astype(jnp.float32) + params["b"].astype(jnp.float32)
    y = checkpoint_name(y, "ln_f32_out")
    return y.astype(x.dtype)


def rmsnorm_init(dim: int, dtype=jnp.float32) -> dict:
    return {"g": jnp.ones((dim,), dtype)}


@jax.named_scope("norm")
def rmsnorm(params: dict, x: Array, *, eps: float = 1e-6) -> Array:
    """``x / sqrt(mean(x^2) + eps) * g`` in f32, cast back: the norm of
    the latent-attention block (ops/transformer.py ``LatentMoEBlock``)."""
    xf = x.astype(jnp.float32)
    y = xf * lax.rsqrt(jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
                       + eps)
    return (y * params["g"].astype(jnp.float32)).astype(x.dtype)


def norm(params: dict, x: Array) -> Array:
    """The norm its parameters describe: a gain with a shift is a
    LayerNorm, a gain alone an RMSNorm. For the callers that hold
    parameters and no configuration (``models.dalle.to_logits``)."""
    return layernorm(params, x) if "b" in params else rmsnorm(params, x)


# ---------------------------------------------------------------------------
# embedding
# ---------------------------------------------------------------------------

def embedding_init(key: Array, num_embeddings: int, dim: int,
                   dtype=jnp.float32) -> dict:
    return {"w": normal_init(key, (num_embeddings, dim), 1.0, dtype)}


def embedding(params: dict, ids: Array) -> Array:
    return jnp.take(params["w"], ids, axis=0)


# ---------------------------------------------------------------------------
# conv2d (NHWC internally — the TPU-native layout)
# ---------------------------------------------------------------------------

def conv2d_init(key: Array, in_ch: int, out_ch: int, kernel: int, *,
                dtype=jnp.float32) -> dict:
    kw, kb = jax.random.split(key)
    fan_in = in_ch * kernel * kernel
    return {
        "w": uniform_fan_in(kw, (kernel, kernel, in_ch, out_ch), fan_in, dtype),
        "b": uniform_fan_in(kb, (out_ch,), fan_in, dtype),
    }


def conv2d(params: dict, x: Array, *, stride: int = 1, padding: int = 0) -> Array:
    """2-D convolution over NHWC input with an HWIO kernel."""
    w = params["w"].astype(x.dtype)
    dn = lax.conv_dimension_numbers(x.shape, w.shape, ("NHWC", "HWIO", "NHWC"))
    y = lax.conv_general_dilated(
        x, w,
        window_strides=(stride, stride),
        padding=((padding, padding), (padding, padding)),
        dimension_numbers=dn,
    )
    return y + params["b"].astype(x.dtype)


def conv2d_transpose(params: dict, x: Array, *, stride: int = 2,
                     padding: int = 1) -> Array:
    """Transposed conv matching torch ConvTranspose2d(k, stride, padding):
    implemented as input-dilated convolution with a spatially flipped kernel
    (out spatial = in*stride for k=4, s=2, p=1 — the dVAE upsample shape,
    reference dalle_pytorch/dalle_pytorch.py:105)."""
    w = params["w"].astype(x.dtype)  # (kh, kw, in, out)
    k = w.shape[0]
    w_flipped = w[::-1, ::-1, :, :]
    pad = k - 1 - padding
    dn = lax.conv_dimension_numbers(x.shape, w_flipped.shape,
                                    ("NHWC", "HWIO", "NHWC"))
    y = lax.conv_general_dilated(
        x, w_flipped,
        window_strides=(1, 1),
        padding=((pad, pad), (pad, pad)),
        lhs_dilation=(stride, stride),
        dimension_numbers=dn,
    )
    return y + params["b"].astype(x.dtype)


# ---------------------------------------------------------------------------
# activations / misc
# ---------------------------------------------------------------------------

def gelu(x: Array) -> Array:
    """Exact (erf) GELU, matching torch F.gelu default used by the reference
    GEGLU (reference dalle_pytorch/transformer.py:36)."""
    return jax.nn.gelu(x, approximate=False)


def swiglu_init(key: Array, dim: int, hidden: int, dtype=jnp.float32) -> dict:
    """SiLU-gated feed-forward without biases: ``w_in`` holds the gate and
    the up projection side by side, (dim, 2 * hidden)."""
    k_in, k_out = jax.random.split(key)
    return {"w_in": uniform_fan_in(k_in, (dim, 2 * hidden), dim, dtype),
            "w_out": uniform_fan_in(k_out, (hidden, dim), hidden, dtype)}


def swiglu(params: dict, x: Array) -> Array:
    """``W_down(silu(W_gate x) * (W_up x))``."""
    gate, up = jnp.split(jnp.dot(x, params["w_in"].astype(x.dtype)), 2,
                         axis=-1)
    return jnp.dot(jax.nn.silu(gate) * up, params["w_out"].astype(x.dtype))


def dropout(key: Optional[Array], x: Array, rate: float, train: bool) -> Array:
    if not train or rate == 0.0 or key is None:
        return x
    keep = 1.0 - rate
    mask = jax.random.bernoulli(key, keep, x.shape)
    return jnp.where(mask, x / keep, jnp.zeros_like(x))


def positional_dropout(key: Optional[Array], x: Array, rate: float,
                       train: bool, *, offset=0) -> Array:
    """Dropout whose mask for token ``i`` (axis 1 of ``x``) is keyed by the
    token's GLOBAL position ``offset + i``, not by the tensor's shape.

    The mask is therefore invariant to how the sequence axis is sharded:
    concatenating per-shard results (each shard passing its global start as
    ``offset``) reproduces the unsharded mask bit-for-bit. This is what lets
    sequence-parallel training (parallel.sequence) run the flagship
    dropout-0.1 config with the same key discipline on every sp degree.
    ``offset`` may be traced (e.g. ``lax.axis_index(sp) * n_local``)."""
    if not train or rate == 0.0 or key is None:
        return x
    keep = 1.0 - rate
    pos = offset + jnp.arange(x.shape[1])
    per_pos_shape = (x.shape[0],) + x.shape[2:]

    def pos_mask(p):
        return jax.random.bernoulli(jax.random.fold_in(key, p), keep,
                                    per_pos_shape)

    mask = jnp.moveaxis(jax.vmap(pos_mask)(pos), 0, 1)
    return jnp.where(mask, x / keep, jnp.zeros_like(x))


def pallas_interpret() -> bool:
    """The one ``interpret=`` default of every Pallas kernel entry
    (flash_attention, block_sparse, paged_attention): compiled by Mosaic
    on a TPU backend, interpreted everywhere else — so the same kernel
    code runs in tier-1 on the CPU mesh. Never a fallback: a kernel
    Mosaic refuses fails the call."""
    return jax.default_backend() != "tpu"


def shard_over_batch_and_heads(fn, operands: Sequence[Optional[Array]],
                               dims: Sequence[str], result_dims):
    """``fn(*operands)``, run per device on its own shard of the batch
    and head dimensions when traced under a device mesh — for functions
    that hold a Pallas call. GSPMD cannot partition a Mosaic kernel by
    itself ("Mosaic kernels cannot be automatically partitioned. Please
    wrap the call in a shard_map"), so without this every dp/tp/fsdp
    train step on more than one chip fails to compile the moment it
    reaches a kernel. Attention kernels are independent per (batch, head).

    The mesh is OBSERVED, not configured: the first operand carries the
    enclosing jit's abstract mesh in its type. Which of its axes hold the
    batch and the heads is the framework's naming convention
    (parallel/mesh.py): ``dp`` and ``fsdp`` shard the batch, ``tp`` the
    heads; an axis whose size does not divide the dimension is left out,
    and every other axis and dimension is replicated. A guess that
    differs from where XLA actually keeps the operands costs a reshard,
    never correctness.

    ``dims`` names each operand's dimensions, one letter each
    (``"bhnd"``, ``"bn"``, ``"bhn"``), ``result_dims`` does the same for
    ``fn``'s result pytree; an operand may be None (an absent pad mask)
    and is passed through as None. Off a mesh (one device, or already
    inside a fully manual shard_map) this is the plain call."""
    from jax.sharding import AxisType, PartitionSpec as P
    mesh = jax.typeof(operands[0]).sharding.mesh
    auto = {a: n for a, n, t in zip(mesh.axis_names, mesh.axis_sizes,
                                    mesh.axis_types)
            if t != AxisType.Manual}
    if all(n == 1 for n in auto.values()):
        return fn(*operands)
    sizes = dict(zip(dims[0], operands[0].shape))

    def dividing(axes, dim):
        kept, span = [], 1
        for a in axes:
            if a in auto and dim % (span * auto[a]) == 0:
                kept.append(a)
                span *= auto[a]
        return tuple(kept) or None

    axes = {"b": dividing(("dp", "fsdp"), sizes["b"]),
            "h": dividing(("tp",), sizes["h"])}

    def spec(names):
        return P(*(axes.get(c) for c in names))

    present = [i for i, x in enumerate(operands) if x is not None]

    def local(*arrays):
        full = [None] * len(operands)
        for i, x in zip(present, arrays):
            full[i] = x
        return fn(*full)

    return jax.shard_map(
        local, mesh=mesh, in_specs=tuple(spec(dims[i]) for i in present),
        out_specs=jax.tree.map(spec, result_dims),
        axis_names=frozenset(auto), check_vma=False,
    )(*(operands[i] for i in present))


def neg_inf(dtype) -> Array:
    """The reference's mask fill value: -finfo(dtype).max
    (reference dalle_pytorch/transformer.py:72)."""
    return jnp.asarray(-jnp.finfo(jnp.dtype(dtype)).max, dtype)
