"""Compile a cell's program for a TPU that is described and not attached
(``jax.experimental.topologies``), to size a cell and to guard its fit in
the CPU tests. Nothing runs; a compile that passes is not a chip run.

The program's two backend switches read ``jax.default_backend()``, which
is the CPU here, so they are steered from this file (never through an
option of the program): buffers are donated and Pallas kernels compile, at the default matmul precision.
"""

from __future__ import annotations

import contextlib
import os


def topology(name: str = "v5e:2x2"):
    os.environ.setdefault("TPU_LOG_DIR", "disabled")   # or it logs to /tmp
    from jax.experimental import topologies
    return topologies.get_topology_desc(platform="tpu", topology_name=name)


@contextlib.contextmanager
def as_on_tpu():
    from dalle_pytorch_tpu.ops import core
    from dalle_pytorch_tpu.parallel import _compat
    saved = core.pallas_interpret, _compat.donate_if_accelerator
    core.pallas_interpret = lambda: False
    _compat.donate_if_accelerator = lambda *argnums: tuple(argnums)
    try:
        # the chip's own default, whatever the caller runs under (the
        # test suite computes at "highest", which Mosaic refuses for bf16)
        import jax
        with jax.default_matmul_precision("default"):
            yield
    finally:
        core.pallas_interpret, _compat.donate_if_accelerator = saved


def _sds(tree, sharding):
    import jax
    if not isinstance(sharding, dict):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=sharding), tree)
    return jax.tree.map(lambda a, s: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=s), tree, sharding)


def compile_train_step(cell, devices):
    """The cell's train step, compiled for ``devices`` (described v5e
    chips). -> the compiled executable (``memory_analysis()``, ``as_text()``)."""
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from benchmark import build
    from benchmark import weights as W
    from dalle_pytorch_tpu.models import dalle as D
    from dalle_pytorch_tpu.parallel import make_mesh
    from dalle_pytorch_tpu.parallel.train import (dalle_param_specs,
                                                  make_train_step)
    spec, mix = cell.spec, cell.traffic
    dims = W.dims_of(cell.config, spec["depth"])
    dtype = jnp.dtype(cell.config["param_dtype"])
    cfg = build.dalle_config(cell.config, dims, spec["flags"])
    mesh = make_mesh(spec.get("mesh") or {"dp": cell.chips},
                     list(devices)[:cell.chips])
    axis = spec.get("batch_axis", "dp")
    rows = int(mix["rows_per_group"]) * int(mesh.shape[axis])
    optimizer = optax.adam(float(spec["flags"]["lr"]))
    shapes = jax.eval_shape(lambda: W.tree(W.split_seed(0), dims, dtype))
    rep = NamedSharding(mesh, P())
    axes = spec.get("param_axes")
    if axes:
        specs = dalle_param_specs(shapes, mesh=mesh, **axes)
        shard = jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                             is_leaf=lambda x: isinstance(x, P))
    else:
        shard = jax.tree.map(lambda _: rep, shapes)
    params = _sds(shapes, shard)
    opt_shapes = jax.eval_shape(optimizer.init, shapes)
    # moments are placed like the parameters, the count replicated
    adam = opt_shapes[0]
    opt = (type(adam)(count=_sds(adam.count, rep), mu=_sds(adam.mu, shard),
                      nu=_sds(adam.nu, shard)),) + tuple(opt_shapes[1:])
    rows_sh = NamedSharding(mesh, P(axis))
    batch = {"text": jax.ShapeDtypeStruct((rows, dims.text_seq_len),
                                          jnp.int32, sharding=rows_sh),
             "image": jax.ShapeDtypeStruct((rows, dims.image_seq_len),
                                           jnp.int32, sharding=rows_sh)}
    rng = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=rep)

    def loss_fn(p, b, r):
        return D.dalle_apply(p, b["text"], b["image"], cfg=cfg,
                             mask=jnp.ones_like(b["text"], bool), rng=r,
                             train=True, return_loss=True)

    with as_on_tpu():
        step = make_train_step(loss_fn, optimizer)
        return step.trace(params, opt, batch, rng).lower(
            lowering_platforms=("tpu",)).compile()


def bytes_needed(compiled) -> int:
    """Device bytes one chip needs for this program: arguments, outputs
    that are not aliased to arguments, and temporaries."""
    m = compiled.memory_analysis()
    return int(m.argument_size_in_bytes + m.output_size_in_bytes
               - m.alias_size_in_bytes + m.temp_size_in_bytes)


def serve_engine(cell):
    """The cell's engine at its real sizes on this host's CPU (zeros for
    weights), built under ``as_on_tpu`` so that its programs donate."""
    import jax
    import jax.numpy as jnp

    from benchmark import build
    from benchmark import weights as W
    from dalle_pytorch_tpu.serve import engine as engine_mod
    from dalle_pytorch_tpu.serve import scheduler as S
    spec = cell.spec
    dims = W.dims_of(cell.config, spec["depth"])
    dtype = jnp.dtype(cell.config["param_dtype"])
    cfg = build.dalle_config(cell.config, dims, spec["flags"])
    shapes = jax.eval_shape(lambda: W.tree(W.split_seed(0), dims, dtype))
    params = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), shapes)
    eng = spec["engine"]
    with as_on_tpu():
        return engine_mod.Engine(
            params, cfg, S.RequestQueue(max_depth=8,
                                        max_prompt_len=cfg.text_seq_len),
            num_slots=int(spec["num_slots"]),
            chunk_steps=int(eng["chunk_steps"]), kv=eng["kv"],
            paged_attn=eng["paged_attn"])


def compile_decode(engine, device):
    """The engine's one fused decode program, compiled for ``device``."""
    from jax.sharding import SingleDeviceSharding
    sh = SingleDeviceSharding(device)
    args = (engine.params, engine.cache, engine.block_tables,
            engine.cur_tok, engine.pos, engine.active, engine.rng,
            engine.temp, engine.topk_k, engine.top_p, engine.cfg_partner,
            engine.cfg_scale, engine.cfg_uncond)
    with as_on_tpu():
        return engine._decode_fn.trace(*_sds(args, sh)).lower(
            lowering_platforms=("tpu",)).compile()


def compile_prefill(engine, bucket: int, device):
    """The engine's admission program of one bucket, for ``device``."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    sh = SingleDeviceSharding(device)
    n = engine.num_slots

    def arr(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=sh)

    state = _sds((engine.params, engine.cache, engine.cur_tok, engine.pos,
                  engine.active, engine.rng, engine.temp, engine.topk_k,
                  engine.top_p), sh)
    rows = (arr((n, bucket), jnp.int32), arr((n,), jnp.int32),
            arr((n,), jnp.int32), arr((n,), jnp.int32),
            arr((n,), jnp.float32), arr((n,), jnp.int32),
            arr((n,), jnp.float32), arr((n,), jnp.int32),
            arr((n,), jnp.float32), arr((n,), jnp.bool_),
            arr((n, bucket), jnp.int32))
    with as_on_tpu():
        return engine._prefill_fn(bucket).trace(*state, *rows).lower(
            lowering_platforms=("tpu",)).compile()
