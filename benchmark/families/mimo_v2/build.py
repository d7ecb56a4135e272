"""The one place where the family touches the program's model code: a
``DALLEConfig`` that describes this block, from a configuration's sizes.
A cell's flags choose nothing here: the block comes from the model's
configuration."""

from __future__ import annotations

from . import weights as W


def program_config(dims: W.Dims, flags: dict):
    from dalle_pytorch_tpu.models import dalle as D
    from dalle_pytorch_tpu.models import vae as V
    from dalle_pytorch_tpu.ops.transformer import WindowGQABlock
    vae = V.VAEConfig(image_size=dims.image_grid * 8,
                      num_tokens=dims.num_image_tokens, num_layers=3,
                      codebook_dim=dims.dim)
    block = WindowGQABlock(
        name="window_sink_gqa_moe",
        kv_heads=dims.kv_heads, full_kv_heads=dims.full_kv_heads,
        head_dim=dims.head_dim, v_head_dim=dims.v_head_dim,
        rotary_dim=dims.rotary_dim, window=dims.window,
        layer_types=dims.layer_types, rope_theta=dims.rope_theta,
        full_rope_theta=dims.full_rope_theta,
        value_scale=dims.value_scale, norm_eps=dims.norm_eps,
        qk_norm=False, out_gate=False, sandwich_norms=False, sink=True,
        dense_layers=dims.dense_layers, dense_hidden=dims.dense_hidden,
        num_experts=dims.experts, experts_per_token=dims.experts_per_token,
        expert_hidden=dims.expert_hidden, shared_hidden=0,
        routed_scale=1.0, experts_held=dims.experts_held,
        first_expert=dims.first_expert)
    return D.DALLEConfig(
        dim=dims.dim, depth=dims.depth, vae=vae,
        num_text_tokens=dims.num_text_tokens,
        text_seq_len=dims.text_seq_len, heads=dims.heads, block=block)
