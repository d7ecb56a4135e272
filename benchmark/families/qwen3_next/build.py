"""The one place where the family touches the program's model code: a
``DALLEConfig`` that describes this block, from a configuration's sizes.
A cell's flags choose nothing here: the block comes from the model's
configuration."""

from __future__ import annotations

from . import weights as W


def program_config(dims: W.Dims, flags: dict):
    from dalle_pytorch_tpu.models import dalle as D
    from dalle_pytorch_tpu.models import vae as V
    from dalle_pytorch_tpu.ops.transformer import DeltaGQABlock
    vae = V.VAEConfig(image_size=dims.image_grid * 8,
                      num_tokens=dims.num_image_tokens, num_layers=3,
                      codebook_dim=dims.dim)
    block = DeltaGQABlock(
        layer_types=dims.layer_types, kv_heads=dims.kv_heads,
        head_dim=dims.head_dim, rotary_dim=dims.rotary_dim,
        rope_theta=dims.rope_theta, norm_eps=dims.norm_eps,
        key_heads=dims.key_heads, value_heads=dims.value_heads,
        key_head_dim=dims.key_head_dim, value_head_dim=dims.value_head_dim,
        conv_taps=dims.conv_taps, num_experts=dims.experts,
        experts_per_token=dims.experts_per_token,
        expert_hidden=dims.expert_hidden, shared_hidden=dims.shared_hidden,
        experts_held=dims.experts_held, first_expert=dims.first_expert)
    return D.DALLEConfig(
        dim=dims.dim, depth=dims.depth, vae=vae,
        num_text_tokens=dims.num_text_tokens,
        text_seq_len=dims.text_seq_len, heads=dims.heads, block=block)
