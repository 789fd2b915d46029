"""The Kimi-K2 configuration's code: its engine builder, as
`serve_cell:llama_engine` is Mistral's."""

from __future__ import annotations


def kimi_engine(config: dict) -> dict:
    """`models/kimi_k2.py` at the file's keys, for `LLMEngine`: the
    engine's `model` family, the `model_cfg` and the flax module that makes
    the weights. The experts held and the first of them are the chip's
    share (`n_routed_experts`, `deployment_share.first_expert`); the
    router's width is the published count."""
    import jax.numpy as jnp

    from ray_tpu.models.kimi_k2 import KimiK2, KimiK2Config

    dtype = jnp.dtype(config["torch_dtype"])
    rope = config["rope_scaling"]
    if rope["type"] != "yarn" or config["scoring_func"] != "sigmoid" \
            or config["n_group"] != 1 or config["topk_group"] != 1 \
            or not config["norm_topk_prob"]:
        raise RuntimeError("the file asks for routing or rope that "
                           "models/kimi_k2.py does not compute")
    cfg = KimiK2Config(
        dtype=dtype, param_dtype=dtype,
        vocab_size=config["vocab_size"],
        n_layer=config["num_hidden_layers"],
        n_dense_layer=config["first_k_dense_replace"],
        n_head=config["num_attention_heads"],
        d_model=config["hidden_size"],
        q_lora_rank=config["q_lora_rank"],
        kv_lora_rank=config["kv_lora_rank"],
        qk_nope_dim=config["qk_nope_head_dim"],
        qk_rope_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"],
        ffn_dim=config["intermediate_size"],
        moe_ffn_dim=config["moe_intermediate_size"],
        n_experts=config["published"]["n_routed_experts"],
        experts_held=config["n_routed_experts"],
        first_expert=config["deployment_share"]["first_expert"],
        top_k=config["num_experts_per_tok"],
        n_shared=config["n_shared_experts"],
        routed_scale=config["routed_scaling_factor"],
        max_seq_len=config["max_position_embeddings"],
        rope_theta=float(config["rope_theta"]),
        rope_factor=float(rope["factor"]),
        rope_beta_fast=float(rope["beta_fast"]),
        rope_beta_slow=float(rope["beta_slow"]),
        rope_mscale=float(rope["mscale"]),
        rope_mscale_all_dim=float(rope["mscale_all_dim"]),
        rope_original_max=rope["original_max_position_embeddings"],
        norm_eps=config["rms_norm_eps"])
    return {"model": "kimi_k2", "model_cfg": cfg, "net": KimiK2(cfg)}
