"""The Ling hybrid configuration's code: its engine builder, as
`kimi_cell:kimi_engine` is Kimi's."""

from __future__ import annotations

# the file's keys at the only value the program computes: a file that asks
# for anything else is refused, never run as something it is not
COMPUTED_AS = {
    "score_function": "sigmoid", "topk_method": "noaux_tc",
    "hidden_act": "silu", "q_lora_rank": None, "use_qk_norm": True,
    "use_mla_nope": False, "kda_safe_gate": True, "no_kda_lora": True,
    "use_kda_lora": False, "linear_silu": True, "group_norm_size": 1,
    "num_kv_heads_for_linear_attn": 0, "use_nGPT": False,
    "gated_attention_proj_granularity_type": "head_wise",
    "scale_router_input": False, "value_norm": False, "up_proj_norm": False,
    "norm_topk_prob": True, "moe_router_enable_expert_bias": True,
    "tie_word_embeddings": False, "partial_rotary_factor": 0.5,
}


def ling_engine(config: dict) -> dict:
    """`models/ling_hybrid.py` at the file's keys, for `LLMEngine`: the
    engine's `model` family, the `model_cfg` and the flax module that makes
    the weights. The experts held and the first of them are the chip's
    share (`num_experts`, `deployment_share.first_expert`); the router's
    width is the published count."""
    import jax.numpy as jnp

    from ray_tpu.models.ling_hybrid import LingHybrid, LingHybridConfig

    wrong = {k: config.get(k) for k, v in COMPUTED_AS.items()
             if config.get(k) != v}
    layers = config["num_hidden_layers"]
    if any(config["expert_swiglu_limit_list"][:layers]) or \
            any(config["share_expert_swiglu_limit_list"][:layers]):
        wrong["swiglu_limit"] = "a clamp on a layer that is kept"
    if config["rotary_dim"] != config["qk_rope_head_dim"] or \
            config["head_dim"] != config["v_head_dim"] or \
            config["num_key_value_heads"] != config["num_attention_heads"]:
        wrong["heads"] = "rotary_dim, head_dim or num_key_value_heads"
    if config["moe_shared_expert_intermediate_size"] != \
            config["moe_intermediate_size"]:
        wrong["moe_shared_expert_intermediate_size"] = \
            config["moe_shared_expert_intermediate_size"]
    if wrong:
        raise RuntimeError(f"the file asks for what models/ling_hybrid.py "
                           f"does not compute: {wrong}")
    dtype = jnp.dtype(config["torch_dtype"])
    cfg = LingHybridConfig(
        dtype=dtype, param_dtype=dtype,
        vocab_size=config["vocab_size"],
        n_layer=layers,
        n_dense_layer=config["first_k_dense_replace"],
        layer_group_size=config["layer_group_size"],
        n_head=config["num_attention_heads"],
        d_model=config["hidden_size"],
        head_dim=config["head_dim"],
        conv_width=config["short_conv_kernel_size"],
        kda_lower_bound=float(config["kda_lower_bound"]),
        kv_lora_rank=config["kv_lora_rank"],
        qk_nope_dim=config["qk_nope_head_dim"],
        qk_rope_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"],
        ffn_dim=config["intermediate_size"],
        moe_ffn_dim=config["moe_intermediate_size"],
        n_experts=config["published"]["num_experts"],
        experts_held=config["num_experts"],
        first_expert=config["deployment_share"]["first_expert"],
        top_k=config["num_experts_per_tok"],
        n_group=config["n_group"], topk_group=config["topk_group"],
        n_shared=config["num_shared_experts"],
        routed_scale=config["routed_scaling_factor"],
        max_seq_len=config["max_position_embeddings"],
        rope_theta=float(config["rope_theta"]),
        norm_eps=config["rms_norm_eps"])
    return {"model": "ling_hybrid", "model_cfg": cfg, "net": LingHybrid(cfg)}
