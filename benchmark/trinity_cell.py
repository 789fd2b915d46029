"""The Trinity (AFMoE) configuration's code: its engine builder, as
`kimi_cell:kimi_engine` is Kimi's."""

from __future__ import annotations

# the file's keys at the only value the program computes: a file that asks
# for anything else is refused, never run as something it is not
COMPUTED_AS = {
    "model_type": "afmoe", "hidden_act": "silu", "score_func": "sigmoid",
    "route_norm": True, "n_group": 1, "topk_group": 1,
    "num_expert_groups": 1, "num_limited_groups": 1, "rope_scaling": None,
    "tie_word_embeddings": False, "mup_enabled": True,
}
LAYER_TYPES = {"sliding_attention": "sliding", "full_attention": "full"}


def afmoe_engine(config: dict) -> dict:
    """`models/afmoe.py` at the file's keys, for `LLMEngine`: the engine's
    `model` family, the `model_cfg` and the flax module that makes the
    weights. The experts held and the first of them are the chip's share
    (`num_experts`, `deployment_share.first_expert`); the router's width is
    the published count."""
    import jax.numpy as jnp

    from ray_tpu.models.afmoe import Afmoe, AfmoeConfig

    wrong = {k: config.get(k, "absent") for k, v in COMPUTED_AS.items()
             if config.get(k, "absent") != v}
    types = config["layer_types"]
    if len(types) != config["num_hidden_layers"] or \
            set(types) - set(LAYER_TYPES):
        wrong["layer_types"] = types
    if wrong:
        raise RuntimeError(f"the file asks for what models/afmoe.py does "
                           f"not compute: {wrong}")
    dtype = jnp.dtype(config["torch_dtype"])
    cfg = AfmoeConfig(
        dtype=dtype, param_dtype=dtype,
        vocab_size=config["vocab_size"],
        n_layer=config["num_hidden_layers"],
        n_dense_layer=config["num_dense_layers"],
        layer_types=tuple(LAYER_TYPES[t] for t in types),
        global_every=config["global_attn_every_n_layers"],
        window=config["sliding_window"],
        n_head=config["num_attention_heads"],
        n_kv_head=config["num_key_value_heads"],
        d_model=config["hidden_size"],
        head_dim=config["head_dim"],
        ffn_dim=config["intermediate_size"],
        moe_ffn_dim=config["moe_intermediate_size"],
        n_experts=config["published"]["num_experts"],
        experts_held=config["num_experts"],
        first_expert=config["deployment_share"]["first_expert"],
        top_k=config["num_experts_per_tok"],
        n_shared=config["num_shared_experts"],
        routed_scale=config["route_scale"],
        mup=config["mup_enabled"],
        max_seq_len=config["max_position_embeddings"],
        rope_theta=float(config["rope_theta"]),
        norm_eps=config["rms_norm_eps"])
    return {"model": "afmoe", "model_cfg": cfg, "net": Afmoe(cfg)}
