"""The MiMo-V2 configuration's code: its engine builder, as
`trinity_cell:afmoe_engine` is Trinity's."""

from __future__ import annotations

# the file's keys at the only value the program computes: a file that asks
# for anything else is refused, never run as something it is not
COMPUTED_AS = {
    "model_type": "mimo_v2", "hidden_act": "silu", "scoring_func": "sigmoid",
    "topk_method": "noaux_tc", "norm_topk_prob": True, "n_group": 1,
    "topk_group": 1, "n_shared_experts": None,
    "routed_scaling_factor": None, "tie_word_embeddings": False,
    "attention_bias": False, "attention_projection_layout": "fused_qkv",
    "add_swa_attention_sink_bias": True,
    "add_full_attention_sink_bias": False,
    "rope_scaling": {"rope_type": "default", "type": "default"},
    # read by nothing in the layer's equations (`assumed` in the file)
    "attention_chunk_size": 128, "hybrid_block_size": None,
}
# pairs of keys the program holds to one value: one query width and one
# window serve both kinds of layer
SAME = (("swa_num_attention_heads", "num_attention_heads"),
        ("swa_head_dim", "head_dim"), ("swa_v_head_dim", "v_head_dim"),
        ("sliding_window_size", "sliding_window"))
LAYER_TYPES = {0: "full", 1: "window"}


def mimo_engine(config: dict) -> dict:
    """`models/mimo_v2.py` at the file's keys, for `LLMEngine`: the engine's
    `model` family, the `model_cfg` and the flax module that makes the
    weights. The experts held and the first of them are the chip's share
    (`n_routed_experts`, `deployment_share.first_expert`); the router's
    width is the published count."""
    import jax.numpy as jnp

    from ray_tpu.models.mimo_v2 import MimoV2, MimoV2Config

    wrong = {k: config.get(k, "absent") for k, v in COMPUTED_AS.items()
             if config.get(k, "absent") != v}
    wrong.update({a: config.get(a, "absent") for a, b in SAME
                  if config.get(a, "absent") != config[b]})
    layers = config["num_hidden_layers"]
    pattern, moe = config["hybrid_layer_pattern"], config["moe_layer_freq"]
    if len(pattern) != layers or set(pattern) - set(LAYER_TYPES):
        wrong["hybrid_layer_pattern"] = pattern
    # leading dense layers, then experts: what `n_dense_layer` can say
    n_dense = moe.index(1) if 1 in moe else len(moe)
    if len(moe) != layers or moe != [0] * n_dense + [1] * (layers - n_dense):
        wrong["moe_layer_freq"] = moe
    if wrong:
        raise RuntimeError(f"the file asks for what models/mimo_v2.py does "
                           f"not compute: {wrong}")
    dtype = jnp.dtype(config["torch_dtype"])
    cfg = MimoV2Config(
        dtype=dtype, param_dtype=dtype,
        vocab_size=config["vocab_size"],
        n_layer=layers,
        layer_types=tuple(LAYER_TYPES[t] for t in pattern),
        n_dense_layer=n_dense,
        window=config["sliding_window"],
        n_head=config["num_attention_heads"],
        n_kv_head=config["num_key_value_heads"],
        n_kv_head_window=config["swa_num_key_value_heads"],
        d_model=config["hidden_size"],
        head_dim=config["head_dim"],
        v_head_dim=config["v_head_dim"],
        rope_dim=int(config["partial_rotary_factor"] * config["head_dim"]),
        rope_theta=float(config["rope_theta"]),
        rope_theta_window=float(config["swa_rope_theta"]),
        value_scale=config["attention_value_scale"],
        ffn_dim=config["intermediate_size"],
        moe_ffn_dim=config["moe_intermediate_size"],
        n_experts=config["published"]["n_routed_experts"],
        experts_held=config["n_routed_experts"],
        first_expert=config["deployment_share"]["first_expert"],
        top_k=config["num_experts_per_tok"],
        max_seq_len=config["max_position_embeddings"],
        norm_eps=config["layernorm_epsilon"])
    return {"model": "mimo_v2", "model_cfg": cfg, "net": MimoV2(cfg)}
