"""The SDAR-MoE configuration's code: its engine builder, as
`ling_cell:ling_engine` is Ling's."""

from __future__ import annotations

# the file's keys at the only value the program computes: a file that asks
# for anything else is refused, never run as something it is not
COMPUTED_AS = {
    "model_type": "sdar_moe", "hidden_act": "silu", "attention_bias": False,
    "decoder_sparse_step": 1, "mlp_only_layers": [], "norm_topk_prob": True,
    "rope_scaling": None, "sliding_window": None,
    "use_sliding_window": False, "tie_word_embeddings": False,
}
GENERATION_AS = {"remasking": "low_confidence_static"}


def sdar_engine(config: dict) -> dict:
    """`models/sdar_moe.py` at the file's keys, for `LLMEngine`: the
    engine's `model` family, the `model_cfg` and the flax module that makes
    the weights. Every expert is held here; block length, passes a block
    and the mask token are the file's `generation` and reach the engine
    through the model's config, never as engine options."""
    import jax.numpy as jnp

    from ray_tpu.models.sdar_moe import SdarMoe, SdarMoeConfig

    gen = config["generation"]
    wrong = {k: config.get(k, "absent") for k, v in COMPUTED_AS.items()
             if config.get(k, "absent") != v}
    wrong.update({f"generation.{k}": gen.get(k) for k, v in
                  GENERATION_AS.items() if gen.get(k) != v})
    if gen["block_length"] % gen["denoise_steps"] or \
            config["engine"]["block_size"] % gen["block_length"]:
        wrong["generation.block_length"] = gen["block_length"]
    if not 0 <= gen["mask_token_id"] < config["vocab_size"]:
        wrong["generation.mask_token_id"] = gen["mask_token_id"]
    if wrong:
        raise RuntimeError(f"the file asks for what models/sdar_moe.py does "
                           f"not compute: {wrong}")
    dtype = jnp.dtype(config["torch_dtype"])
    cfg = SdarMoeConfig(
        dtype=dtype, param_dtype=dtype,
        vocab_size=config["vocab_size"],
        n_layer=config["num_hidden_layers"],
        n_head=config["num_attention_heads"],
        n_kv_head=config["num_key_value_heads"],
        d_model=config["hidden_size"],
        head_dim=config["head_dim"],
        moe_ffn_dim=config["moe_intermediate_size"],
        n_experts=config["num_experts"],
        experts_held=config["num_experts"], first_expert=0,
        top_k=config["num_experts_per_tok"],
        max_seq_len=config["max_position_embeddings"],
        rope_theta=float(config["rope_theta"]),
        norm_eps=config["rms_norm_eps"],
        block_length=gen["block_length"],
        denoise_steps=gen["denoise_steps"],
        mask_token=gen["mask_token_id"])
    return {"model": "sdar_moe", "model_cfg": cfg, "net": SdarMoe(cfg)}
