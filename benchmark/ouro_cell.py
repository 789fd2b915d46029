"""The Ouro configuration's code: its engine builder, as
`kimi_cell:kimi_engine` is Kimi's."""

from __future__ import annotations

# the file's keys at the only value the program computes: a file that asks
# for anything else is refused, never run as something it is not
COMPUTED_AS = {
    "model_type": "ouro", "hidden_act": "silu", "rope_scaling": None,
    "sliding_window": None, "use_sliding_window": False,
    "tie_word_embeddings": False, "early_exit_threshold": 1,
}


def ouro_engine(config: dict) -> dict:
    """`models/ouro.py` at the file's keys, for `LLMEngine`: the engine's
    `model` family, the `model_cfg` and the flax module that makes the
    weights. `early_exit_threshold` is run at 1 only (every token answers
    from the last pass): a pass count a lane is the scheduler's work."""
    import jax.numpy as jnp

    from ray_tpu.models.ouro import Ouro, OuroConfig

    wrong = {k: config.get(k, "absent") for k, v in COMPUTED_AS.items()
             if config.get(k, "absent") != v}
    if set(config["layer_types"]) != {"full_attention"} or \
            len(config["layer_types"]) != config["num_hidden_layers"]:
        wrong["layer_types"] = config["layer_types"]
    if wrong:
        raise RuntimeError(f"the file asks for what models/ouro.py does "
                           f"not compute: {wrong}")
    dtype = jnp.dtype(config["torch_dtype"])
    cfg = OuroConfig(
        dtype=dtype, param_dtype=dtype,
        vocab_size=config["vocab_size"],
        n_layer=config["num_hidden_layers"],
        n_pass=config["total_ut_steps"],
        exit_threshold=float(config["early_exit_threshold"]),
        n_head=config["num_attention_heads"],
        n_kv_head=config["num_key_value_heads"],
        d_model=config["hidden_size"],
        ffn_dim=config["intermediate_size"],
        max_seq_len=config["max_position_embeddings"],
        rope_theta=float(config["rope_theta"]),
        norm_eps=config["rms_norm_eps"])
    if cfg.head_dim != config["head_dim"]:
        raise RuntimeError("the model's head size is not the file's")
    return {"model": "ouro", "model_cfg": cfg, "net": Ouro(cfg)}
