"""The Brumby configuration's code: its engine builder, as
`ouro_cell:ouro_engine` is Ouro's."""

from __future__ import annotations

# the file's keys at the only value the program computes: a file that asks
# for anything else is refused, never run as something it is not
COMPUTED_AS = {
    "model_type": "brumby", "hidden_act": "silu", "attention_bias": False,
    "rope_scaling": None, "sliding_window": None,
    "use_sliding_window": False, "tie_word_embeddings": False,
}
# the readings the config has no key for, at the only value computed
ASSUMED_AS = {"retention_degree": 2, "state_dtype": "float32"}


def brumby_engine(config: dict) -> dict:
    """`models/brumby.py` at the file's keys, for `LLMEngine`: the engine's
    `model` family, the `model_cfg` and the flax module that makes the
    weights."""
    import jax.numpy as jnp

    from ray_tpu.models.brumby import Brumby, BrumbyConfig

    wrong = {k: config.get(k, "absent") for k, v in COMPUTED_AS.items()
             if config.get(k, "absent") != v}
    assumed = config["assumed"]
    wrong.update({k: assumed.get(k, "absent") for k, v in ASSUMED_AS.items()
                  if assumed.get(k, "absent") != v})
    if wrong:
        raise RuntimeError(f"the file asks for what models/brumby.py does "
                           f"not compute: {wrong}")
    dtype = jnp.dtype(config["torch_dtype"])
    cfg = BrumbyConfig(
        dtype=dtype, param_dtype=dtype,
        vocab_size=config["vocab_size"],
        n_layer=config["num_hidden_layers"],
        n_head=config["num_attention_heads"],
        n_kv_head=config["num_key_value_heads"],
        d_model=config["hidden_size"],
        head_dim=config["head_dim"],
        ffn_dim=config["intermediate_size"],
        max_seq_len=config["max_position_embeddings"],
        rope_theta=float(config["rope_theta"]),
        norm_eps=config["rms_norm_eps"],
        retention_eps=float(assumed["retention_eps"]))
    if cfg.state_dim != assumed["state_dim"]:
        raise RuntimeError("the state's D is not the file's")
    return {"model": "brumby", "model_cfg": cfg, "net": Brumby(cfg)}
