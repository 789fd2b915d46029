"""Model zoo: GPT + ResNet forward/backward, sharded end-to-end on the
8-device mesh with DP/FSDP/TP rules applied from logical annotations."""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from ray_tpu.models import GPT, GPTConfig, ResNet, ResNetConfig
from ray_tpu.models.gpt import count_params, cross_entropy_loss
from ray_tpu.parallel import (ShardingStrategy, logical_axis_rules,
                              tracing_for)


def test_gpt_forward_loss():
    cfg = GPTConfig.tiny()
    model = GPT(cfg)
    tokens = jnp.ones((2, 16), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), tokens)
    logits = model.apply(params, tokens)
    assert logits.shape == (2, 16, cfg.vocab_size)
    loss = cross_entropy_loss(logits, tokens)
    # Roughly -log(1/vocab) at init.
    assert 4.0 < float(loss) < 8.0


def test_gpt_param_count_125m():
    cfg = GPTConfig.gpt2_125m()
    model = GPT(cfg)
    params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.ones((1, 8), jnp.int32))
    )
    n = sum(int(np.prod(p.shape)) for p in jax.tree_util.tree_leaves(params))
    assert 120e6 < n < 170e6  # 124M + padded vocab


def _run_sharded_step(strategy):
    """One pjit train step under DP / DP+FSDP / DP+FSDP+TP; loss must agree
    across strategies (same math, different shardings)."""
    cfg = GPTConfig.tiny(dtype=jnp.float32, remat=False)
    model = GPT(cfg)
    mesh = strategy.build_mesh()
    rules = logical_axis_rules(strategy)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0,
                                cfg.vocab_size)

    with tracing_for(mesh), nn.logical_axis_rules(rules):
        params = model.init(jax.random.PRNGKey(0), tokens)
        tx = optax.adamw(1e-3)
        opt_state = tx.init(params)

        @jax.jit
        def step(params, opt_state, tokens):
            def loss_fn(p):
                logits = model.apply(p, tokens[:, :-1])
                return cross_entropy_loss(logits, tokens[:, 1:])

            loss, grads = jax.value_and_grad(loss_fn)(params)
            updates, opt_state = tx.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
            return params, opt_state, loss

        params, opt_state, loss1 = step(params, opt_state, tokens)
        _, _, loss2 = step(params, opt_state, tokens)
    assert float(loss2) < float(loss1)  # it learns
    return float(loss1)


@pytest.mark.parametrize("strategy", [
    ShardingStrategy(dp=8),
    ShardingStrategy(dp=2, fsdp=4),
    ShardingStrategy(dp=2, fsdp=2, tp=2),
])
def test_gpt_sharded_train_step(strategy):
    _run_sharded_step(strategy)


def test_strategies_agree_on_loss():
    losses = [
        _run_sharded_step(ShardingStrategy(dp=8)),
        _run_sharded_step(ShardingStrategy(dp=2, fsdp=2, tp=2)),
    ]
    np.testing.assert_allclose(losses[0], losses[1], rtol=1e-4)


def test_resnet_forward_backward():
    cfg = ResNetConfig.resnet18(num_classes=10, small_images=True,
                                dtype=jnp.float32)
    model = ResNet(cfg)
    imgs = jnp.ones((4, 32, 32, 3))
    labels = jnp.array([0, 1, 2, 3])
    variables = model.init(jax.random.PRNGKey(0), imgs, train=False)

    def loss_fn(params):
        logits, updates = model.apply(
            {"params": params, "batch_stats": variables["batch_stats"]},
            imgs, train=True, mutable=["batch_stats"],
        )
        onehot = jax.nn.one_hot(labels, 10)
        return -jnp.mean(jnp.sum(onehot * jax.nn.log_softmax(logits), -1))

    loss, grads = jax.value_and_grad(loss_fn)(variables["params"])
    assert float(loss) > 0
    gnorm = sum(float(jnp.abs(g).sum())
                for g in jax.tree_util.tree_leaves(grads))
    assert gnorm > 0


def test_llama_forward_loss():
    from ray_tpu.models import Llama, LlamaConfig

    cfg = LlamaConfig.tiny(dtype=jnp.float32)
    model = Llama(cfg)
    tokens = jnp.ones((2, 16), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), tokens)
    logits = model.apply(params, tokens)
    assert logits.shape == (2, 16, cfg.vocab_size)
    loss = cross_entropy_loss(logits, tokens)
    assert 4.0 < float(loss) < 8.0


def test_llama_gqa_kv_heads_shrink_params():
    """GQA: fewer KV heads -> smaller fused QKV kernel than MHA."""
    from ray_tpu.models import Llama, LlamaConfig

    def qkv_features(n_kv):
        cfg = LlamaConfig.tiny(dtype=jnp.float32, n_kv_head=n_kv)
        model = Llama(cfg)
        shapes = jax.eval_shape(
            lambda: model.init(jax.random.PRNGKey(0),
                               jnp.ones((1, 8), jnp.int32)))
        kernel = shapes["params"]["layer0"]["attn_qkv"]["kernel"]
        return jax.tree_util.tree_leaves(kernel)[0].shape[-1]

    assert qkv_features(2) < qkv_features(4)  # 4 == n_head -> MHA


def test_llama_rope_rotation_properties():
    """RoPE preserves norms and is position-dependent."""
    from ray_tpu.models.llama import apply_rope, rope_tables

    cos, sin = rope_tables(32, 8, 10000.0)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 32, 2, 8))
    y = apply_rope(x, cos, sin)
    np.testing.assert_allclose(
        np.linalg.norm(np.asarray(x), axis=-1),
        np.linalg.norm(np.asarray(y), axis=-1), rtol=1e-5)
    # position 0 is the identity rotation
    np.testing.assert_allclose(np.asarray(y[:, 0]), np.asarray(x[:, 0]),
                               rtol=1e-6)
    assert not np.allclose(np.asarray(y[:, 1]), np.asarray(x[:, 1]))


@pytest.mark.parametrize("strategy", [
    ShardingStrategy(dp=2, fsdp=2, tp=2),
])
def test_llama_sharded_train_step(strategy):
    from ray_tpu.models import Llama, LlamaConfig

    cfg = LlamaConfig.tiny(dtype=jnp.float32, remat=False)
    model = Llama(cfg)
    mesh = strategy.build_mesh()
    rules = logical_axis_rules(strategy)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0,
                                cfg.vocab_size)
    with tracing_for(mesh), nn.logical_axis_rules(rules):
        params = model.init(jax.random.PRNGKey(0), tokens)
        tx = optax.adamw(1e-3)
        opt_state = tx.init(params)

        @jax.jit
        def step(params, opt_state, tokens):
            def loss_fn(p):
                logits = model.apply(p, tokens[:, :-1])
                return cross_entropy_loss(logits, tokens[:, 1:])
            loss, grads = jax.value_and_grad(loss_fn)(params)
            updates, opt_state = tx.update(grads, opt_state, params)
            return optax.apply_updates(params, updates), opt_state, loss

        params, opt_state, loss1 = step(params, opt_state, tokens)
        _, _, loss2 = step(params, opt_state, tokens)
    assert float(loss2) < float(loss1)


def test_vit_forward_backward():
    from ray_tpu.models import ViT, ViTConfig

    cfg = ViTConfig.tiny(dtype=jnp.float32)
    model = ViT(cfg)
    imgs = jax.random.normal(jax.random.PRNGKey(0), (4, 32, 32, 3))
    labels = jnp.array([0, 1, 2, 3])
    params = model.init(jax.random.PRNGKey(1), imgs)
    logits = model.apply(params, imgs)
    assert logits.shape == (4, cfg.num_classes)

    def loss_fn(p):
        lg = model.apply(p, imgs)
        onehot = jax.nn.one_hot(labels, cfg.num_classes)
        return -jnp.mean(jnp.sum(onehot * jax.nn.log_softmax(lg), -1))

    loss, grads = jax.value_and_grad(loss_fn)(params)
    assert np.isfinite(float(loss))
    gnorm = sum(float(jnp.abs(g).sum())
                for g in jax.tree_util.tree_leaves(grads))
    assert gnorm > 0


def test_moe_gpt_forward_and_aux_loss():
    from ray_tpu.models import MoEGPT, MoEGPTConfig
    from ray_tpu.models.moe_gpt import total_aux_loss

    cfg = MoEGPTConfig.tiny(dtype=jnp.float32, remat=False)
    model = MoEGPT(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(0), (2, 16), 0,
                                cfg.vocab_size)
    variables = model.init(jax.random.PRNGKey(1), tokens)
    logits, aux_vars = model.apply(variables, tokens,
                                   mutable=["moe_aux_loss"])
    assert logits.shape == (2, 16, cfg.vocab_size)
    aux = total_aux_loss(aux_vars)
    # Switch aux loss is ~1.0-ish at uniform routing, scaled by coeff
    assert 0 < float(aux) < 1.0
    # expert params exist with a leading num_experts axis
    k = variables["params"]["h0"]["moe"]["experts_up"]
    assert jax.tree_util.tree_leaves(k)[0].shape[0] == cfg.num_experts


def test_moe_gpt_expert_sharded_train_step():
    """MoE decoder trains under dp x ep sharding: expert params placed
    over the ep axis (GSPMD all_to_all dispatch), loss decreases."""
    from ray_tpu.models import MoEGPT, MoEGPTConfig
    from ray_tpu.models.moe_gpt import total_aux_loss

    strategy = ShardingStrategy(dp=2, ep=4)
    cfg = MoEGPTConfig.tiny(dtype=jnp.float32, remat=False)
    model = MoEGPT(cfg)
    mesh = strategy.build_mesh()
    rules = logical_axis_rules(strategy)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0,
                                cfg.vocab_size)
    with tracing_for(mesh), nn.logical_axis_rules(rules):
        variables = model.init(jax.random.PRNGKey(0), tokens)
        params = variables["params"]
        tx = optax.adamw(1e-3)
        opt_state = tx.init(params)

        @jax.jit
        def step(params, opt_state, tokens):
            def loss_fn(p):
                logits, aux_vars = model.apply(
                    {"params": p}, tokens[:, :-1],
                    mutable=["moe_aux_loss"])
                return (cross_entropy_loss(logits, tokens[:, 1:])
                        + total_aux_loss(aux_vars))
            loss, grads = jax.value_and_grad(loss_fn)(params)
            updates, opt_state = tx.update(grads, opt_state, params)
            return optax.apply_updates(params, updates), opt_state, loss

        params, opt_state, loss1 = step(params, opt_state, tokens)
        _, _, loss2 = step(params, opt_state, tokens)
    assert float(loss2) < float(loss1)


def test_chunked_cross_entropy_matches_dense():
    """Blockwise LM-head loss == full-logits loss (incl. a non-divisible
    tail chunk and ignore_index masking)."""
    from ray_tpu.models import GPT, GPTConfig
    from ray_tpu.models.gpt import chunked_cross_entropy

    cfg = GPTConfig.tiny(dtype=jnp.float32)
    model = GPT(cfg)
    rng = np.random.default_rng(0)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 34)))
    targets = toks[:, 1:].at[0, 5].set(-1)  # masked position
    params = model.init(jax.random.PRNGKey(0), toks[:, :-1])
    dense = cross_entropy_loss(model.apply(params, toks[:, :-1]), targets)
    hidden, wte = model.apply(params, toks[:, :-1], return_hidden=True)
    chunked = chunked_cross_entropy(hidden, wte, targets, chunk_size=8)
    np.testing.assert_allclose(float(dense), float(chunked), rtol=1e-5)
    # gradients must match too (scan backward correctness)
    g1 = jax.grad(lambda p: cross_entropy_loss(
        model.apply(p, toks[:, :-1]), targets))(params)
    g2 = jax.grad(lambda p: chunked_cross_entropy(
        *model.apply(p, toks[:, :-1], return_hidden=True), targets,
        chunk_size=8))(params)
    for a, b in zip(jax.tree_util.tree_leaves(g1),
                    jax.tree_util.tree_leaves(g2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-6)


# --------------------------------------------------------------------------
# BERT-family bidirectional encoder
# --------------------------------------------------------------------------

def test_bert_encoder_is_bidirectional():
    """Changing a LATER token must change an EARLIER position's hidden
    state (a causal decoder would leave it untouched)."""
    from ray_tpu.models import BertConfig, BertEncoder

    cfg = BertConfig.tiny(remat=False)
    enc = BertEncoder(cfg)
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (1, 16)))
    params = enc.init(jax.random.PRNGKey(0), tokens)
    h1, _ = enc.apply(params, tokens)
    tokens2 = tokens.at[0, 12].set((int(tokens[0, 12]) + 1)
                                   % cfg.vocab_size)
    h2, _ = enc.apply(params, tokens2)
    # position 3 sees position 12 through bidirectional attention
    assert float(jnp.abs(h1[0, 3] - h2[0, 3]).max()) > 0


def test_bert_mlm_trains():
    """80/10/10 corruption + fused-CE MLM loss decreases, and the loss
    only scores masked positions (ignore_index contract)."""
    import optax

    from ray_tpu.models import (BertConfig, BertEncoder, mask_tokens,
                                mlm_loss)

    cfg = BertConfig.tiny(remat=False)
    enc = BertEncoder(cfg)
    rng = np.random.default_rng(1)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size - 1, (4, 32)))
    mask_id = cfg.vocab_size - 1
    corrupted, targets = mask_tokens(
        tokens, jax.random.PRNGKey(0), mask_token_id=mask_id,
        vocab_size=cfg.vocab_size)
    assert int((targets >= 0).sum()) > 0           # some positions masked
    assert int((targets >= 0).sum()) < targets.size  # not all
    params = enc.init(jax.random.PRNGKey(0), corrupted)
    tx = optax.adam(1e-3)
    opt_state = tx.init(params)

    @jax.jit
    def step(params, opt_state):
        loss, grads = jax.value_and_grad(
            lambda p: mlm_loss(enc, p, corrupted, targets))(params)
        updates, opt_state = tx.update(grads, opt_state)
        return optax.apply_updates(params, updates), opt_state, loss

    params, opt_state, first = step(params, opt_state)
    for _ in range(25):
        params, opt_state, loss = step(params, opt_state)
    assert float(loss) < float(first)


def test_bert_shards_like_the_decoders():
    """The encoder carries the same logical axes, so DP/TP sharding
    applies unchanged (outputs equal across strategies)."""
    import flax.linen as nn

    from ray_tpu.models import BertConfig, BertEncoder
    from ray_tpu.parallel import logical_axis_rules

    cfg = BertConfig.tiny(remat=False)
    enc = BertEncoder(cfg)
    rng = np.random.default_rng(2)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (4, 16)))
    params = enc.init(jax.random.PRNGKey(0), tokens)
    ref, _ = enc.apply(params, tokens)

    strategy = ShardingStrategy(dp=2, tp=2)
    mesh = strategy.build_mesh(jax.devices()[:4])
    with tracing_for(mesh), \
            nn.logical_axis_rules(logical_axis_rules(strategy)):
        out, _ = jax.jit(lambda p, t: enc.apply(p, t))(params, tokens)
    # bf16 activations reassociate differently under tp sharding
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=5e-2, rtol=5e-2)


def test_families_share_layers_by_public_names_alone():
    """A family file composes `models/layers.py`; what it takes from another
    family it takes by a public name (`from ...<family> import _name` and
    `<family>._name` made one family everyone's library, PR 52), `layers.py`
    imports no family, the families that have nothing of Kimi's do not
    import it, and a registry row stays (module, net, config): what a family
    leaves in the cache is its module's own names, read by
    `engine._family_cache`."""
    import ast
    import dataclasses
    import pathlib

    import ray_tpu.models
    from ray_tpu.serve.llm.engine import MODEL_FAMILIES, ModelFamily

    root = pathlib.Path(ray_tpu.models.__file__).parent
    package = "ray_tpu.models"
    bad, imports = [], {}
    for path in sorted(root.glob("*.py")):
        tree = ast.parse(path.read_text())
        siblings = {}       # local name -> the sibling module it stands for
        for node in ast.walk(tree):
            if isinstance(node, ast.ImportFrom) and node.module \
                    and node.module.startswith(package):
                if node.module == package:      # from ray_tpu.models import x
                    siblings.update({a.asname or a.name: a.name
                                     for a in node.names})
                    continue
                imports.setdefault(path.stem, set()).add(
                    node.module[len(package) + 1:])
                bad += [f"{path.name}:{node.lineno} from {node.module} "
                        f"import {a.name}" for a in node.names
                        if a.name.startswith("_")]
            elif isinstance(node, ast.Import):
                siblings.update({
                    a.asname: a.name[len(package) + 1:] for a in node.names
                    if a.asname and a.name.startswith(package + ".")})
        imports.setdefault(path.stem, set()).update(siblings.values())
        bad += [f"{path.name}:{node.lineno} {node.value.id}.{node.attr}"
                for node in ast.walk(tree)
                if isinstance(node, ast.Attribute)
                and isinstance(node.value, ast.Name)
                and node.value.id in siblings and node.attr.startswith("_")]
    assert not bad, bad
    assert imports["layers"] == set()
    for family in ("sdar_moe", "afmoe", "ouro"):
        assert "kimi_k2" not in imports[family], (family, imports[family])
    assert {row.module.rsplit(".", 1)[1] for row in MODEL_FAMILIES.values()} \
        <= set(imports)
    assert [f.name for f in dataclasses.fields(ModelFamily)] \
        == ["module", "net", "config"]


def test_gpt_call_sites_above_the_flash_kernel_keep_their_lines():
    """A Pallas kernel's serialized body carries the file and LINE of every
    Python frame that reaches it, and that body is part of the training
    step's lowered text, so of its compile-cache key (found in PR 52: with
    `_dense` moved out of `gpt.py` the step of `gpt2-medium.pretrain`, the
    cell with the flash kernel, took a new key though no op had moved). The
    two frames of `models/gpt.py` on the way to `ops.flash_attention` stand
    where they stood on bdee1a2; a PR that moves them recompiles that cell
    (78 MB, 40 s of its first `setup_s`) and changes these numbers on
    purpose."""
    import inspect

    from ray_tpu.models import gpt

    def line_of(fn, text):
        lines, first = inspect.getsourcelines(fn)
        (at,) = [i for i, line in enumerate(lines) if text in line]
        return first + at

    assert line_of(gpt.Block.__call__, "attend(q, k, v)") == 111
    assert line_of(gpt.GPT.__call__, "(x, deterministic)") == 173
