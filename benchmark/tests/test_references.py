"""The two plain references against the program's models at a tiny size on
the CPU: GPT-2 against `models/gpt.py` (logits, loss, one block's gradient
norm), Mistral against `models/llama.py` (prefill, then decode through
`PagedKVCache`). On the chip every benchmark run compares at published
widths: `train_cell._check` the loss and gradient norm, `BenchLLMDeployment.
bench_check` the tokens that greedy `generate` streams."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.references import gpt2 as ref_gpt2
from benchmark.references import mistral as ref_mistral
from benchmark.serve_cell import llama_engine

HERE = os.path.dirname(os.path.abspath(__file__))


def _config(name):
    with open(os.path.join(HERE, "data", "configs", f"{name}.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def gpt2_case():
    import flax.linen as nn

    from ray_tpu.models import GPT, GPTConfig

    config = _config("tiny-gpt2")
    cfg = GPTConfig(vocab_size=config["assumed"]["vocab_rows"],
                    n_layer=config["n_layer"], n_head=config["n_head"],
                    d_model=config["n_embd"],
                    max_seq_len=config["n_positions"], remat=False,
                    dtype=jnp.float32)
    model = GPT(cfg)
    tokens = np.random.default_rng(0).integers(
        0, config["vocab_size"], (2, 33), dtype=np.int32)
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    params = model.init(jax.random.PRNGKey(0), inputs)
    plain = nn.meta.unbox(params)["params"]
    return config, model, params, plain, inputs, targets


def test_gpt2_reference_logits_match_the_model(gpt2_case):
    config, model, params, plain, inputs, _ = gpt2_case
    with jax.default_matmul_precision("highest"):
        want = ref_gpt2.logits(plain, config, jnp.asarray(inputs))
        got = model.apply(params, inputs)
    # float32 on both sides: only the order of additions differs
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=1e-4)


def test_gpt2_reference_loss_and_block0_gradient_match_autodiff(gpt2_case):
    import flax.linen as nn

    from ray_tpu.models.gpt import cross_entropy_loss

    config, model, params, plain, inputs, targets = gpt2_case

    def loss_of(p):
        return cross_entropy_loss(model.apply(p, inputs), targets)

    with jax.default_matmul_precision("highest"):
        want_loss, grads = jax.value_and_grad(loss_of)(params)
        got_loss, got_norm = ref_gpt2.loss_and_block0_grad_norm(
            plain, config, jnp.asarray(inputs), jnp.asarray(targets))
    h0 = nn.meta.unbox(grads)["params"]["h0"]
    want_norm = np.sqrt(sum(float(jnp.sum(g ** 2))
                            for g in jax.tree_util.tree_leaves(h0)))
    assert float(got_loss) == pytest.approx(float(want_loss), rel=1e-5)
    assert float(got_norm) == pytest.approx(want_norm, rel=1e-4)


@pytest.fixture(scope="module")
def mistral_case():
    from ray_tpu.models.llama import Llama, unboxed_params

    config = _config("tiny-mistral")
    cfg = llama_engine(config)["model_cfg"]
    assert cfg.dtype == jnp.float32 and cfg.ffn_dim == 128
    params = Llama(cfg).init(jax.random.PRNGKey(1),
                             jnp.ones((1, 16), jnp.int32))
    ids = np.random.default_rng(1).integers(0, config["vocab_size"], 40)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref_mistral.logits(
            unboxed_params(params), config, jnp.asarray(ids, jnp.int32)))
    return config, cfg, params, ids, want


def test_mistral_reference_matches_the_model_forward(mistral_case):
    from ray_tpu.models.llama import Llama

    _, cfg, params, ids, want = mistral_case
    with jax.default_matmul_precision("highest"):
        got = Llama(cfg).apply(params, jnp.asarray(ids[None], jnp.int32))[0]
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=1e-4)


def test_mistral_prefill_then_paged_decode_match_the_reference(mistral_case):
    from ray_tpu.models import llama
    from ray_tpu.serve.llm.kv_cache import PagedKVCache

    config, cfg, params, ids, want = mistral_case
    n, steps, block = 30, 5, 16
    kv = PagedKVCache(8, cfg.n_layer, block, cfg.n_kv_head, cfg.head_dim,
                      dtype=np.float32)
    owner = object()
    pages = kv.alloc(kv.pages_for_tokens(n + steps), owner)
    toks = np.zeros((1, 32), np.int32)
    toks[0, :n] = ids[:n]
    with jax.default_matmul_precision("highest"):
        logits, k, v = llama.prefill_step(
            params, cfg, toks, np.asarray([n], np.int32))
        np.testing.assert_allclose(logits[0], want[n - 1], atol=2e-5,
                                   rtol=1e-4)
        kv.write_prefill(pages, np.asarray(k[0]), np.asarray(v[0]), n)
        table = np.zeros((1, 128 // block), np.int32)
        table[0, :len(pages)] = pages
        for j in range(steps):
            pos = n + j
            logits, nk, nv = llama.decode_step(
                params, cfg, np.asarray([ids[pos]], np.int32),
                np.asarray([pos], np.int32), kv.k_pages, kv.v_pages, table)
            kv.append(pages, pos, np.asarray(nk)[0], np.asarray(nv)[0])
            np.testing.assert_allclose(logits[0], want[pos], atol=2e-5,
                                       rtol=1e-4)
    kv.free(pages, owner)
