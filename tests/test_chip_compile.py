"""Compile the main path's programs for a described TPU v5e, with no chip.

The TPU compiler is installed beside jax and compiles for a topology that
is described, not attached (on-chip-measurement guide, section 2). It
refuses what interpret mode lets through — a slice off the tiling, too much
VMEM, a program over 16 GB — so these guard every later PR at no chip time.
Nothing runs: results and times come from `chip_smoke.py` on the chip.
"""

import math
import os
import re
from functools import partial
from unittest import mock

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import SingleDeviceSharding

from ray_tpu.models import llama
from ray_tpu.ops import flash_attention
from ray_tpu.parallel import ShardingStrategy

HBM_BYTES = 16 * 1024**3


def _on_the_tpu():
    """Code that asks `jax.default_backend()` sees cpu here and would take
    its CPU branch (`parallel.moe.expert_shard_layer`: `lax.ragged_dot`):
    inside this the expert families' programs are lowered as on the chip,
    with `ops.grouped_matmul`'s kernel."""
    return mock.patch.object(jax, "default_backend", lambda: "tpu")


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here: skip
        pytest.skip(f"cannot describe a v5e topology: {e}")
    # a compile for a described device is written to the persistent cache
    # but cannot be read back without a chip: keep these out of it
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


def _compile_flash(topo, q_shape, kv_heads, **blocks):
    """Forward and backward of the public wrapper, steered onto the kernel:
    it asks `jax.default_backend()`, which here still says cpu."""
    one_chip = SingleDeviceSharding(topo.devices[0])
    b, t, _h, d = q_shape
    q = jax.ShapeDtypeStruct(q_shape, jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((b, t, kv_heads, d), jnp.bfloat16,
                              sharding=one_chip)
    attend = partial(flash_attention, causal=True, **blocks)

    def loss(q, k, v):
        return attend(q, k, v).astype(jnp.float32).sum()

    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        fwd = jax.jit(attend).lower(q, kv, kv).compile()
        bwd = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
            q, kv, kv).compile()
    for compiled in (fwd, bwd):
        assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("q_shape,kv_heads,blocks", [
    ((16, 1024, 12, 64), 12, {}),                            # GPT-2 MHA
    ((16, 1024, 12, 64), 4, {}),                             # Llama GQA 12:4
    ((4, 4096, 12, 64), 12, {"block_q": 512, "block_k": 1024}),  # chunked KV
], ids=["mha_16x1024", "gqa_12to4_16x1024", "chunked_4x4096"])
def test_flash_kernel_compiles_for_v5e(topo, q_shape, kv_heads, blocks):
    _compile_flash(topo, q_shape, kv_heads, **blocks)


@pytest.mark.parametrize("rows, d, f, held", [
    (2048, 2048, 768, 128), (8192, 2048, 768, 128),     # SDAR pass, prefill
    (512, 2560, 768, 128), (8192, 2560, 768, 128),      # Ling decode, chunk
    (128, 7168, 2048, 12), (8192, 7168, 2048, 12),      # Kimi decode, chunk
    (128, 3072, 3072, 8), (4096, 3072, 3072, 8),        # Trinity
    (8, 7168, 2048, 12),                                # a bucket of one lane
], ids=["sdar_pass", "sdar_prefill", "ling_decode", "ling_chunk",
        "kimi_decode", "kimi_chunk", "trinity_decode", "trinity_chunk",
        "kimi_one_lane"])
def test_grouped_matmul_compiles_for_v5e(topo, rows, d, f, held):
    """Both grouped products of an expert layer, gate|up `[rows, d] x
    [held, d, 2f]` and down `[rows, f] x [held, f, d]`, at the shapes the
    four expert cells run: the kernel's blocks fit VMEM and its slices the
    tiling, with the grid's length a value of the program."""
    from ray_tpu.ops.grouped_matmul import grouped_matmul
    from ray_tpu.parallel import moe

    def product(lhs, rhs, group_sizes):
        walk = moe.tile_walk(*moe.group_tiles(group_sizes, rows), rows)
        return grouped_matmul(lhs, rhs, walk, tm=moe.ROW_TILE)

    one_chip = SingleDeviceSharding(topo.devices[0])
    for k, n in ((d, 2 * f), (f, d)):
        compiled = jax.jit(product).lower(
            jax.ShapeDtypeStruct((rows, k), jnp.bfloat16, sharding=one_chip),
            jax.ShapeDtypeStruct((held, k, n), jnp.bfloat16,
                                 sharding=one_chip),
            jax.ShapeDtypeStruct((held,), jnp.int32,
                                 sharding=one_chip)).compile()
        assert "tpu_custom_call" in compiled.as_text()
        assert f"bf16[{rows},{n}]" in compiled.as_text()


def test_llama_125m_decode_step_compiles_for_v5e(topo):
    """One decode bucket at `llama_125m` width in bf16 with the arena the
    engine sizes for eight running sequences (1,024 pages of 16 tokens)."""
    one_chip = SingleDeviceSharding(topo.devices[0])
    cfg = llama.LlamaConfig.llama_125m()
    batch, block = 8, 16
    pages_per_seq = cfg.max_seq_len // block

    def on_chip(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree_util.tree_map(
        lambda a: on_chip(a.shape, a.dtype),
        jax.eval_shape(llama.Llama(cfg).init, jax.random.PRNGKey(0),
                       jnp.ones((1, 16), jnp.int32)))
    pages = on_chip((batch * pages_per_seq, cfg.n_layer, block,
                     cfg.n_kv_head, cfg.head_dim), jnp.bfloat16)
    compiled = jax.jit(
        lambda v, tok, pos, k, vv, table: llama.decode_step(
            v, cfg, tok, pos, k, vv, table)
    ).lower(params, on_chip((batch,), jnp.int32), on_chip((batch,), jnp.int32),
            pages, pages, on_chip((batch, pages_per_seq), jnp.int32)).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.output_size_in_bytes \
        + mem.temp_size_in_bytes < HBM_BYTES


MISTRAL_7B_L20 = dict(
    dtype=jnp.bfloat16, param_dtype=jnp.bfloat16, vocab_size=32000,
    n_layer=20, n_head=32, n_kv_head=8, d_model=4096, ffn_mult=3.5,
    max_seq_len=2048)


def _fake_kv(arena, state=(), pools=None):
    """What the engine's program builders read of a `PagedKVCache`: the
    arrays, and where each kind of page has its own (one kind, here)."""
    import types

    return types.SimpleNamespace(
        arena=arena, state=state,
        pools=(types.SimpleNamespace(arrays=slice(0, len(arena))),)
        if pools is None else pools)


def _assert_returns_a_token_a_lane(compiled, lanes):
    """A decode program's first output is the tokens it chose, int32
    [lanes], and no row of logits is among its outputs."""
    first, *rest = jax.tree_util.tree_leaves(compiled.out_info)
    assert (first.dtype, first.shape) == (jnp.int32, (lanes,))
    assert not [a for a in rest if jnp.issubdtype(a.dtype, jnp.floating)
                and a.shape[:1] == (lanes,) and a.ndim == 2]


def _compile_engine_program(topo, cfg, kind, size, block=16, num_pages=2048):
    """The engine's own `llama` program of one kind and bucket (the model's
    step, then the scatter of its new K/V into the donated arena), compiled
    for the described chip. Returns (compiled, the arena's shape)."""
    import types

    from ray_tpu.serve.llm.engine import LLMEngine

    one_chip = SingleDeviceSharding(topo.devices[0])

    def on_chip(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree_util.tree_map(
        lambda a: on_chip(a.shape, a.dtype),
        jax.eval_shape(llama.Llama(cfg).init, jax.random.PRNGKey(0),
                       jnp.ones((1, 16), jnp.int32)))
    pages = on_chip((num_pages, cfg.n_layer, block, cfg.n_kv_head,
                     cfg.head_dim), jnp.bfloat16)
    engine = types.SimpleNamespace(
        _mod=llama, model_cfg=cfg, _step_counts=(),
        _state_in_place=False, kv=_fake_kv((pages, pages)))
    if kind == "decode":
        fn = LLMEngine._make_decode_fn(engine, size)
        args = (params, on_chip((size,)), on_chip((size,)), pages, pages,
                on_chip((size, cfg.max_seq_len // block)), on_chip((size,)),
                on_chip((size,)))
    else:
        fn = LLMEngine._make_prefill_fn(engine, size)
        args = (params, on_chip((1, size)), on_chip((1,)), pages, pages,
                on_chip((size,)), on_chip((size,)))
    return jax.jit(fn, donate_argnums=(3, 4)).lower(*args).compile(), \
        pages.shape


@pytest.mark.parametrize("kind, size", [("decode", 16), ("prefill", 2048)])
def test_engine_program_updates_the_donated_arena_in_place(topo, kind, size):
    """The engine's own program (the model's step, then the scatter of its
    new K/V into the donated arena) at the widths and the arena of the
    benchmark's serving cells (Mistral-7B's, 20 layers, 2,048 pages of 16
    tokens): both halves of the arena alias their outputs, and no
    operation copies or re-lays out an array of the arena's whole shape.
    (With heads of 64, `llama_125m`, the compiler does re-lay it out.)"""
    compiled, arena = _compile_engine_program(
        topo, llama.LlamaConfig(**MISTRAL_7B_L20), kind, size)
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == 2 * 2 * math.prod(arena)
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < HBM_BYTES
    shape = "bf16[" + ",".join(map(str, arena)) + "]"
    moved = [line.strip()[:120] for line in compiled.as_text().splitlines()
             if " copy(" in line and shape in line.split(" copy(")[0]]
    assert not moved, moved
    if kind == "decode":
        _assert_returns_a_token_a_lane(compiled, size)


def _scheduled(hlo_text):
    """The instructions outside every fused computation, one line each: the
    operations the device runs (and a trace names), not what a fusion
    computes on the way."""
    fused = set(re.findall(r"fusion\([^\n]*calls=(%[\w.\-]+)", hlo_text))
    comp = None
    for line in hlo_text.splitlines():
        opened = re.match(r"(?:ENTRY )?(%[\w.\-]+) \(.*\{\s*$", line)
        if opened:
            comp = opened.group(1)
        elif line.startswith("}"):
            comp = None
        elif comp is not None and comp not in fused and " = " in line:
            yield line.strip()


def _materialised(hlo_text):
    """(dtype, dims) of every array that an instruction outside a fused
    computation defines: what the program holds in memory."""
    out = []
    for line in _scheduled(hlo_text):
        head = line.split(" = ", 1)[1]
        head = head[:head.index(")") + 1] if head.startswith("(") \
            else head.split(" ", 1)[0]
        out += [(m.group(1), tuple(map(int, m.group(2).split(","))))
                for m in re.finditer(r"(\w+)\[([0-9,]+)\]", head)]
    return out


def _assert_a_trip_is_gathered_never_a_table(text, mem, lanes, table_pages,
                                             arena):
    """The absorbed path's walk over the cached latents (`kimi_k2.walk_latents`
    on `listed_walk`'s work list, PR 61) in a compiled decode program of
    `lanes` lanes over `arena` [pages, layers, block, row]: no array holds
    every slot of every lane's table (the parent's gathered latents `[lanes,
    slots, row]`, its scores and weights `[lanes, H, slots (+ 1)]`), the
    largest gather of pages is one trip's pairs' (the arena's own count of
    pages apart: where it has one layer, the program sees it as `[pages,
    block, row]`, a bitcast), and the program's temporaries are a small part
    of the arena, which the parent's gather was the size of."""
    from ray_tpu.models import kimi_k2

    num_pages, _, block, row = arena
    held = _materialised(text)
    slots = table_pages * block
    table_wide = [a for a in held if a[1] == (lanes, slots, row)
                  or (a[1][0] == lanes and a[1][-1] in (slots, slots + 1))]
    assert not table_wide, table_wide
    gathered = {a[1][0] for a in held if a[0] == "bf16"
                and a[1][1:] == (block, row)} - {num_pages}
    assert gathered and max(gathered) == kimi_k2.PAIRS_A_LANE * lanes * (
        kimi_k2.LATENT_BLOCK // block), gathered
    print(f"temp_size_in_bytes {mem.temp_size_in_bytes}, gathers of pages "
          f"{sorted(gathered)}")
    assert mem.temp_size_in_bytes < 2 * math.prod(arena) // 4


@pytest.mark.parametrize("widths", ["mistral_7b_l20", "llama_125m"])
def test_decode_program_reads_live_pages_once(topo, widths):
    """What the engine's decode-16 program holds in memory (the arena of
    2,048 pages of 16 tokens): `paged_attend` gathers a key block's pages
    by (page, layer) and scores the grouped query heads against K and V as
    they lie there, so no array is one layer of the whole arena
    (`[2048, 16, KVH, D]`: the per-layer slice cost a sixth of the device's
    time, at heads of 64 too), none carries a repeated head axis over
    cached keys (`[16, keys, KVH, H/KVH, D]`, `[16, keys, H, D]`, nor with a
    trip's 64 pairs in the lanes' place), and the largest over keys is one
    trip's gather: 64 (lane, key block) pairs of the work list, a block of
    64 keys. At Mistral's widths the program's temporaries are 0.04 GB (2.5
    GB before: every slot of every table row gathered, repeated four times
    and read twice)."""
    cfg = llama.LlamaConfig(**MISTRAL_7B_L20) if widths == "mistral_7b_l20" \
        else llama.LlamaConfig.llama_125m(dtype=jnp.bfloat16,
                                          param_dtype=jnp.bfloat16)
    size = 16
    compiled, (num_pages, _, block, kvh, d) = _compile_engine_program(
        topo, cfg, "decode", size)
    held = _materialised(compiled.as_text())
    h = cfg.n_head
    layer_slice = (num_pages, block, kvh, d)
    assert not [a for a in held if a[1] == layer_slice]
    # [B, G, R, D] is the accumulator: a key count is what is left of 16
    # and of the head counts
    width = llama.PAIRS_A_LANE * size
    repeated = [a for a in held if a[1][0] in (size, width) and (
        (len(a[1]) == 5 and a[1][2:] == (kvh, h // kvh, d))
        or (len(a[1]) == 4 and a[1][2:] == (h, d)))]
    assert not repeated, repeated
    over_keys = [a for a in held if a[1][0] == width and len(a[1]) >= 4
                 and a[1][-2:] == (kvh, d)]
    assert over_keys and max(a[1][1] for a in over_keys) \
        == llama.pair_block(h // kvh)
    if widths == "mistral_7b_l20":
        assert compiled.memory_analysis().temp_size_in_bytes < 128 * 2**20


@pytest.mark.parametrize("kind, size", [("decode", 16), ("chunk", 1024)])
def test_latent_arena_is_updated_in_place_at_kimi_k2_widths(topo, kind, size):
    """The engine's decode-16 and chunk-1,024 programs of the Kimi-K2 cell
    (published widths, 7 layers, 12 of 384 experts held, 8,192 pages of 16
    tokens): the one latent arena aliases its output, no operation copies
    or re-lays out an array of its shape, and the program fits the chip
    beside its 9.7 GB of weights. The row is 640 wide: at the latent's own
    576 (four and a half lane tiles) the compiler keeps the arena with the
    pages innermost and copies all of it to a rows-innermost layout and
    back around the scatter, which a tile of 1 shows. The chunk program
    walks the cached keys a block at a time: it holds no array over the
    table's 8,192 slots and the chunk's 1,024 together, and no float32
    array larger than a head group's scores against one key block. The
    decode program gathers a trip of its work list at a time (16 pairs of 40
    pages), never the lanes' tables (`[8192,16,640]` before PR 61)."""
    import types

    from ray_tpu.models import kimi_k2
    from ray_tpu.serve.llm.engine import LLMEngine

    one_chip = SingleDeviceSharding(topo.devices[0])
    cfg = kimi_k2.KimiK2Config(vocab_size=20480, n_layer=7, experts_held=12,
                               max_seq_len=8192)
    block, num_pages = 16, 8192

    def on_chip(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree_util.tree_map(
        lambda a: on_chip(a.shape, a.dtype),
        jax.eval_shape(kimi_k2.KimiK2(cfg).init, jax.random.PRNGKey(0),
                       jnp.ones((1, 16), jnp.int32)))

    def compile_at(cfg):
        (row,) = kimi_k2.cache_rows(cfg)
        pages = on_chip((num_pages, cfg.n_layer, block) + row, jnp.bfloat16)
        engine = types.SimpleNamespace(
            _mod=kimi_k2, model_cfg=cfg, _step_counts=kimi_k2.STEP_COUNTS,
            _state_in_place=False, kv=_fake_kv((pages,)))
        table = on_chip((size if kind == "decode" else 1,
                         cfg.max_seq_len // block))
        if kind == "decode":
            fn = LLMEngine._make_decode_fn(engine, size)
            args = (params, on_chip((size,)), on_chip((size,)), pages,
                    table, on_chip((size,)), on_chip((size,)))
        else:
            fn = LLMEngine._make_chunk_fn(engine, size)
            args = (params, on_chip((1, size)), on_chip((1,)), pages, table,
                    on_chip((1, size)), on_chip((1, size)))
        with _on_the_tpu():
            compiled = jax.jit(fn, donate_argnums=(3,)).lower(*args).compile()
        if kind == "decode":
            _assert_returns_a_token_a_lane(compiled, size)
        shape = "bf16[" + ",".join(map(str, pages.shape)) + "]"
        text = compiled.as_text()
        assert text.count("tpu_custom_call") >= 2 * (cfg.n_layer
                                                     - cfg.n_dense_layer)
        moved = [line.strip()[:120] for line in text.splitlines()
                 if " copy(" in line and shape in line.split(" copy(")[0]]
        return compiled.memory_analysis(), 2 * math.prod(pages.shape), \
            moved, text

    mem, arena_bytes, moved, text = compile_at(cfg)
    assert mem.alias_size_in_bytes == arena_bytes
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < HBM_BYTES
    assert not moved, moved
    if kind == "chunk":
        held = _materialised(text)
        scores = kimi_k2.HEAD_GROUP * size * kimi_k2.KEY_BLOCK
        assert ("f32", (kimi_k2.HEAD_GROUP, size, kimi_k2.KEY_BLOCK)) in held
        padded = cfg.max_seq_len + size
        assert not [a for a in held if padded in a[1]]
        assert str(padded) not in text
        assert not [a for a in held
                    if a[0] == "f32" and math.prod(a[1]) > scores]
    if kind == "decode":
        _assert_a_trip_is_gathered_never_a_table(
            text, mem, size, cfg.max_seq_len // block,
            (num_pages, cfg.n_layer, block, cfg.row_dim))
        with mock.patch.object(kimi_k2, "LANE_TILE", 1):
            assert kimi_k2.cache_rows(cfg) == ((576,),)
            _, _, moved, _ = compile_at(cfg)
        assert moved, "a 576-wide arena is in place now: drop the padding"


@pytest.mark.parametrize("kind, size", [("decode", 64), ("chunk", 1024)])
def test_sequence_state_arena_is_updated_in_place_at_ling_widths(topo, kind,
                                                                 size):
    """The engine's decode-64 and chunk-1,024 programs of the Ling hybrid
    cell (published widths, 7 layers of which one pages, 128 of 512 experts
    held, 32,768 pages of 16 tokens, 65 state slots): the latent arena and
    both sequence-state arrays alias their outputs, no operation copies or
    re-lays out an array of the state arena's or the latent arena's shape,
    and the program fits the chip beside its 10.5 GB of weights. The family
    updates the state arena itself (`STATE_IN_PLACE`, PR 55), and the
    decode-64 program walks it in slot order: nothing of the lanes' states'
    size stands beside the arena (no gather, no stack: 1.48 GB of
    temporaries fewer than the parent's program), and a KDA layer's update
    is ONE operation with ONE result, of the arena's type, which the
    benchmark's trace readers find by that type (a tuple there would turn
    them dark); both reductions are one more, and the `kda_path_*` readers'
    pattern takes exactly those two a layer. Its MLA layer gathers a trip of
    Kimi's work list at a time (64 pairs of 40 pages), never the lanes'
    tables (`[32768,16,640]` before PR 61)."""
    import types

    from ray_tpu.models import ling_hybrid
    from ray_tpu.serve.llm.engine import LLMEngine

    one_chip = SingleDeviceSharding(topo.devices[0])
    cfg = ling_hybrid.LingHybridConfig(
        vocab_size=39296, n_layer=7, n_dense_layer=1, experts_held=128,
        max_seq_len=8192)
    block, num_pages, slots = 16, 32768, 65

    def on_chip(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree_util.tree_map(
        lambda a: on_chip(a.shape, a.dtype),
        jax.eval_shape(ling_hybrid.LingHybrid(cfg).init,
                       jax.random.PRNGKey(0), jnp.ones((1, 16), jnp.int32)))
    (row,) = ling_hybrid.cache_rows(cfg)
    pages = on_chip((num_pages, ling_hybrid.paged_layers(cfg), block) + row,
                    jnp.bfloat16)
    state = tuple(on_chip((slots,) + shape, dtype)
                  for shape, dtype in ling_hybrid.seq_state(cfg))
    assert pages.shape[1] == 1 and state[0].shape == (65, 6, 32, 128, 128)
    engine = types.SimpleNamespace(
        _mod=ling_hybrid, model_cfg=cfg,
        _step_counts=ling_hybrid.STEP_COUNTS,
        _state_in_place=True, kv=_fake_kv((pages,), state))
    lanes = size if kind == "decode" else 1
    rows = (size,) if kind == "decode" else (1, size)
    fn = LLMEngine._make_decode_fn(engine, size) if kind == "decode" \
        else LLMEngine._make_chunk_fn(engine, size)
    args = (params, on_chip(rows), on_chip((lanes,)), pages, *state,
            on_chip((lanes, cfg.max_seq_len // block)), on_chip(rows),
            on_chip(rows), on_chip((lanes,)))
    with _on_the_tpu():
        compiled = jax.jit(fn, donate_argnums=(3, 4, 5)).lower(
            *args).compile()
    # both grouped products of the six expert layers are the kernel
    assert compiled.as_text().count("tpu_custom_call") \
        >= 2 * (cfg.n_layer - cfg.n_dense_layer)
    mem = compiled.memory_analysis()
    held = [2 * math.prod(pages.shape), 4 * math.prod(state[0].shape),
            2 * math.prod(state[1].shape)]
    # the tail's rows of bf16 are padded to whole tiles: 3 MB over
    assert sum(held) <= mem.alias_size_in_bytes < sum(held) + 2**22
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < HBM_BYTES
    text = compiled.as_text()
    for array, dtype in ((pages, "bf16"), (state[0], "f32")):
        shape = dtype + "[" + ",".join(map(str, array.shape)) + "]"
        moved = [line.strip()[:120] for line in text.splitlines()
                 if " copy(" in line and shape in line.split(" copy(")[0]]
        assert not moved, moved
    if kind == "chunk":
        return
    _assert_returns_a_token_a_lane(compiled, size)
    _assert_a_trip_is_gathered_never_a_table(
        text, mem, size, cfg.max_seq_len // block, pages.shape)
    held = _materialised(text)
    assert not [a for a in held if a[0] == "f32" and a[1] in (
        (size, 6, 32, 128, 128), (size, 32, 128, 128))]
    print(f"temp_size_in_bytes {mem.temp_size_in_bytes} "
          f"(the parent's program: {LING_DECODE_64_TEMP_BYTES_AT_PR_54})")
    assert mem.temp_size_in_bytes \
        <= LING_DECODE_64_TEMP_BYTES_AT_PR_54 - 10**9
    # the operations the KDA trace readers match, as the chip names them:
    # `kda_step_*` / `kda_device_pct` (the accepted pair: the arena's type)
    # and `kda_path_*` (PR 55: the whole path, update and reductions)
    import json

    from benchmark import trace_reduce

    def pattern_of(metric):
        with open(os.path.join(os.path.dirname(os.path.dirname(
                os.path.abspath(__file__))), "benchmark", "layer_metrics",
                metric + ".reason-wide.json")) as f:
            return re.compile(json.load(f)["args"]["pattern"])

    by_type = pattern_of("kda_step_hbm_roofline_pct")
    path = pattern_of("kda_path_hbm_roofline_pct")
    assert path.pattern == pattern_of("kda_path_device_pct").pattern
    arena_type = "f32[" + ",".join(map(str, state[0].shape)) + "]"
    made = [line for line in _scheduled(text)
            if re.search(r"\) fusion\(|\} fusion\(", line)]
    updates = [line for line in made
               if arena_type in line.split(" fusion(")[0]]
    assert len(updates) == len(cfg.kda_layers), updates
    for line in updates:
        kind_of = trace_reduce.op_name(line)
        # one result, and every reader sees it
        assert kind_of.endswith(" " + arena_type) and by_type.match(kind_of) \
            and path.match(kind_of), kind_of
    # the reductions S'^T k and S'^T q, off one read of the old state: one
    # operation a layer, which the path's readers see (the accepted pair's
    # pattern, written for the parent's program, does not: PERF.md section 7)
    reductions = [trace_reduce.op_name(line) for line in made
                  if "dot_general" in line and "kda_step" in line]
    print("kda_step reductions:", sorted(set(reductions)))
    assert len(reductions) == len(cfg.kda_layers), reductions
    assert all(path.match(k) for k in reductions), reductions
    # and nothing else of the program is the path's: not the 1 MB rows that
    # put the lanes' inputs in slot order
    seen = [k for k in map(trace_reduce.op_name, made) if path.match(k)]
    assert len(seen) == 2 * len(cfg.kda_layers), seen


# `temp_size_in_bytes` of the decode-64 program above on the parent of PR 55
# (a4d277e: the lanes' states gathered [64,6,32,128,128], the new ones
# stacked, the stack scattered by the engine), compiled here the same way
LING_DECODE_64_TEMP_BYTES_AT_PR_54 = 2_458_232_832


@pytest.mark.parametrize("kind, size", [("decode", 64), ("prefill", 1024),
                                        ("chunk", 1024)])
def test_block_programs_fit_the_chip_at_sdar_widths(topo, kind, size):
    """The engine's decode-64 (one block of 4 a lane), prefill-1,024 and
    chunk-1,024 programs of the SDAR-MoE cell (published widths, 7 layers,
    all 128 experts, the whole vocabulary, 8,256 pages of 16 tokens): the
    K/V arena aliases its outputs and no operation copies an array of its
    shape, the program fits the chip beside its 9.97 GB of weights, the
    block pass gathers a trip of its work list at a time and holds no
    temporary of an arena array's size, its logits are float32 [lanes, 4,
    vocabulary] inside the program (it returns two [lanes, 4] arrays), and a
    prefill computes no head at all."""
    import types

    from ray_tpu.models import sdar_moe
    from ray_tpu.serve.llm.engine import LLMEngine

    one_chip = SingleDeviceSharding(topo.devices[0])
    cfg = sdar_moe.SdarMoeConfig(n_layer=7, max_seq_len=4096)
    block, num_pages = 16, 8256

    def on_chip(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree_util.tree_map(
        lambda a: on_chip(a.shape, a.dtype),
        jax.eval_shape(sdar_moe.SdarMoe(cfg).init, jax.random.PRNGKey(0),
                       jnp.ones((1, 16), jnp.int32)))
    arena = tuple(on_chip((num_pages, cfg.n_layer, block) + row, jnp.bfloat16)
                  for row in sdar_moe.cache_rows(cfg))
    engine = types.SimpleNamespace(
        _mod=sdar_moe, model_cfg=cfg, _step_counts=sdar_moe.STEP_COUNTS,
        _state_in_place=False, kv=_fake_kv(arena))
    table = on_chip((size if kind == "decode" else 1,
                     cfg.max_seq_len // block))
    if kind == "decode":
        rows = (size, cfg.block_length)
        fn = LLMEngine._make_block_decode_fn(engine, size)
        args = (params, on_chip(rows), on_chip((size,)), *arena, table,
                on_chip(rows), on_chip(rows), on_chip((size,), jnp.bool_))
    elif kind == "prefill":
        fn = LLMEngine._make_prefill_fn(engine, size)
        args = (params, on_chip((1, size)), on_chip((1,)), *arena,
                on_chip((size,)), on_chip((size,)))
    else:
        fn = LLMEngine._make_chunk_fn(engine, size)
        args = (params, on_chip((1, size)), on_chip((1,)), *arena, table,
                on_chip((1, size)), on_chip((1, size)))
    with _on_the_tpu():
        compiled = jax.jit(fn, donate_argnums=(3, 4)).lower(*args).compile()
    mem = compiled.memory_analysis()
    held = sum(2 * math.prod(a.shape) for a in arena)
    assert held <= mem.alias_size_in_bytes < held + 2**22
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < HBM_BYTES
    text = compiled.as_text()
    # both grouped products of every layer are the kernel (a prefill and a
    # chunk compute no head: the last layer keeps its K and V and no expert)
    assert text.count("tpu_custom_call") \
        >= 2 * (cfg.n_layer - (kind != "decode"))
    shape = "bf16[" + ",".join(map(str, arena[0].shape)) + "]"
    moved = [line.strip()[:120] for line in text.splitlines()
             if " copy(" in line and shape in line.split(" copy(")[0]]
    assert not moved, moved
    import re
    if kind == "decode":
        logits = f"[{size},{cfg.block_length},{cfg.vocab_size}]"
        assert "f32" + logits in text and "bf16" + logits not in text
        # the walk over the cached keys (`block_attend`'s work list, PR 59):
        # the arena is read as rows of [block x K/V heads, head] (a bitcast,
        # or `moved` above would show, and no temporary of an arena array's
        # size is held), and a trip gathers its 64 pairs' 12 pages each,
        # never a lane's whole table of 256
        assert mem.temp_size_in_bytes < held // 2
        row = f"{block * cfg.n_kv_head},{cfg.head_dim}"
        assert f"bf16[{num_pages * cfg.n_layer},{row}]" in text
        gathered = {int(n) for n in re.findall(
            rf"bf16\[(\d+),(?:{row}|{block},{cfg.n_kv_head},"
            rf"{cfg.head_dim})\]", text)} - {num_pages * cfg.n_layer}
        assert gathered and max(gathered) == 64 * 12, gathered
    else:
        # no array as wide as the vocabulary but the embedding
        assert set(re.findall(rf"\w+\[[\d,]*{cfg.vocab_size}[\d,]*\]", text)) \
            == {f"bf16[{cfg.vocab_size},{cfg.d_model}]"}


def _compile_program_by_kinds(topo, mod, net, cfg, kind, size, block,
                              num_pages, lanes_max=32):
    """The engine's program of one kind and bucket for a family whose pages
    are of a window kind and a full kind (`mod.page_kinds`): the full kind's
    `num_pages`, the window kind's `lanes_max` rings, every array bfloat16
    and donated, lowered as on the chip and compiled for the described one.
    Returns (compiled, the arena's shapes)."""
    import types

    from ray_tpu.serve.llm.engine import LLMEngine

    one_chip = SingleDeviceSharding(topo.devices[0])

    def on_chip(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree_util.tree_map(
        lambda a: on_chip(a.shape, a.dtype),
        jax.eval_shape(net(cfg).init, jax.random.PRNGKey(0),
                       jnp.ones((1, 16), jnp.int32)))
    ring = cfg.window // block + 1
    pages = {"window": lanes_max * ring, "full": num_pages}
    width = {"window": ring, "full": cfg.max_seq_len // block}
    arena, pools, kinds = (), (), mod.page_kinds(cfg)
    for name, layers, rows, _ in kinds:
        pools += (types.SimpleNamespace(
            arrays=slice(len(arena), len(arena) + len(rows))),)
        arena += tuple(on_chip((pages[name], layers, block) + row,
                               jnp.bfloat16) for row in rows)
    engine = types.SimpleNamespace(
        _mod=mod, model_cfg=cfg, _step_counts=mod.STEP_COUNTS,
        _state_in_place=False, kv=_fake_kv(arena, pools=pools))
    lanes = size if kind == "decode" else 1
    rows = (size,) if kind == "decode" else (1, size)
    if kind == "prefill":
        fn = LLMEngine._make_prefill_fn(engine, size)
        coords = [on_chip((size,)) for _ in kinds for _ in range(2)]
    else:
        fn = LLMEngine._make_decode_fn(engine, size) if kind == "decode" \
            else LLMEngine._make_chunk_fn(engine, size)
        coords = [c for name, *_ in kinds for c in (
            on_chip((lanes, width[name])), on_chip(rows), on_chip(rows))]
    args = (params, on_chip(rows if kind != "prefill" else (1, size)),
            on_chip((lanes,)), *arena, *coords)
    with _on_the_tpu():
        compiled = jax.jit(fn, donate_argnums=tuple(
            range(3, 3 + len(arena)))).lower(*args).compile()
    return compiled, [a.shape for a in arena]


def _assert_arena_in_place(compiled, shapes):
    """Every array of the arena (bfloat16, `shapes`) aliases its output, no
    operation copies one, and the program fits the chip. Returns the
    program's text."""
    mem = compiled.memory_analysis()
    held = sum(2 * math.prod(shape) for shape in shapes)
    assert held <= mem.alias_size_in_bytes < held + 2**22
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < HBM_BYTES
    text = compiled.as_text()
    assert "tpu_custom_call" in text    # the grouped products' kernel
    for shape in set(shapes):
        shape = "bf16[" + ",".join(map(str, shape)) + "]"
        moved = [line.strip()[:120] for line in text.splitlines()
                 if " copy(" in line and shape in line.split(" copy(")[0]]
        assert not moved, moved
    return text


@pytest.mark.parametrize("kind, size", [("decode", 32), ("prefill", 1024),
                                        ("chunk", 1024)])
def test_window_and_full_programs_fit_the_chip_at_trinity_widths(topo, kind,
                                                                 size):
    """The engine's decode-32, prefill-1,024 and chunk-1,024 programs of the
    Trinity cell (published widths, 9 layers, 8 of 256 experts, an eighth of
    the vocabulary; the window kind's 8,224 pages of 7 layers and the full
    kind's 16,384 of 2): both kinds' K/V arrays alias their outputs and no
    operation copies an array of their shapes, and the program fits the
    chip beside its 5.76 GB of weights."""
    import json

    from benchmark.trinity_cell import afmoe_engine
    from ray_tpu.models import afmoe

    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "benchmark", "configs",
            "trinity-large-ep32-l9.json")) as f:
        config = json.load(f)
    cfg = afmoe_engine(config)["model_cfg"]
    compiled, shapes = _compile_program_by_kinds(
        topo, afmoe, afmoe.Afmoe, cfg, kind, size,
        config["engine"]["block_size"], config["engine"]["num_pages"])
    assert shapes == [(8224, 7, 16, 8, 128)] * 2 \
        + [(16384, 2, 16, 8, 128)] * 2
    _assert_arena_in_place(compiled, shapes)
    if kind == "decode":
        _assert_returns_a_token_a_lane(compiled, size)


@pytest.mark.parametrize("kind, size", [("decode", 16), ("prefill", 384),
                                        ("chunk", 1024)])
def test_looped_programs_fit_the_chip_at_ouro_widths(topo, kind, size):
    """The engine's decode-16, prefill-384 and chunk-1,024 programs of the
    Ouro cell (published widths, all 48 layers and the whole vocabulary, four
    passes a token; the file's pages of 192 page layers): K and V alias
    their outputs, no operation copies an array of the arena's shape (a
    slice of it by a traced page layer did, twice 3.9 GB, in the chunk's
    first draft), and the program fits the chip beside its 5.34 GB of
    weights (the chunk's 2.9 GB of temporaries are 1.6 GB of new rows and
    their way into the scatter: it is the tightest of the three)."""
    import json
    import types

    from benchmark.ouro_cell import ouro_engine
    from ray_tpu.models import ouro
    from ray_tpu.serve.llm.engine import LLMEngine

    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "benchmark", "configs",
            "ouro-2.6b.json")) as f:
        config = json.load(f)
    one_chip = SingleDeviceSharding(topo.devices[0])
    cfg = ouro_engine(config)["model_cfg"]
    block = config["engine"]["block_size"]

    def on_chip(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree_util.tree_map(
        lambda a: on_chip(a.shape, a.dtype),
        jax.eval_shape(ouro.Ouro(cfg).init, jax.random.PRNGKey(0),
                       jnp.ones((1, 16), jnp.int32)))
    pages = on_chip((config["engine"]["num_pages"], ouro.paged_layers(cfg),
                     block, cfg.n_kv_head, cfg.head_dim), jnp.bfloat16)
    assert pages.shape[1:] == (192, 16, 16, 128)
    engine = types.SimpleNamespace(
        _mod=ouro, model_cfg=cfg, _step_counts=ouro.STEP_COUNTS,
        _state_in_place=False, kv=_fake_kv((pages, pages)))
    if kind == "prefill":
        fn = LLMEngine._make_prefill_fn(engine, size)
        args = (params, on_chip((1, size)), on_chip((1,)), pages, pages,
                on_chip((size,)), on_chip((size,)))
    else:
        lanes = size if kind == "decode" else 1
        rows = (size,) if kind == "decode" else (1, size)
        fn = LLMEngine._make_decode_fn(engine, size) if kind == "decode" \
            else LLMEngine._make_chunk_fn(engine, size)
        args = (params, on_chip(rows), on_chip((lanes,)), pages, pages,
                on_chip((lanes, cfg.max_seq_len // block)), on_chip(rows),
                on_chip(rows))
    compiled = jax.jit(fn, donate_argnums=(3, 4)).lower(*args).compile()
    mem = compiled.memory_analysis()
    held = 2 * 2 * math.prod(pages.shape)
    assert held <= mem.alias_size_in_bytes < held + 2**22
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < HBM_BYTES
    text = compiled.as_text()
    shape = "bf16[" + ",".join(map(str, pages.shape)) + "]"
    moved = [line.strip()[:120] for line in text.splitlines()
             if " copy(" in line and shape in line.split(" copy(")[0]]
    assert not moved, moved
    if kind == "decode":    # a step's own temporaries: the gathered block
        assert mem.temp_size_in_bytes < 256 * 2**20
        _assert_returns_a_token_a_lane(compiled, size)


@pytest.mark.parametrize("kind, size", [("decode", 16), ("chunk", 1024),
                                        ("prefill", 512)])
def test_retention_state_arena_is_updated_in_place_at_brumby_widths(topo,
                                                                    kind,
                                                                    size):
    """The engine's decode-16, chunk-1,024 and prefill-512 programs of the
    Brumby cell (published widths, 8 layers, the whole vocabulary, no page
    kind, 17 state slots of 273 MB): both arrays of the state arena alias
    their outputs, no operation copies an array of the arena's shape and NO
    TEMPORARY IS OF ITS SIZE (the steps return the arena itself; the decode
    step slices a lane's state out of it and writes its successor back, a
    lane at a time: 9 MB of temporaries beside 4.6 GB of states), and the
    program with its arguments is under 15.0 GB beside 8.4 GB of weights."""
    import json
    import types

    from benchmark.brumby_cell import brumby_engine
    from ray_tpu.models import brumby
    from ray_tpu.serve.llm.engine import LLMEngine

    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "benchmark", "configs",
            "brumby-14b-l8.json")) as f:
        config = json.load(f)
    one_chip = SingleDeviceSharding(topo.devices[0])
    cfg = brumby_engine(config)["model_cfg"]
    slots = config["engine"]["max_running"] + 1

    def on_chip(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree_util.tree_map(
        lambda a: on_chip(a.shape, a.dtype),
        jax.eval_shape(brumby.Brumby(cfg).init, jax.random.PRNGKey(0),
                       jnp.ones((1, 16), jnp.int32)))
    state = tuple(on_chip((slots,) + shape, dtype)
                  for shape, dtype in brumby.seq_state(cfg))
    assert brumby.page_kinds(cfg) == ()
    assert state[0].shape == (17, 8, 8, 8256, 128)
    engine = types.SimpleNamespace(
        _mod=brumby, model_cfg=cfg, _step_counts=brumby.STEP_COUNTS,
        _state_in_place=brumby.STATE_IN_PLACE,
        kv=_fake_kv((), state, pools=()))
    lanes = size if kind == "decode" else 1
    rows = {"decode": (size,), "chunk": (1, size), "prefill": (size,)}[kind]
    fn = getattr(LLMEngine, f"_make_{kind}_fn")(engine, size)
    tokens = on_chip((size,) if kind == "decode" else (1, size))
    # after the arena: the rows that are tokens (no page says), the slots
    args = (params, tokens, on_chip((lanes,)), *state,
            on_chip(rows, jnp.bool_), on_chip((lanes,)))
    compiled = jax.jit(fn, donate_argnums=(3, 4)).lower(*args).compile()
    mem = compiled.memory_analysis()
    held = sum(4 * math.prod(a.shape) for a in state)
    assert 4.6e9 < held <= mem.alias_size_in_bytes < held + 2**22
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.0e9
    # a lane's state, a block's phi(q): nothing near a fifth of the arena
    assert mem.temp_size_in_bytes < held / 5
    text = compiled.as_text()
    shape = "f32[" + ",".join(map(str, state[0].shape)) + "]"
    moved = [line.strip()[:120] for line in text.splitlines()
             if " copy(" in line and shape in line.split(" copy(")[0]]
    assert not moved, moved
    # no array of [lanes, layers, ...] states beside the arena
    assert f"f32[{lanes},8,8,8256,128]" not in text
    if kind == "decode":
        assert mem.temp_size_in_bytes < 64 * 2**20
        _assert_returns_a_token_a_lane(compiled, size)
    else:       # one row of logits a sequence, not a window's
        first = jax.tree_util.tree_leaves(compiled.out_info)[0]
        assert first.shape == (1, cfg.vocab_size)


@pytest.mark.parametrize("kind, size", [("decode", 32), ("prefill", 1024),
                                        ("chunk", 1024)])
def test_two_kinds_of_rows_fit_the_chip_at_mimo_widths(topo, kind, size):
    """The engine's decode-32, prefill-1,024 and chunk-1,024 programs of the
    MiMo cell (published widths, 7 layers, 16 of 256 experts, an eighth of
    the vocabulary; the window kind's 288 pages of 5 layers at rows of 1,536
    and 1,024, the full kind's 32,768 of 2 at rows of 768 and 512): all four
    arrays alias their outputs, no operation copies an array of their shapes
    and NO TEMPORARY IS OF AN ARRAY'S SIZE (with rows of [4, 192], which the
    chip's tiles of (8, 128) do not hold, every program re-laid the full
    kind's K array out and back: 2.2 GB of temporaries a decode step, 4.3 GB
    a chunk; `mimo_v2.cache_row`), and the program fits the chip beside its
    6.86 GB of weights. The patterns of the cell's three device-share
    metrics match every float operation under their scope, as the chip
    names it, and no operation under another scope."""
    import json

    from benchmark import trace_reduce
    from benchmark.mimo_cell import mimo_engine
    from ray_tpu.models import mimo_v2

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "mimo-v2.5-ep16-l7.json")) as f:
        config = json.load(f)
    cfg = mimo_engine(config)["model_cfg"]
    compiled, shapes = _compile_program_by_kinds(
        topo, mimo_v2, mimo_v2.MimoV2, cfg, kind, size,
        config["engine"]["block_size"], config["engine"]["num_pages"])
    assert shapes == [(288, 5, 16, 1536), (288, 5, 16, 1024),
                      (32768, 2, 16, 768), (32768, 2, 16, 512)]
    text = _assert_arena_in_place(compiled, shapes)
    # the smallest of the full kind's arrays is 1.07 GB
    assert compiled.memory_analysis().temp_size_in_bytes < 0.5e9
    if kind == "decode":
        _assert_returns_a_token_a_lane(compiled, size)
    # the trace readers' patterns against the operations as the chip names
    # them, sorted by the named scope their metadata carries

    def pattern_of(metric):
        with open(os.path.join(root, "benchmark", "layer_metrics",
                               metric + ".long-agent.json")) as f:
            return re.compile(json.load(f)["args"]["pattern"])

    patterns = {"attn_window": pattern_of("attn_window_device_pct"),
                "attn_full": pattern_of("attn_full_device_pct"),
                "moe_experts": pattern_of("moe_experts_device_pct")}
    assert patterns["attn_full"].pattern \
        == pattern_of("attn_full_hbm_roofline_pct").pattern
    scopes = ("attn_window", "attn_full", "moe_experts", "moe_route",
              "dense_mlp", "lm_head")
    by_scope = {scope: set() for scope in scopes}
    kernels = set()
    for line in _scheduled(text):
        if not re.search(r"[)}\]] (fusion|copy|custom-call|reshape|transpose)\(",
                         line):
            continue
        name = trace_reduce.op_name(line)
        if 'custom_call_target="tpu_custom_call"' in line:
            kernels.add(name)
        where = re.search(r'op_name="([^"]*)"', line)
        for scope in scopes:
            if where and f"/{scope}/" in where.group(1) + "/":
                by_scope[scope].add(name)
    assert kernels and all(patterns["moe_experts"].search(k)
                           for k in kernels), kernels
    for scope in ("attn_window", "attn_full"):
        # what the walk computes: float arrays; a row of [lanes, 64] (the
        # sinks' and the output's, a shape the projections share) is not
        # told from theirs and is left to them, as is a fusion of several
        # results (a tuple), which holds another scope's work too
        floats = {k for k in by_scope[scope]
                  if re.search(r"^\S+ (bf16|f32)\[", k)
                  and not re.search(r"f32\[(\d+,)?64\]$", k)}
        assert floats, scope
        unseen = {k for k in floats if not patterns[scope].search(k)}
        assert not unseen, (scope, unseen)
    for scope, pattern in patterns.items():
        for other in scopes:
            stray = {k for k in by_scope[other] if pattern.search(k)}
            assert other == scope or not stray, (scope, other, stray)


def test_build_mesh_on_tpu_follows_the_topology(topo):
    """On TPU devices `build_mesh` takes `create_device_mesh`'s assignment
    (a 2x2 torus orders the ring 0,1,3,2), never a plain reshape."""
    mesh = ShardingStrategy(fsdp=2, tp=2).build_mesh(topo.devices)
    assert dict(mesh.shape) == {"fsdp": 2, "tp": 2}
    assert [[d.id for d in row] for row in mesh.devices] == [[0, 1], [3, 2]]
    with pytest.raises(Exception):  # noqa: B017 — whatever jax raises
        # three of four chips form no mesh the topology knows
        ShardingStrategy(dp=3).build_mesh(topo.devices[:3])
