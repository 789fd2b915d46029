"""Flash attention as a Pallas TPU kernel.

Why: XLA's dense softmax attention materialises the [B, H, T, T] score
tensor in HBM (f32: ~800 MB per layer at B=16, T=1024) and walks it
several times (mask, max, exp, sum, divide, then again in the backward).
At GPT-2 shapes that makes attention bandwidth-bound at ~15% of peak.
This kernel streams Q blocks and K/V chunks through VMEM with an online
softmax — scores never exist in HBM, in either direction.

Design notes (see /opt/skills/guides/pallas_guide.md):
- forward grid = (batch, heads, num_q_blocks, num_kv_chunks); the last
  grid dim is innermost-sequential on TPU, so the online-softmax state
  (running max / sum / output accumulator) lives in VMEM scratch across
  a Q block's KV chunks and flushes once.
- **Causal chunk skipping** (round-3 change; the round-2 kernel executed
  fully-masked blocks on the claim that skipping cost more than it
  saved — false at long context, where the masked upper triangle is
  ~half the FLOPs): a KV chunk entirely above the diagonal skips ALL its
  compute via `pl.when` — only its (overlapped, ~free) DMA remains. At
  T=4096 this removes ~45% of attention FLOPs; the same predicate trims
  the backward. Work per Q block now scales with its causal KV range,
  not T.
- Chunked KV also removes the old whole-K/V-in-VMEM residency, so the
  T <= 4096 kernel cap is gone: VMEM per step is O(block_q*d + block_k*d),
  independent of T.
- Softmax statistics are f32 on the VPU; all matmuls (Q@K^T, P@V, and
  the grad contractions) run on the MXU with preferred_element_type=f32.
- The backward recomputes P per chunk from the forward's per-row
  logsumexp (a [B, H, T, 1] side output — the trailing singleton exists
  because a [1,1,block_q] block fails the TPU (8,128) tiling rule on its
  last two dims) — two kernels, one accumulating dQ over KV chunks, one
  accumulating dK/dV over Q blocks (and over the query-head group for
  GQA, by folding heads-in-group into the innermost grid dim). The
  softmax-jacobian rowsum delta = rowsum(dO*O) is precomputed once as an
  XLA prologue, so O never streams through the kernels.

Reference parity: fcas/ray has no TPU attention kernel; its model-side
equivalent is torch F.scaled_dot_product_attention (flash backend) used
by its model code. API matches `full_attention` in
ray_tpu/parallel/ring_attention.py so models can swap it in untouched.
"""

from __future__ import annotations

import collections
import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30
_LANES = 128  # stats scratch is [block_q, _LANES]; only column 0 is real

# Which path `flash_attention` took, counted per call of the wrapper (under
# jit that is once per trace, not per step): "pallas" is the kernel, "dense"
# the XLA fallback. A caller that must be on the kernel reads this after
# building its step (chip_smoke.py fails when "dense" moved).
_PATH_CALLS = collections.Counter()


def path_calls() -> dict:
    return {"pallas": _PATH_CALLS["pallas"], "dense": _PATH_CALLS["dense"]}


def _pick_block_q(t: int) -> int:
    for cand in (512, 256, 128):
        if t % cand == 0:
            return cand
    return 0  # caller falls back to the XLA path


def _pick_block_k(t: int) -> int:
    """Measured policy (GPT-2 125M on v5e, tok/s, same session):
    at T=1024 whole-KV wins (117.7k vs 108.2k for bk=512 — chunking
    overhead beats the 25% causal skip at short context); at T=4096 the
    r5 sweep measured bq=512: bk=1024 74.1k > bk=2048 72.7k > bk=512
    63.9k — finer chunks skip more of the upper triangle (executed
    cols 20480 vs 24576 of 18432 useful) until per-chunk overhead wins.
    So: whole-KV up to 2048, chunks of 1024 beyond.
    """
    if t <= 2048:
        return t
    for cand in (1024, 512, 256, 128):
        if t % cand == 0:
            return cand
    return 0


# f32 [block_q, block_k] temporaries (s, p, ds, dp live together in the
# backward) put a hard product cap on the block pair: 1024x2048 was
# measured to overflow the 16 MB VMEM scoped allocation
_MAX_BLOCK_PRODUCT = 512 * 2048


def _chunk_scores(q, k, scale, causal, qi, ki, block_q, block_k):
    """[bq, bk] f32 masked scores of one Q block vs one KV chunk."""
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale
    if causal:
        q_pos = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 0)
        k_pos = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        s = jnp.where(q_pos >= k_pos, s, _NEG_INF)
    return s


def _run_causal(run_pred, body):
    """Run `body(masked=True)` under the chunk-skip predicate. A
    diagonal/below-diagonal mask split was tried in r5 (mask-free body
    for chunks strictly below the diagonal): consistently SLOWER
    end-to-end (73.5k vs 74.7k tok/s at T=4096, A/B in one session) —
    the duplicated pl.when bodies cost more than the iota+where mask
    they avoid, so every running chunk takes the masked path."""

    @pl.when(run_pred)
    def _():
        body(masked=True)


# --------------------------------------------------------------------------
# forward
# --------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_s, l_s, acc_s, *,
                scale, causal, block_q, block_k):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _():
        m_s[...] = jnp.full_like(m_s, _NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    def body(masked):
        q = q_ref[0, 0, :, :]
        k = k_ref[0, 0, :, :]
        v = v_ref[0, 0, :, :]
        s = _chunk_scores(q, k, scale, masked, qi, ki, block_q, block_k)
        m_prev = m_s[:, :1]                                   # [bq, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, 1, keepdims=True))
        corr = jnp.exp(m_prev - m_new)                        # [bq, 1]
        p = jnp.exp(s - m_new)                                # [bq, bk]
        l_new = l_s[:, :1] * corr + jnp.sum(p, 1, keepdims=True)
        acc_s[...] = acc_s[...] * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)               # [bq, d]
        m_s[...] = jnp.broadcast_to(m_new, m_s.shape)
        l_s[...] = jnp.broadcast_to(l_new, l_s.shape)

    if causal:
        # causal chunk skip: a KV chunk starting past this Q block's
        # last row is fully masked — no compute (this is where the
        # long-context FLOPs go from O(T^2) to O(T^2/2))
        run = ki * block_k <= qi * block_q + block_q - 1
        _run_causal(run, body)
    else:
        body(masked=False)

    @pl.when(ki == nk - 1)
    def _():
        l = l_s[:, :1]
        o_ref[0, 0, :, :] = (acc_s[...] / l).astype(o_ref.dtype)
        lse_ref[0, 0, :, :] = m_s[:, :1] + jnp.log(l)


# --------------------------------------------------------------------------
# single-chunk specializations (block_k == T)
#
# When the whole K/V fits one chunk (the <= 2048-token hot path — GPT-2
# T=1024 trains here), the online-softmax machinery is pure overhead:
# per-step stat broadcasts into [bq, 128] lanes, the correction
# exp/multiply, and scratch init/flush cost ~9% end-to-end (measured
# r2->r3: 129.0k -> 117.2k tok/s/chip). These kernels do the plain
# one-pass softmax over [bq, T] scores instead — no scratch, no
# correction — while still emitting the logsumexp the shared chunked
# backward structure expects.
# --------------------------------------------------------------------------

def _fwd_single_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *,
                       scale, causal, block_q, block_k):
    qi = pl.program_id(2)
    q = q_ref[0, 0, :, :]
    k = k_ref[0, 0, :, :]
    v = v_ref[0, 0, :, :]
    s = _chunk_scores(q, k, scale, causal, qi, 0, block_q, block_k)
    m = jnp.max(s, axis=1, keepdims=True)                     # [bq, 1]
    p = jnp.exp(s - m)                                        # [bq, T]
    l = jnp.sum(p, axis=1, keepdims=True)                     # [bq, 1]
    o = jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)                   # [bq, d]
    o_ref[0, 0, :, :] = (o / l).astype(o_ref.dtype)
    lse_ref[0, 0, :, :] = m + jnp.log(l)


def _bwd_single_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                       dq_ref, dk_ref, dv_ref, dk_s, dv_s, *,
                       scale, causal, block_q, block_k, group):
    # grid = (b, h, nq): ONE fused pass produces dq (written per step)
    # and dk/dv (accumulated in [T, d] scratch across a KV head's whole
    # query-head group x Q blocks, flushed once per KV head) — the
    # scores/probabilities are computed ONCE and q/k/v/do stream through
    # VMEM once, where split dq/dkv kernels would pay both twice.
    hi = pl.program_id(1)
    qi = pl.program_id(2)
    nq = pl.num_programs(2)

    @pl.when((qi == 0) & (hi % group == 0))
    def _():
        dk_s[...] = jnp.zeros_like(dk_s)
        dv_s[...] = jnp.zeros_like(dv_s)

    q = q_ref[0, 0, :, :]
    k = k_ref[0, 0, :, :]
    v = v_ref[0, 0, :, :]
    do = do_ref[0, 0, :, :]
    lse = lse_ref[0, 0, :, :]                                 # [bq, 1]
    delta = delta_ref[0, 0, :, :]                             # [bq, 1]
    s = _chunk_scores(q, k, scale, causal, qi, 0, block_q, block_k)
    p = jnp.exp(s - lse)                                      # [bq, T]
    dp = jax.lax.dot_general(
        do.astype(v.dtype), v, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)                   # [bq, T]
    ds = p * (dp - delta)                                     # [bq, T]
    dq_ref[0, 0, :, :] = (jax.lax.dot_general(
        ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32) * scale).astype(dq_ref.dtype)
    dk_s[...] += jax.lax.dot_general(
        ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32) * scale           # [T, d]
    dv_s[...] += jax.lax.dot_general(
        p.astype(do_ref.dtype), do.astype(do_ref.dtype),
        (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)                   # [T, d]

    @pl.when((qi == nq - 1) & (hi % group == group - 1))
    def _():
        dk_ref[0, 0, :, :] = dk_s[...].astype(dk_ref.dtype)
        dv_ref[0, 0, :, :] = dv_s[...].astype(dv_ref.dtype)


def _fwd(q, k, v, scale, causal, block_q, block_k, group, interpret):
    b, h, t, d = q.shape
    if block_k == t:
        grid = (b, h, t // block_q)
        q_spec = pl.BlockSpec((1, 1, block_q, d),
                              lambda bi, hi, qi: (bi, hi, qi, 0))
        kv_spec = pl.BlockSpec((1, 1, t, d),
                               lambda bi, hi, qi: (bi, hi // group, 0, 0))
        lse_spec = pl.BlockSpec((1, 1, block_q, 1),
                                lambda bi, hi, qi: (bi, hi, qi, 0))
        return pl.pallas_call(
            functools.partial(_fwd_single_kernel, scale=scale,
                              causal=causal, block_q=block_q, block_k=t),
            grid=grid,
            in_specs=[q_spec, kv_spec, kv_spec],
            out_specs=[q_spec, lse_spec],
            out_shape=[jax.ShapeDtypeStruct((b, h, t, d), q.dtype),
                       jax.ShapeDtypeStruct((b, h, t, 1), jnp.float32)],
            interpret=interpret,
        )(q, k, v)
    grid = (b, h, t // block_q, t // block_k)
    q_spec = pl.BlockSpec((1, 1, block_q, d),
                          lambda bi, hi, qi, ki: (bi, hi, qi, 0))
    # GQA: query head hi reads KV head hi // group (group == 1 -> MHA)
    kv_spec = pl.BlockSpec((1, 1, block_k, d),
                           lambda bi, hi, qi, ki: (bi, hi // group, ki, 0))
    # trailing singleton: a [1,1,bq] block fails the TPU (8,128) tiling
    # rule on its last two dims; [1,1,bq,1] block over [b,h,t,1] passes
    # (last dim full, second-to-last divisible by 8)
    lse_spec = pl.BlockSpec((1, 1, block_q, 1),
                            lambda bi, hi, qi, ki: (bi, hi, qi, 0))
    return pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k),
        grid=grid,
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=[q_spec, lse_spec],
        out_shape=[jax.ShapeDtypeStruct((b, h, t, d), q.dtype),
                   jax.ShapeDtypeStruct((b, h, t, 1), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((block_q, _LANES), jnp.float32),
                        pltpu.VMEM((block_q, _LANES), jnp.float32),
                        pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
    )(q, k, v)


# --------------------------------------------------------------------------
# backward
# --------------------------------------------------------------------------

def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               dq_s, *, scale, causal, block_q, block_k):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _():
        dq_s[...] = jnp.zeros_like(dq_s)

    def body(masked):
        q = q_ref[0, 0, :, :]
        k = k_ref[0, 0, :, :]
        v = v_ref[0, 0, :, :]
        do = do_ref[0, 0, :, :]
        lse = lse_ref[0, 0, :, :]                             # [bq, 1]
        delta = delta_ref[0, 0, :, :]                         # [bq, 1]
        s = _chunk_scores(q, k, scale, masked, qi, ki, block_q, block_k)
        p = jnp.exp(s - lse)                                  # [bq, bk]
        dp = jax.lax.dot_general(
            do.astype(v.dtype), v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)               # [bq, bk]
        ds = p * (dp - delta)                                 # [bq, bk]
        dq_s[...] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale       # [bq, d]

    if causal:
        run = ki * block_k <= qi * block_q + block_q - 1
        _run_causal(run, body)
    else:
        body(masked=False)

    @pl.when(ki == nk - 1)
    def _():
        dq_ref[0, 0, :, :] = dq_s[...].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_s, dv_s, *,
                scale, causal, block_q, block_k, group, nq):
    # grid = (b, h_kv, nk, group * nq): the innermost dim folds the KV
    # head's whole query-head group x Q blocks, so dK/dV accumulate in
    # VMEM scratch across all of them and flush once per (kv head, ki).
    ki = pl.program_id(2)
    jj = pl.program_id(3)
    qi = jj % nq
    nj = pl.num_programs(3)

    @pl.when(jj == 0)
    def _():
        dk_s[...] = jnp.zeros_like(dk_s)
        dv_s[...] = jnp.zeros_like(dv_s)

    def body(masked):
        q = q_ref[0, 0, :, :]
        k = k_ref[0, 0, :, :]
        v = v_ref[0, 0, :, :]
        do = do_ref[0, 0, :, :]
        lse = lse_ref[0, 0, :, :]
        delta = delta_ref[0, 0, :, :]
        s = _chunk_scores(q, k, scale, masked, qi, ki, block_q, block_k)
        p = jnp.exp(s - lse)                                  # [bq, bk]
        dp = jax.lax.dot_general(
            do.astype(v.dtype), v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        dk_s[...] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale       # [bk, d]
        dv_s[...] += jax.lax.dot_general(
            p.astype(do_ref.dtype), do.astype(do_ref.dtype),
            (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)               # [bk, d]

    if causal:
        # causal skip (roles swapped): a Q block entirely above this KV
        # chunk contributes nothing to its dK/dV
        run = qi * block_q + block_q - 1 >= ki * block_k
        _run_causal(run, body)
    else:
        body(masked=False)

    @pl.when(jj == nj - 1)
    def _():
        dk_ref[0, 0, :, :] = dk_s[...].astype(dk_ref.dtype)
        dv_ref[0, 0, :, :] = dv_s[...].astype(dv_ref.dtype)


def _bwd(scale, causal, block_q, block_k, group, interpret, res, g):
    q, k, v, out, lse = res
    b, h, t, d = q.shape
    h_kv = k.shape[1]
    nq, nk = t // block_q, t // block_k
    # softmax-jacobian rowsum, computed ONCE (XLA fuses this into one
    # elementwise+reduce pass); O then never enters the kernels
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1, keepdims=True)                   # [b,h,t,1]

    if block_k == t:
        # single-chunk backward: one fused dq/dk/dv kernel
        q_spec = pl.BlockSpec((1, 1, block_q, d),
                              lambda bi, hi, qi: (bi, hi, qi, 0))
        kv_spec = pl.BlockSpec((1, 1, t, d),
                               lambda bi, hi, qi: (bi, hi // group, 0, 0))
        lse_spec = pl.BlockSpec((1, 1, block_q, 1),
                                lambda bi, hi, qi: (bi, hi, qi, 0))
        dq, dk, dv = pl.pallas_call(
            functools.partial(_bwd_single_kernel, scale=scale,
                              causal=causal, block_q=block_q, block_k=t,
                              group=group),
            grid=(b, h, nq),
            in_specs=[q_spec, kv_spec, kv_spec, q_spec, lse_spec,
                      lse_spec],
            out_specs=[q_spec, kv_spec, kv_spec],
            out_shape=[
                jax.ShapeDtypeStruct((b, h, t, d), q.dtype),
                jax.ShapeDtypeStruct((b, h_kv, t, d), k.dtype),
                jax.ShapeDtypeStruct((b, h_kv, t, d), v.dtype),
            ],
            scratch_shapes=[pltpu.VMEM((t, d), jnp.float32),
                            pltpu.VMEM((t, d), jnp.float32)],
            interpret=interpret,
        )(q, k, v, g, lse, delta)
        return dq, dk, dv

    q_spec = pl.BlockSpec((1, 1, block_q, d),
                          lambda bi, hi, qi, ki: (bi, hi, qi, 0))
    kv_spec = pl.BlockSpec((1, 1, block_k, d),
                           lambda bi, hi, qi, ki: (bi, hi // group, ki, 0))
    lse_spec = pl.BlockSpec((1, 1, block_q, 1),
                            lambda bi, hi, qi, ki: (bi, hi, qi, 0))
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k),
        grid=(b, h, nq, nk),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, lse_spec, lse_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, t, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
    )(q, k, v, g, lse, delta)

    # dK/dV: per-(kv head, KV chunk) accumulation over group x Q blocks
    gq_spec = pl.BlockSpec(
        (1, 1, block_q, d),
        lambda bi, hk, ki, jj: (bi, hk * group + jj // nq, jj % nq, 0))
    glse_spec = pl.BlockSpec(
        (1, 1, block_q, 1),
        lambda bi, hk, ki, jj: (bi, hk * group + jj // nq, jj % nq, 0))
    gkv_in_spec = pl.BlockSpec((1, 1, block_k, d),
                               lambda bi, hk, ki, jj: (bi, hk, ki, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, group=group,
                          nq=nq),
        grid=(b, h_kv, nk, group * nq),
        in_specs=[gq_spec, gkv_in_spec, gkv_in_spec, gq_spec, glse_spec,
                  glse_spec],
        out_specs=[gkv_in_spec, gkv_in_spec],
        out_shape=[jax.ShapeDtypeStruct((b, h_kv, t, d), k.dtype),
                   jax.ShapeDtypeStruct((b, h_kv, t, d), v.dtype)],
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
        interpret=interpret,
    )(q, k, v, g, lse, delta)
    return dq, dk, dv


# --------------------------------------------------------------------------
# public API
# --------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash(q, k, v, scale, causal, block_q, block_k, group, interpret):
    out, _lse = _fwd(q, k, v, scale, causal, block_q, block_k, group,
                     interpret)
    return out


def _flash_fwd(q, k, v, scale, causal, block_q, block_k, group, interpret):
    out, lse = _fwd(q, k, v, scale, causal, block_q, block_k, group,
                    interpret)
    return out, (q, k, v, out, lse)


_flash.defvjp(_flash_fwd, _bwd)


def flash_attention(q, k, v, *, causal: bool = True,
                    scale: Optional[float] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None):
    """Drop-in for `full_attention`: q is [B, T, H, head_dim]; k/v may
    carry fewer (grouped-query) heads — [B, T, H_kv, head_dim] with
    H % H_kv == 0 — which the kernel serves natively via its KV index
    map, with no query-side KV expansion in HBM.

    Falls back to the XLA dense path when (a) not running on TPU (the
    interpret-mode kernel is for tests, not speed) or (b) the shape
    doesn't block evenly — same semantics either way, and `path_calls()`
    says which ran. The chunked-KV
    online softmax has no sequence-length cap (VMEM per step is
    independent of T). For sequence-sharded meshes use ring/Ulysses
    attention (ray_tpu/parallel/ring_attention.py); this kernel is the
    single-chip hot path.
    """
    b, t, h, d = q.shape
    h_kv = k.shape[2]
    if scale is None:
        scale = d ** -0.5
    bq = block_q or _pick_block_q(t)
    bk = block_k or _pick_block_k(t)
    while bq > 128 and bq * bk > _MAX_BLOCK_PRODUCT:
        bq //= 2  # keep the f32 score temporaries inside scoped VMEM
    if (bq == 0 or bk == 0 or t % bq or t % bk or d % 64 or h % h_kv
            or bq * bk > _MAX_BLOCK_PRODUCT
            or jax.default_backend() != "tpu"):
        from ray_tpu.parallel.ring_attention import full_attention
        _PATH_CALLS["dense"] += 1
        return full_attention(q, k, v, causal=causal, scale=scale)
    _PATH_CALLS["pallas"] += 1
    # kernel layout is [B, H, T, d] so the T dim is block-sliceable
    qt, kt, vt = (x.transpose(0, 2, 1, 3) for x in (q, k, v))
    out = _flash(qt, kt, vt, scale, causal, bq, bk, h // h_kv, False)
    return out.transpose(0, 2, 1, 3)
