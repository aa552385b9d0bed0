"""Flash-attention forward Pallas TPU kernel (online softmax).

Grid: (batch*heads, q_blocks). Each program holds one (block_q, hd) query
tile and the *whole* (S, hd) K and V of its batch-head in VMEM, and walks
K/V tiles of (block_k, hd) out of those VMEM blocks with a
``lax.fori_loop``, keeping the running max / normalizer (m, l) of the
online-softmax recurrence. Tiles are MXU-aligned (block_q, block_k
multiples of 128 when the sequence allows).

Because the K/V blocks span the sequence, VMEM bounds S: the working set
is O(S * hd), not O(block_q * (hd + block_k)). :func:`max_seq_len` gives
the longest S this kernel accepts, and the wrapper raises ``ValueError``
above it rather than let the chip's compiler fail on VMEM. Streaming K/V
over the grid would lift the bound.

Causal masking skips fully-masked K tiles via the loop upper bound.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30

# Largest (S, hd) K or V block, lane-padded to 128, that v5e's scoped VMEM
# takes with both inputs double-buffered: 3 MiB blocks compile, 4 MiB
# blocks are refused (tests/test_tpu_compile.py compiles both sides).
KV_BLOCK_MAX_BYTES = 3 * 2**20


def max_seq_len(hd: int, dtype) -> int:
    """Longest sequence whose K/V blocks fit VMEM at head dim ``hd``."""
    lanes = -(-hd // 128) * 128
    return KV_BLOCK_MAX_BYTES // (lanes * jnp.dtype(dtype).itemsize)


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, *, block_k: int, causal: bool,
                  scale: float):
    _, bq, hd = q_ref.shape
    Sk = k_ref.shape[1]
    q = q_ref[0, :, :].astype(jnp.float32) * scale
    iq = pl.program_id(1)
    # Mosaic contracts f32 operands at bf16 precision unless told otherwise
    # (a v5e's f32 output was 1.2e-2 off the reference); f32 inputs get f32
    prec = jax.lax.Precision.HIGHEST if q_ref.dtype == jnp.float32 else None

    def body(ik, carry):
        acc, m, l = carry
        k = k_ref[0, pl.ds(ik * block_k, block_k), :].astype(jnp.float32)
        v = v_ref[0, pl.ds(ik * block_k, block_k), :].astype(jnp.float32)
        s = jnp.dot(q, k.T, precision=prec)              # (bq, bk)
        if causal:
            qpos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, block_k), 0)
            kpos = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, (bq, block_k), 1)
            s = jnp.where(kpos <= qpos, s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + p.sum(axis=-1)
        acc = acc * alpha[:, None] + jnp.dot(p, v, precision=prec)
        return acc, m_new, l_new

    acc0 = jnp.zeros((bq, hd), jnp.float32)
    m0 = jnp.full((bq,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq,), jnp.float32)
    if causal:
        # K tiles strictly above the diagonal are skipped entirely
        n_k = ((iq + 1) * bq + block_k - 1) // block_k
    else:
        n_k = Sk // block_k
    acc, m, l = jax.lax.fori_loop(0, n_k, body, (acc0, m0, l0))
    out = (acc / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)
    o_ref[0, :, :] = out


def flash_attention(q, k, v, *, causal: bool = True, block_q: int = 128,
                    block_k: int = 128, scale=None,
                    interpret: bool = False):
    """q,k,v: (B, S, H, hd) (same head count; expand GQA beforehand)."""
    B, S, H, hd = q.shape
    if S > max_seq_len(hd, k.dtype):
        raise ValueError(
            f"flash_attention holds K/V of the whole sequence in VMEM: "
            f"S={S} exceeds the longest that fits, "
            f"{max_seq_len(hd, k.dtype)} at hd={hd} in {k.dtype}")
    scale = scale or hd ** -0.5
    bq = min(block_q, S)
    bk = min(block_k, S)
    while S % bq:
        bq //= 2
    while S % bk:
        bk //= 2
    # fold batch and heads into the grid's first axis
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    kf = k.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    vf = v.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    grid = (B * H, S // bq)
    out = pl.pallas_call(
        functools.partial(_flash_kernel, block_k=bk, causal=causal,
                          scale=scale),
        out_shape=jax.ShapeDtypeStruct((B * H, S, hd), q.dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, hd), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, S, hd), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, S, hd), lambda b, i: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, hd), lambda b, i: (b, i, 0)),
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(B, H, S, hd).transpose(0, 2, 1, 3)
