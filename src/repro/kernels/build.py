"""Fused builder&merger kernels (paper Fig. 14) — one chunk, one M buffer.

Two sequential ``pallas_call``s sharing one buffer via input/output aliasing
(the paper's single-array memory optimization, expressed safely for TPU —
Pallas output blocks are not reloaded on revisit, so the read-modify-write
backward pass must take M as an aliased *input*):

  build_fwd   forward frontier mat-vec scan from the join entry ``J_{i-1}``;
              grid step t writes ``M[t] = clamp(N[x_t] @ frontier)``.
  merge_bwd   backward scan with *transposed* matrices from the next chunk's
              backward entry ``Ĵ_{i+1}``; grid step s visits t = k-1-s and
              ANDs in place: ``M[t] *= β_{t+1}``, then ``β ← N[x_t]ᵀ β``.

Transition matrices are scalar-prefetch-selected per step (the chunk's class
ids drive the BlockSpec index_map), so the next N block is DMA'd while the
current mat-vec runs — the DMA/compute overlap a CPU table-walk cannot express.
The frontier is a (1, ℓ) VMEM scratch carried across grid steps.  Columns
leave in (8, ℓ) row blocks, each revisited by 8 consecutive grid steps —
the (8, 128) tiling the TPU compiler requires of a block.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


_ROWS = 8  # sublane rows of one output block: the (8, 128) tiling unit

# Largest ℓp (a multiple of 128) that compiles within a TPU v5e core's
# scoped VMEM: the double-buffered (ℓ, ℓ) N block runs out at ℓp = 1536
MAX_ELL_PAD = 1408


def _dot_nt(v, n):
    """Row vector ``v`` (1, ℓ) times ``nᵀ`` without materialising the transpose."""
    return jax.lax.dot_general(
        v, n, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )


def _build_fwd_kernel(ids_ref, n_ref, jf_ref, m_ref, fr_ref):
    t = pl.program_id(0)

    @pl.when(t == 0)
    def _init():
        fr_ref[...] = jf_ref[...]

    # frontier <- clamp(N[x_t] @ frontier)  (row-vector form: fr @ Nᵀ)
    nf = jnp.minimum(_dot_nt(fr_ref[...], n_ref[0]), 1.0)
    fr_ref[...] = nf
    # the (8, ℓ) output block is revisited by 8 consecutive steps; step t
    # fills its row t % 8 and the block is written back once all 8 are set
    row = jax.lax.broadcasted_iota(jnp.int32, m_ref.shape, 0)
    m_ref[...] = jnp.where(row == t % _ROWS, nf.astype(m_ref.dtype), m_ref[...])


def _merge_bwd_kernel(ids_ref, n_ref, jb_ref, m_in_ref, m_ref, fr_ref, *, kp: int):
    s = pl.program_id(0)

    @pl.when(s == 0)
    def _init():
        fr_ref[...] = jb_ref[...]

    # visiting t = kp-1-s:  M[t] *= β_{t+1};  β ← clamp(N[x_t]ᵀ @ β)
    row = jax.lax.broadcasted_iota(jnp.int32, m_ref.shape, 0)
    merged = m_in_ref[...] * fr_ref[...].astype(m_ref.dtype)
    m_ref[...] = jnp.where(row == (kp - 1 - s) % _ROWS, merged, m_ref[...])
    fr_ref[...] = jnp.minimum(
        jnp.dot(fr_ref[...], n_ref[0], preferred_element_type=jnp.float32), 1.0
    )


def build_merge_chunk(
    N: jnp.ndarray,          # (A+1, ℓ, ℓ) {0,1} — PAD class (index A) = identity
    ids: jnp.ndarray,        # (k,) int32 char classes of the chunk
    entry_f: jnp.ndarray,    # (ℓ,) forward join entry J_{i-1}
    entry_b: jnp.ndarray,    # (ℓ,) backward join entry Ĵ_{i+1}
    *,
    interpret: bool = False,
) -> jnp.ndarray:
    """Clean SLPF columns M (k, ℓ) of one chunk (paper Fig. 14)."""
    _, ell, _ = N.shape
    k = ids.shape[0]
    # pad the chunk to whole (8, ℓ) output blocks with the identity PAD
    # class: trailing PAD steps leave the backward state unchanged and
    # their forward rows are sliced off below
    kp = -(-k // _ROWS) * _ROWS
    ids = jnp.pad(ids, (0, kp - k), constant_values=N.shape[0] - 1)
    row_block = lambda t: (t // _ROWS, 0)

    fwd_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(kp,),
        in_specs=[
            pl.BlockSpec((1, ell, ell), lambda t, ids: (ids[t], 0, 0)),
            pl.BlockSpec((1, ell), lambda t, ids: (0, 0)),
        ],
        out_specs=pl.BlockSpec((_ROWS, ell), lambda t, ids: row_block(t)),
        scratch_shapes=[pltpu.VMEM((1, ell), jnp.float32)],
    )
    m_fwd = pl.pallas_call(
        _build_fwd_kernel,
        grid_spec=fwd_spec,
        out_shape=jax.ShapeDtypeStruct((kp, ell), N.dtype),
        interpret=interpret,
    )(ids, N, entry_f[None])

    bwd_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(kp,),
        in_specs=[
            pl.BlockSpec((1, ell, ell), lambda s, ids: (ids[kp - 1 - s], 0, 0)),
            pl.BlockSpec((1, ell), lambda s, ids: (0, 0)),
            pl.BlockSpec((_ROWS, ell), lambda s, ids: row_block(kp - 1 - s)),  # M in
        ],
        out_specs=pl.BlockSpec((_ROWS, ell), lambda s, ids: row_block(kp - 1 - s)),
        scratch_shapes=[pltpu.VMEM((1, ell), jnp.float32)],
    )
    M = pl.pallas_call(
        functools.partial(_merge_bwd_kernel, kp=kp),
        grid_spec=bwd_spec,
        out_shape=jax.ShapeDtypeStruct((kp, ell), N.dtype),
        input_output_aliases={3: 0},  # M buffer written in place (+1 for prefetch arg)
        interpret=interpret,
    )(ids, N, entry_b[None], m_fwd)
    return M[:k]
