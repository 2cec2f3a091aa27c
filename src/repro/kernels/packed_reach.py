"""Packed reach kernel: fold packed rows through a chunk, OR-AND on uint32 words.

The word-native twin of ``kernels/reach.py``.  Every matrix is in the
bit-packed layout of ``core/matrices.py``'s packed semiring — ``(ℓp, W =
ℓp/32)`` uint32 rows, row ``col`` holding the packed target set of source
``col``.  The kernel folds L such rows through the chunk's transition
matrices, one grid step per character:

    R'[j] = OR_k bit_k(R[j]) · N_packed[x_t][k]

Two callers share it.  The packed backend's chunk product
``P = N[x_k] ⊗ … ⊗ N[x_1]`` is the fold of the packed identity (L = ℓp,
``packed_reach_chunk_product``); the sparse backend folds only its S
gathered feasible-start rows (L = S, ``packed_fold_rows``).

Layout.  The running rows live transposed in a (W, L) VMEM scratch: the word
index on sublanes, the row index on lanes, so every vector op is lane-dense
and no step slices a lane dimension dynamically.  For each source word wk
the 32 bits of every row broadcast to a (32, L) mask, which ANDs with each
of the W target words of N's rows 32·wk … 32·wk+31 (a lane broadcast); an
OR over the 32 sublanes finishes each target word.  The step is pure VPU
word arithmetic, no MXU.

TPU-native structure mirrors the f32 kernel: the chunk's char-class ids are
a *scalar-prefetch* operand and the BlockSpec index map selects
``N_packed[x_t]`` per step, so the next class's packed rows DMA while the
current step computes.  Each step moves ℓp·W·4 = ℓp²/8 bytes of transition
rows, 32× fewer than the f32 kernel's 4ℓp².
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.matrices import packed_identity

_WORD = 32

# Largest ℓp whose fold compiles within a TPU v5e core's scoped VMEM: the
# W running (32, L) word accumulators outgrow it at ℓp = 1472 (L = ℓp)
MAX_ELL_PAD = 1408


def _or_rows(x: jnp.ndarray) -> jnp.ndarray:
    """OR-reduce a (32, L) word block over its sublanes → (1, L)."""
    n = x.shape[0]
    while n > 1:
        n //= 2
        x = x[:n] | x[n:]
    return x


def _fold_kernel(ids_ref, r0_ref, np_ref, out_ref, acc_ref, *, k: int):
    t = pl.program_id(0)
    W = acc_ref.shape[0]

    @pl.when(t == 0)
    def _init():
        acc_ref[...] = r0_ref[...]

    shifts = jax.lax.broadcasted_iota(jnp.uint32, (_WORD, 1), 0)

    def source_word(wk, new):
        # bits 32·wk … 32·wk+31 of every row, as all-ones / all-zeros words
        words = acc_ref[pl.ds(wk, 1), :]                            # (1, L)
        mask = jnp.uint32(0) - ((words >> shifts) & jnp.uint32(1))  # (32, L)
        start = pl.multiple_of(wk * _WORD, _WORD)
        rows = np_ref[0, pl.ds(start, _WORD), :]                    # (32, W)
        return tuple(new[w] | (mask & rows[:, w:w + 1]) for w in range(W))

    L = acc_ref.shape[1]
    zero = jnp.zeros((_WORD, L), jnp.uint32)
    new = jax.lax.fori_loop(0, W, source_word, (zero,) * W)
    for w in range(W):
        acc_ref[w:w + 1, :] = _or_rows(new[w])

    @pl.when(t == k - 1)
    def _done():
        out_ref[...] = acc_ref[...]


def packed_fold_rows(
    Np: jnp.ndarray,         # (A+1, ℓp, W) uint32 packed transition rows
    ids: jnp.ndarray,        # (k,) int32 char classes of the chunk
    R0: jnp.ndarray,         # (L, W) uint32 packed start rows
    *,
    interpret: bool = False,
) -> jnp.ndarray:
    """Rows ``R0`` folded through the chunk: (L, W) uint32.  ℓp = 32·W."""
    _, ell, W = Np.shape
    assert ell == W * _WORD, (Np.shape, "ℓp must be a multiple of 32")
    L = R0.shape[0]
    k = ids.shape[0]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(k,),
        in_specs=[
            # the start rows, resident every step (read once at t == 0)
            pl.BlockSpec((W, L), lambda t, ids: (0, 0)),
            # one (1, ℓp, W) block of packed rows per step, chosen by the ids
            pl.BlockSpec((1, ell, W), lambda t, ids: (ids[t], 0, 0)),
        ],
        out_specs=pl.BlockSpec((W, L), lambda t, ids: (0, 0)),
        scratch_shapes=[pltpu.VMEM((W, L), jnp.uint32)],
    )
    out = pl.pallas_call(
        functools.partial(_fold_kernel, k=k),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((W, L), jnp.uint32),
        interpret=interpret,
    )(ids, R0.T, Np)
    return out.T


def packed_reach_chunk_product(
    Np: jnp.ndarray,         # (A+1, ℓp, W) uint32 packed transition rows
    ids: jnp.ndarray,        # (k,) int32 char classes of the chunk
    *,
    interpret: bool = False,
) -> jnp.ndarray:
    """Packed chunk product (ℓp, W) uint32: the fold of the packed identity."""
    return packed_fold_rows(
        Np, ids, packed_identity(Np.shape[1]), interpret=interpret
    )
