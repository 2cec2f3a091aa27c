"""Reach-phase kernel: per-chunk transition chain product, scalar-prefetched.

Computes ``P = N[x_k] ⊗ … ⊗ N[x_1]`` for one chunk — the paper's Eq. (6) with
all ℓ ME-DFA entries evaluated simultaneously as matrix columns (DESIGN §2).

TPU-native structure: the chunk's char-class ids are a *scalar-prefetch*
operand; the grid walks the chunk sequentially and each step's BlockSpec
index_map selects ``N[x_t]`` — so the next step's transition matrix is DMA'd
from HBM into VMEM while the current product runs on the MXU (the classic
lookahead the paper's table-walk cannot express).  The running product lives
in a VMEM scratch across grid steps.

The (ℓ, ℓ) f32 blocks — N double-buffered, the running product and the
output — fit a v5e core's scoped VMEM up to ``MAX_ELL_PAD``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


# Largest ℓp (a multiple of 128) that compiles within a TPU v5e core's
# scoped VMEM; ℓp = 1024 runs out
MAX_ELL_PAD = 896


def _reach_kernel(ids_ref, n_ref, out_ref, acc_ref, *, k: int):
    t = pl.program_id(0)

    @pl.when(t == 0)
    def _init():
        ell = acc_ref.shape[0]
        eye = (
            jax.lax.broadcasted_iota(jnp.int32, (ell, ell), 0)
            == jax.lax.broadcasted_iota(jnp.int32, (ell, ell), 1)
        )
        acc_ref[...] = eye.astype(jnp.float32)

    # P <- N[x_t] ⊗ P   (OR-AND: fp32 matmul + clamp)
    acc_ref[...] = jnp.minimum(
        jnp.dot(n_ref[0], acc_ref[...], preferred_element_type=jnp.float32), 1.0
    )

    @pl.when(t == k - 1)
    def _done():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def reach_chunk_product(
    N: jnp.ndarray,          # (A+1, ℓ, ℓ) {0,1} — PAD class = identity
    ids: jnp.ndarray,        # (k,) int32 char classes of the chunk
    *,
    interpret: bool = False,
) -> jnp.ndarray:
    """Chunk product P (ℓ, ℓ).  ℓ must be 128-aligned (EngineTables pad)."""
    _, ell, ell2 = N.shape
    assert ell == ell2
    k = ids.shape[0]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(k,),
        in_specs=[
            # one (1, ℓ, ℓ) block of N per step, chosen by the prefetched ids
            pl.BlockSpec((1, ell, ell), lambda t, ids: (ids[t], 0, 0)),
        ],
        out_specs=pl.BlockSpec((ell, ell), lambda t, ids: (0, 0)),
        scratch_shapes=[pltpu.VMEM((ell, ell), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_reach_kernel, k=k),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((ell, ell), N.dtype),
        interpret=interpret,
    )(ids, N)
