"""Public jit'd wrappers for the parser kernels.

On the CPU the kernels execute with ``interpret=True`` — the kernel body
runs in Python per grid step, validating the BlockSpec tiling and index maps
against the pure-jnp oracles.  On every other platform the same calls lower
to Mosaic, so a kernel that cannot compile there fails instead of silently
running interpreted.  ``use_interpret()`` picks automatically.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from typing import Optional

from . import build as _build
from . import flash_attention as _flash
from . import packed_reach as _packed_reach
from . import reach as _reach
from . import semiring as _semiring
from . import ssd_chunk as _ssd
from .ref import (
    build_merge_chunk_ref,
    flash_attention_ref,
    reach_chunk_product_ref,
    semiring_matmul_ref,
    ssd_chunk_ref,
)


def use_interpret() -> bool:
    """Interpret the kernels only on the CPU platform."""
    return jax.default_backend() == "cpu"


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk"))
def semiring_matmul(a, b, *, bm: int = 128, bn: int = 128, bk: int = 128):
    return _semiring.semiring_matmul(
        a, b, bm=bm, bn=bn, bk=bk, interpret=use_interpret()
    )


@jax.jit
def reach_chunk_product(N, ids):
    return _reach.reach_chunk_product(N, ids, interpret=use_interpret())


@jax.jit
def packed_reach_chunk_product(Np, ids):
    """Word-packed chunk product (uint32 OR-AND) — see packed_reach.py."""
    return _packed_reach.packed_reach_chunk_product(
        Np, ids, interpret=use_interpret()
    )


@jax.jit
def build_merge_chunk(N, ids, entry_f, entry_b):
    return _build.build_merge_chunk(
        N, ids, entry_f, entry_b, interpret=use_interpret()
    )


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6)
)
def flash_attention(q, k, v, causal=True, window=None, q_block=512, k_block=512):
    """Fused flash-attention forward (Pallas) with recompute backward.

    q/k/v: (b, L, h, hd); kv must already match the query head count (use the
    model's repeat/grouped layout upstream).  Under ``jax.grad`` the backward
    pass recomputes via the pure-jnp oracle (flash-style recompute)."""
    return _flash_fwd_public(q, k, v, causal, window, q_block, k_block)


def _flash_fwd_public(q, k, v, causal, window, q_block, k_block):
    b, L, h, hd = q.shape
    Lk = k.shape[1]
    qf = jnp.moveaxis(q, 2, 1).reshape(b * h, L, hd)
    kf = jnp.moveaxis(k, 2, 1).reshape(b * h, Lk, hd)
    vf = jnp.moveaxis(v, 2, 1).reshape(b * h, Lk, hd)
    of = _flash.flash_attention_fwd(
        qf, kf, vf, causal=causal, window=window,
        q_block=q_block, k_block=k_block, interpret=use_interpret(),
    )
    return jnp.moveaxis(of.reshape(b, h, L, hd), 1, 2)


def _flash_fwd_vjp(q, k, v, causal, window, q_block, k_block):
    out = _flash_fwd_public(q, k, v, causal, window, q_block, k_block)
    return out, (q, k, v)


def _flash_bwd_vjp(causal, window, q_block, k_block, res, g):
    q, k, v = res
    _, vjp = jax.vjp(
        lambda q_, k_, v_: flash_attention_ref(q_, k_, v_, causal=causal, window=window),
        q, k, v,
    )
    return vjp(g)


flash_attention.defvjp(_flash_fwd_vjp, _flash_bwd_vjp)


@jax.jit
def ssd_chunk(xdt, cs, B, C, S_prev):
    """Fused SSD intra-chunk compute (y, state contribution) — see ssd_chunk.py."""
    return _ssd.ssd_chunk(xdt, cs, B, C, S_prev, interpret=use_interpret())


__all__ = [
    "semiring_matmul",
    "reach_chunk_product",
    "packed_reach_chunk_product",
    "build_merge_chunk",
    "flash_attention",
    "ssd_chunk",
    "ssd_chunk_ref",
    "semiring_matmul_ref",
    "reach_chunk_product_ref",
    "build_merge_chunk_ref",
    "flash_attention_ref",
    "use_interpret",
]
