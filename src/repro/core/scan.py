"""Generic chunked three-phase scan — the paper's parallel schema as a primitive.

The paper's reach / join / build decomposition (Sect. 3.2) is an instance of a
general pattern for parallelizing any *associative* sequence recurrence:

    reach :  per chunk, fold the per-element monoid values into one summary
             (chunk products  P_i = e_k ⊗ … ⊗ e_1)                  — parallel
    join  :  exclusive scan of summaries across chunks
             (entry states    J_i = act(P_{i-1} ⊗ … ⊗ P_1, init))   — log-depth
    build :  per chunk, replay the recurrence from the known entry   — parallel

A monoid ``(M, ⊗)`` with identity acts on a state space via ``act(m, s)``; the
per-element recurrence is ``s_t = act(e_t, s_{t-1})``.

Instantiations in this framework:
  * Boolean (OR-AND) semiring on segment-transition matrices → the RE parser
    (``core/engine.py``): the chunk product *is* the ME-DFA analogue — all ℓ
    speculative entry states evaluated simultaneously as matrix columns, so the
    speculation bound is ℓ (paper Sect. 3.1), never the 2^ℓ DFA state count.
  * Affine real monoid on (decay, increment) pairs → Mamba-2 SSD chunked state
    passing (``models/mamba.py``): cross-chunk/device state propagation is the
    same join phase the parser uses.

Cross-device: when the chunk axis is sharded over mesh axes, ``join`` runs as a
single ``all_gather`` of the small per-chunk summaries followed by a replicated
local associative scan — O(c·|summary|) bytes of collective traffic, independent
of the sequence length (the paper's key scalability property, Sect. 3.3).
"""

from __future__ import annotations

from typing import Any, Callable, Sequence, Tuple

import jax
import jax.numpy as jnp

Combine = Callable[[Any, Any], Any]   # (later, earlier) -> composed; associative
Act = Callable[[Any, Any], Any]       # (monoid elem, state) -> state


def associative_prefix(combine: Combine, xs: Any, *, reverse: bool = False) -> Any:
    """Inclusive prefix combine along axis 0 (log-depth, pytree-aware).

    ``combine(later, earlier)``; with ``reverse=True`` computes suffix products.
    """
    return jax.lax.associative_scan(
        lambda a, b: combine(b, a), xs, axis=0, reverse=reverse
    )


def exclusive_entries(combine: Combine, act: Act, summaries: Any, init: Any) -> Any:
    """Join phase: entry state per chunk from stacked chunk summaries (axis 0).

    ``entries[0] = init``; ``entries[i] = act(summaries[i-1] ⊗ … ⊗ summaries[0],
    init)``.  Returns entries stacked along axis 0 (length c).
    """
    prefix = associative_prefix(combine, summaries)          # inclusive prefixes
    applied = jax.vmap(lambda m: act(m, init))(prefix)       # states after chunks

    def shift(leaf_applied, leaf_init):
        leaf_init = jnp.broadcast_to(
            jnp.asarray(leaf_init), leaf_applied.shape[1:]
        )[None]
        return jnp.concatenate([leaf_init, leaf_applied[:-1]], axis=0)

    return jax.tree.map(shift, applied, init)


def sharded_exclusive_entries(
    combine: Combine,
    act: Act,
    local_summary: Any,
    init: Any,
    axis_names: Sequence[str],
) -> Any:
    """Cross-device join: each device holds ONE chunk summary; returns this
    device's entry state.  One all_gather + replicated local scan + slice.

    Must run inside ``shard_map`` with ``axis_names`` bound.  Traffic per device
    is ``(c-1)·|summary|`` bytes — independent of chunk length.
    """
    gathered = jax.tree.map(lambda x: _all_gather_multi(x, axis_names), local_summary)
    entries = exclusive_entries(combine, act, gathered, init)
    idx = linear_index(axis_names)
    return jax.tree.map(lambda e: jax.lax.dynamic_index_in_dim(e, idx, 0, False), entries)


def _all_gather_multi(x: jnp.ndarray, axis_names: Sequence[str]) -> jnp.ndarray:
    """all_gather over possibly-multiple mesh axes, flattened to one chunk axis."""
    g = jax.lax.all_gather(x, tuple(axis_names), axis=0, tiled=False)
    if len(axis_names) > 1:
        g = g.reshape((-1,) + x.shape)
    return g


def linear_index(axis_names: Sequence[str]) -> jnp.ndarray:
    """This device's linear position over possibly-multiple mesh axes."""
    idx = jnp.int32(0)
    for name in axis_names:
        idx = idx * jax.lax.axis_size(name) + jax.lax.axis_index(name)
    return idx


def chunk_fold(combine: Combine, elems: Any, identity: Any) -> Any:
    """Reach phase for one chunk: fold elems (axis 0, length k) into a summary.

    Sequential ``lax.scan`` chain — O(k) combines of constant-size state; when
    the summary is a matrix each combine is one matmul (MXU work), and a chain
    has the same total FLOPs as a tree reduction with better locality.
    """

    def step(acc, e):
        return combine(e, acc), None

    out, _ = jax.lax.scan(step, identity, elems)
    return out


def chunk_replay(apply: Act, elems: Any, entry: Any) -> Tuple[Any, Any]:
    """Build phase for one chunk: replay the recurrence from ``entry``.

    Returns (final_state, stacked per-position states) — e.g. the SLPF columns.
    """

    def step(state, e):
        nxt = apply(e, state)
        return nxt, nxt

    return jax.lax.scan(step, entry, elems)


def chunked_scan(
    combine: Combine,
    apply: Act,
    elems: Any,
    init: Any,
    identity: Any,
    n_chunks: int,
) -> Any:
    """Single-program form of the full three-phase scan (jit-friendly).

    ``elems`` leaves: (n, ...) with n divisible by ``n_chunks``.  Returns the
    per-position states (n, ...) — identical to the serial left fold
    ``s_t = apply(e_t, s_{t-1})``, computed with the paper's reach/join/build
    structure (equivalence validated in tests).
    """

    def reshape(leaf):
        n = leaf.shape[0]
        assert n % n_chunks == 0, "sequence length must divide into chunks"
        k = n // n_chunks
        return leaf.reshape((n_chunks, k) + leaf.shape[1:])

    chunked = jax.tree.map(reshape, elems)
    summaries = jax.vmap(lambda e: chunk_fold(combine, e, identity))(chunked)
    entries = exclusive_entries(combine, act=apply, summaries=summaries, init=init)
    _, states = jax.vmap(lambda e, s: chunk_replay(apply, e, s))(chunked, entries)
    return jax.tree.map(lambda leaf: leaf.reshape((-1,) + leaf.shape[2:]), states)
