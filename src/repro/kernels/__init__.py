"""Pallas TPU kernels for the parser's two hot loops (DESIGN §2).

The paper's compute hot-spots are the reach phase (per-chunk ME-DFA
speculation ≡ Boolean-semiring matrix chain product) and the fused
builder&merger (Fig. 14).  ``packed_reach.py`` is the word-native (uint32
OR-AND) form of the reach kernel for the bit-packed backend — 32× less
HBM↔VMEM traffic per step — and also folds the sparse backend's gathered
feasible-start rows.  Each kernel records the largest ℓp that compiles
within a v5e core's scoped VMEM (``MAX_ELL_PAD``).  Each kernel ships with:

  * ``<name>.py``  — ``pl.pallas_call`` + explicit BlockSpec VMEM tiling;
  * ``ops.py``     — jit'd public wrappers (interpret=True on CPU);
  * ``ref.py``     — pure-jnp oracles the kernels are verified against.
"""
