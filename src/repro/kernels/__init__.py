"""Pallas TPU kernels for the compute hot-spots the paper optimizes.

word_logical  — word-aligned AND/OR/XOR/ANDNOT with clean-tile skipping
popcount      — set-bit counts (selectivity / 1-C/N profiles)
bitpack       — Algorithm 3's row->word packing
grad_compress — blockwise norms for EWAH sparse-gradient all-reduce

`ops` holds the jit'd wrappers, `ref` the pure-jnp oracles.
Kernels target TPU ((8,128)-aligned tiles, VMEM BlockSpecs, SMEM
scalars); ``ops.interpret_mode()`` runs them interpreted on the CPU.
"""
from . import ops, ref
