"""Pallas TPU kernels for the framework's compute hot-spots.

local_sdca.py      the paper's LocalSDCA inner loop (Algorithm 2): u/dalpha
                   persistent in VMEM across a sequential grid; ops.py wraps
                   it as a drop-in CoCoA+ local solver.
sparse_sdca.py     the same loop over padded-ELL rows (gather-dot +
                   scatter-axpy on u): O(nnz) HBM traffic instead of O(d),
                   validated bit-for-bit against its oracle.
ref.py             pure-jnp oracles; every kernel is validated allclose in
                   interpret mode (tests/test_kernels.py, tests/test_sparse.py).
"""
from .local_sdca import local_sdca_pallas
from .sparse_sdca import sparse_local_sdca
