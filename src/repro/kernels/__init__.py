"""Pallas TPU kernels for the LTP-sync hot loops (compiled on a TPU,
interpreted on any other backend; ``common.interpret_mode``).

  dropfill.py       bubble-fill + compensation over packet tiles
  packet_reduce.py  PS-side masked multi-worker reduce
  randomk.py        Random-k sparsification
  ops.py            jit'd padding-aware wrappers
  common.py         interpret-mode and output-vma resolution
  ref.py            pure-jnp oracles
"""
from repro.kernels.ops import (  # noqa: F401
    ltp_dropfill,
    ltp_packet_reduce,
    randomk_sparsify,
)
