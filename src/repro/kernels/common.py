"""What every kernel wrapper in this package shares.

* Interpret mode. The kernels compile only for a TPU. On any other
  backend (the CPU the tests run on) their bodies run in a Pallas
  interpreter. The platform decides, so no config carries the choice:
  every wrapper takes ``interpret=None`` and resolves it in
  ``interpret_mode``.
* The output's varying mesh axes (``vma``). Inside a ``jax.shard_map``
  with ``check_vma=True`` a ``pallas_call`` must say over which manual
  axes its output varies; it varies wherever an input does.
"""
from __future__ import annotations

from typing import Optional

import jax
from jax.experimental.pallas import tpu as pltpu


def interpret_mode(interpret: Optional[bool] = None) -> bool:
    """``interpret`` when given; otherwise interpret unless JAX's default
    backend is a TPU."""
    if interpret is not None:
        return bool(interpret)
    return jax.default_backend() != "tpu"


def pallas_interpret(interpret: Optional[bool], vma: frozenset):
    """The ``interpret=`` argument of a ``pallas_call`` whose output
    varies over ``vma``: False (compile) on a TPU. Elsewhere the generic
    interpreter (``True``), except for a call inside a ``check_vma``
    shard_map (non-empty ``vma``): only the TPU interpreter keeps those
    types. The TPU interpreter is not the default because it keeps
    process-wide state and hangs when asynchronously dispatched calls
    run two kernels at once."""
    if not interpret_mode(interpret):
        return False
    return pltpu.InterpretParams() if vma else True


def out_vma(*inputs) -> frozenset:
    """The mesh axes over which any of ``inputs`` varies."""
    return frozenset().union(*(jax.typeof(x).vma for x in inputs))
