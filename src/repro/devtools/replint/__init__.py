"""replint — AST-based invariant linter for this repo (DESIGN.md §13).

The simulator's correctness rests on hand-enforced invariants: bitwise
same-seed replay, generation-fenced pooled flows, tracker-off hot-path
parity, hashable frozen configs. ``replint`` mechanizes them as six
static checks over ``src/``:

  determinism     no wall clocks, global RNG, ``id()`` keys, or
                  set-iteration-order dependence in net/ and runtime/
  pool-reset      classes implementing the pooling ``reset()`` protocol
                  must reset every mutable attribute ``__init__`` makes
  gen-fence       ``meta["g"]`` only through ``repro.net.genfence``;
                  sim-registered closures in runtime/ carry a staleness
                  guard
  hotpath         functions marked ``# replint: hotpath`` allocate no
                  closures / comprehensions / f-strings off-tracker,
                  and open no profiler span
  frozen-config   frozen dataclasses in config.py stay hashable
  design-ref      §N citations into DESIGN.md resolve to real sections

Findings are suppressed per line with ``# replint: ok(<rule>)`` — the
rule name is mandatory, and unused or malformed pragmas are themselves
findings. CLI: ``python -m repro.devtools.replint src/``.

Stdlib only; importing this package never touches the sim modules.
"""
from repro.devtools.replint.core import (
    Finding,
    RULES,
    iter_python_files,
    lint_file,
    lint_paths,
    rule_names,
)

__all__ = [
    "Finding",
    "RULES",
    "iter_python_files",
    "lint_file",
    "lint_paths",
    "rule_names",
]
