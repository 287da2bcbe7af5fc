"""How the per-layer readers find the program's operations in a trace, by
the names the program gives them.  One place, so that a rename in the
program is one edit here."""
from __future__ import annotations

# cross-chip collectives as XLA names them
COLLECTIVE_PREFIXES = ("all-reduce", "all-gather", "reduce-scatter",
                       "all-to-all", "collective-permute")


def is_collective(name, stats) -> bool:
    from bench.trace import opcode
    op = opcode(name)
    return any(op.startswith(p) for p in COLLECTIVE_PREFIXES)
