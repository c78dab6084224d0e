"""PQL — the Pilosa Query Language (reference: pql/ directory).

A pure host-side layer: grammar-compatible parser producing the same
Call/Condition AST shape as the reference (pql/ast.go:27,263,482), consumed
by the executor which lowers ASTs to jitted XLA computations.
"""

from pilosa_tpu.pql.ast import Call, Condition, Query
from pilosa_tpu.pql.parser import CALL_NAMES, ParseError, parse, parse_noting_hit

__all__ = [
    "CALL_NAMES", "Call", "Condition", "Query", "ParseError", "parse",
    "parse_noting_hit",
]
