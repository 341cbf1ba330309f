"""The general generators and system drivers, one per kind of configuration.

A configuration names its driver; ``perfbench/drivers/<driver>.py``
defines ``Driver(cell, seed)`` with ``setup()``, ``recording(on)``,
``window(seconds) -> {metric: value}``, ``attempted``, ``counters()``,
``release()``, ``check(control=False) -> [Check]`` and, after the check, ``failed``;
``notes``, a dict of set-up times and window counts, is printed with the
result on standard error.
"""

_OPS = {"<": "__lt__", "<=": "__le__", ">": "__gt__", ">=": "__ge__",
        "==": "__eq__", "!=": "__ne__"}


def predicate_expr(terms):
    """The system's expression for the AND of (column, op, constant)."""
    from repro.aformat.expressions import field

    expr = None
    for column, op, value in terms:
        term = getattr(field(column), _OPS[op])(value)
        expr = term if expr is None else expr & term
    return expr
