"""Fused multi-column predicate evaluation — Pallas TPU kernel.

The scan hot loop evaluates ``(col_a OP c_a) COMBINE (col_b OP c_b) ...``
over millions of rows.  On CPU Arrow does this one compare kernel at a
time, materializing an intermediate mask per term; on TPU we fuse every
term into one VMEM pass: each grid step streams a (1, TILE) block of each
of the C predicate columns into VMEM, evaluates all compares on the VPU
and combines them in registers, emitting one (1, TILE) int32 0/1 mask.
Arithmetic intensity is (C compares + C-1 logicals) per C·4 bytes — memory
bound, which is exactly why fusing (one pass, no intermediate masks)
matters.

The predicate program is *static* (baked at trace time): real systems
compile predicates once per query; specializing the kernel per query shape
is the TPU analogue.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

TILE = 2048   # lanes per grid step; multiple of 128

# comparison opcodes
OPS = ("lt", "le", "gt", "ge", "eq", "ne")


@dataclasses.dataclass(frozen=True)
class Term:
    col: int          # index into the kernel's column list
    op: str           # one of OPS
    value: float      # compare constant (f32-exact domain: ints < 2**24)


@dataclasses.dataclass(frozen=True)
class Program:
    terms: tuple[Term, ...]
    combine: str = "and"          # "and" | "or"
    negate: bool = False

    def __post_init__(self):
        if self.combine not in ("and", "or"):
            raise ValueError(self.combine)
        for t in self.terms:
            if t.op not in OPS:
                raise ValueError(t.op)


def _apply_term(cols, t: Term):
    x = cols[t.col]
    v = jnp.float32(t.value)
    return {
        "lt": lambda: x < v, "le": lambda: x <= v,
        "gt": lambda: x > v, "ge": lambda: x >= v,
        "eq": lambda: x == v, "ne": lambda: x != v,
    }[t.op]()


def _kernel(*refs, prog: Program):
    *col_refs, out_ref = refs
    cols = [r[...] for r in col_refs]          # C x (1, TILE) f32 in VMEM
    acc = _apply_term(cols, prog.terms[0])
    for t in prog.terms[1:]:
        m = _apply_term(cols, t)
        acc = jnp.logical_and(acc, m) if prog.combine == "and" \
            else jnp.logical_or(acc, m)
    if prog.negate:
        acc = jnp.logical_not(acc)
    out_ref[...] = acc.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("prog", "interpret"))
def predicate_mask(cols: Sequence[jax.Array], prog: Program, *,
                   interpret: bool = False) -> jax.Array:
    """cols: C float32 arrays of shape (N,) (N a multiple of TILE) ->
    (N,) int32 0/1 mask."""
    c, n = len(cols), cols[0].shape[0]
    if n % TILE:
        raise ValueError(f"N={n} not a multiple of {TILE}; pad in ops.py")
    spec = pl.BlockSpec((1, TILE), lambda i: (0, i))
    return pl.pallas_call(
        functools.partial(_kernel, prog=prog),
        grid=(n // TILE,),
        in_specs=[spec] * c,
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((1, n), jnp.int32),
        interpret=interpret,
        name="predicate_mask",
    )(*[col.reshape(1, n) for col in cols]).reshape(n)
