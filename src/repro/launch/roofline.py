"""Roofline-term derivation from compiled dry-run artifacts.

compute_s    = per-device HLO flops / peak bf16 flops
memory_s     = per-device HLO bytes accessed / HBM bandwidth
collective_s = sum over collective ops of wire_bytes(op) / link bandwidth

``cost_analysis()`` on an SPMD executable reports per-device numbers
(verified in EXPERIMENTS.md §Dry-run).  Collective bytes are not in
cost_analysis, so we parse the partitioned HLO text: every
all-gather / all-reduce / reduce-scatter / all-to-all / collective-permute
result shape, with ring wire factors and ICI-vs-DCN classification by
whether the replica group crosses the pod boundary (device id >= 256).
"""

from __future__ import annotations

import dataclasses
import re
from typing import Any

import numpy as np

from repro.launch import mesh as mesh_mod

DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16,
}

COLL_OPS = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
            "collective-permute")

# wire factor: fraction of the RESULT (gather) or OPERAND (others) bytes
# each device puts on the wire under ring algorithms, as f(group size n)
WIRE_FACTOR = {
    "all-reduce": lambda n: 2.0 * (n - 1) / n,
    "all-gather": lambda n: (n - 1) / n,
    "reduce-scatter": lambda n: (n - 1) / n,
    "all-to-all": lambda n: (n - 1) / n,
    "collective-permute": lambda n: 1.0,
}

_SHAPE_RE = re.compile(r"(\w+)\[([0-9,]*)\]")
_LINE_RE = re.compile(
    r"=\s*((?:\([^)]*\))|(?:\w+\[[^\]]*\][^ ]*))\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")
_GROUPS_LIST_RE = re.compile(r"replica_groups=\{(\{[^}]*\}(?:,\{[^}]*\})*)\}")
_GROUPS_IOTA_RE = re.compile(
    r"replica_groups=\[(\d+),(\d+)\]<=\[([0-9,]+)\](?:T\(([0-9,]+)\))?")


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for dtype, dims in _SHAPE_RE.findall(shape_str):
        if dtype not in DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * DTYPE_BYTES[dtype]
    return total


def _parse_groups(line: str):
    """Returns (group_size, crosses_pod) for the collective on this line."""
    m = _GROUPS_LIST_RE.search(line)
    if m:
        groups = [[int(x) for x in g.split(",") if x]
                  for g in re.findall(r"\{([^}]*)\}", m.group(1))]
        size = max((len(g) for g in groups), default=1)
        crosses = any((max(g) // 256) != (min(g) // 256)
                      for g in groups if g)
        return size, crosses
    m = _GROUPS_IOTA_RE.search(line)
    if m:
        ngroups, gsize = int(m.group(1)), int(m.group(2))
        dims = [int(x) for x in m.group(3).split(",")]
        n = int(np.prod(dims))
        ids = np.arange(n).reshape(dims)
        if m.group(4):
            perm = [int(x) for x in m.group(4).split(",")]
            ids = ids.transpose(perm)
        ids = ids.reshape(ngroups, gsize)
        crosses = bool(((ids // 256).max(axis=1)
                        != (ids // 256).min(axis=1)).any())
        return gsize, crosses
    return 1, False


@dataclasses.dataclass
class Collective:
    op: str
    result_bytes: int
    group_size: int
    crosses_pod: bool
    wire_bytes: float


# -- computation structure: multiply collectives inside while bodies --------

_COMP_RE = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s+\(.*\)\s*->.*\{$")
_WHILE_RE = re.compile(
    r"while\(.*?(?:condition=%?([\w.\-]+), body=%?([\w.\-]+)"
    r"|body=%?([\w.\-]+), condition=%?([\w.\-]+))")
_CONST_RE = re.compile(r"[su](?:8|16|32|64)\[\]\s+constant\((\d+)\)")
_TRIP_RE = re.compile(r'"known_trip_count":\{"n":"(\d+)"\}')


def _split_computations(hlo_text: str):
    """-> (comps: {name: [lines]}, entry_name)."""
    comps: dict[str, list[str]] = {}
    entry = None
    cur: list[str] | None = None
    for line in hlo_text.splitlines():
        m = _COMP_RE.match(line.strip())
        if m and cur is None:
            name = m.group(2)
            comps[name] = cur = []
            if m.group(1):
                entry = name
            continue
        if cur is not None:
            if line.strip().startswith("}"):
                cur = None
            else:
                cur.append(line)
    return comps, entry


def _trip_count(cond_lines: list[str]) -> int:
    """Loop bound heuristic: the largest integer constant in the loop
    condition (induction starts at 0, compares LT bound)."""
    best = 1
    for line in cond_lines:
        for m in _CONST_RE.finditer(line):
            best = max(best, int(m.group(1)))
    return best


def computation_multipliers(hlo_text: str):
    """{computation: execution multiplier} from nested while trip counts."""
    comps, entry = _split_computations(hlo_text)
    mult: dict[str, int] = {}

    def visit(name: str, m: int):
        if name not in comps or mult.get(name, 0) >= m:
            return
        mult[name] = m
        for line in comps[name]:
            w = _WHILE_RE.search(line)
            if not w:
                continue
            cond = w.group(1) or w.group(4)
            body = w.group(2) or w.group(3)
            t = _TRIP_RE.search(line)           # XLA's own trip count
            trips = int(t.group(1)) if t else _trip_count(comps.get(cond,
                                                                    []))
            visit(body, m * trips)
            visit(cond, m * trips)

    if entry:
        visit(entry, 1)
    return comps, mult


def while_trip_counts(hlo_text: str) -> dict[str, int]:
    """Residual loops and their trip counts (diagnostic: should be empty
    or all-1 in an unrolled cost pass)."""
    comps, mult = computation_multipliers(hlo_text)
    return {name: m for name, m in mult.items()
            if m > 1 and any(_LINE_RE.search(l) for l in comps[name])}


def parse_collectives(hlo_text: str) -> list[Collective]:
    """Collectives weighted by how many times their computation executes
    (while bodies run trip-count times; cost text lists them once)."""
    comps, mult = computation_multipliers(hlo_text)
    if not comps:
        comps = {"": hlo_text.splitlines()}
        mult = {"": 1}
    out = []
    for name, lines in comps.items():
        m_exec = mult.get(name, 1)
        for line in lines:
            m = _LINE_RE.search(line)
            if not m:
                continue
            shape_str, op = m.group(1), m.group(2)
            nbytes = _shape_bytes(shape_str)
            gsize, crosses = _parse_groups(line)
            if gsize <= 1:
                continue
            wire = nbytes * WIRE_FACTOR[op](gsize)
            for _ in range(m_exec):
                out.append(Collective(op, nbytes, gsize, crosses, wire))
    return out


def cost_analysis_dict(compiled) -> dict:
    """``compiled.cost_analysis()`` normalized across jax versions: older
    releases return a one-element list of per-program dicts, newer ones a
    bare dict."""
    ca = compiled.cost_analysis() or {}
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    return dict(ca)


# ---------------------------------------------------------------------------
# Text-based flop/byte model with loop multipliers
#
# cost_analysis() counts a while body once; full unroll is too slow to
# compile for the 70B+ cells on this host.  So we re-derive flops and
# bytes-accessed from the HLO text itself and weight every computation by
# its execution count (XLA's known_trip_count).  Validated against
# cost_analysis() on loop-free graphs (tests/test_perfmodel.py).
# ---------------------------------------------------------------------------

_DEF_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(\S+)\s+([\w\-]+)")
_CONTRACT_RE = re.compile(r"lhs_contracting_dims=\{([0-9,]*)\}")
_FUSION_CALL_RE = re.compile(r"(?:calls=|to_apply=)%?([\w.\-]+)")
_SHAPE_ONLY_RE = re.compile(r"(\w+)\[([0-9,]*)\]")

_NO_TRAFFIC = {"parameter", "constant", "tuple", "get-tuple-element",
               "bitcast", "after-all", "iota", "copy-start", "copy-done",
               "while", "conditional", "call"}


def _type_bytes_dims(type_str: str):
    """(total bytes, dims-of-first-shape) for an HLO type string."""
    total = 0
    first = None
    for m in _SHAPE_ONLY_RE.finditer(type_str):
        if m.group(1) not in DTYPE_BYTES:
            continue
        dims = [int(d) for d in m.group(2).split(",") if d]
        n = DTYPE_BYTES[m.group(1)]
        for d in dims:
            n *= d
        total += n
        if first is None:
            first = dims
    return total, (first if first is not None else [])


def _operand_names(rhs: str) -> list[str]:
    """%names inside the operand parens (excludes calls=/condition= refs)."""
    start = rhs.find("(")
    if start < 0:
        return []
    depth = 0
    end = start
    for i in range(start, len(rhs)):
        if rhs[i] == "(":
            depth += 1
        elif rhs[i] == ")":
            depth -= 1
            if depth == 0:
                end = i
                break
    return re.findall(r"%([\w.\-]+)", rhs[start:end])


def text_costs(hlo_text: str) -> dict[str, float]:
    """Loop-aware per-device {flops, bytes} from the partitioned HLO.

    flops: dot contractions (2*M*N*K incl. batch dims), weighted by loop
    trip counts.  bytes: per-instruction output+operand buffer sizes
    (fusion internals excluded — the fusion call carries the traffic),
    weighted likewise.  Elementwise flops are ignored (dots dominate);
    validated against cost_analysis() on loop-free graphs.
    """
    comps, mult = computation_multipliers(hlo_text)
    if not comps:
        comps, mult = {"": hlo_text.splitlines()}, {"": 1}

    # symbol table: instruction name -> (bytes, first-shape dims)
    defs: dict[str, tuple[int, list[int]]] = {}
    for lines in comps.values():
        for line in lines:
            m = _DEF_RE.match(line)
            if m:
                defs[m.group(1)] = _type_bytes_dims(m.group(2))

    direct_flops: dict[str, float] = {}
    direct_bytes: dict[str, float] = {}
    calls: dict[str, list[str]] = {}
    for name, lines in comps.items():
        f = b = 0.0
        cl = []
        for line in lines:
            m = _DEF_RE.match(line)
            if not m:
                continue
            out_bytes, out_dims = _type_bytes_dims(m.group(2))
            opcode = m.group(3)
            rhs = line.split("=", 1)[1]
            if opcode == "dot":
                ops = _operand_names(rhs)
                cm = _CONTRACT_RE.search(line)
                if ops and cm and ops[0] in defs:
                    lhs_dims = defs[ops[0]][1]
                    k = 1
                    for idx in cm.group(1).split(","):
                        if idx:
                            k *= lhs_dims[int(idx)]
                    out_elems = 1
                    for d in out_dims:
                        out_elems *= d
                    f += 2.0 * out_elems * k
            if opcode == "fusion" or "to_apply=" in line:
                fm = _FUSION_CALL_RE.search(line)
                if fm:
                    cl.append(fm.group(1))
            if opcode not in _NO_TRAFFIC:
                b += out_bytes
                for op_name in _operand_names(rhs):
                    b += defs.get(op_name, (0, []))[0]
        direct_flops[name], direct_bytes[name] = f, b
        calls[name] = cl

    import functools

    @functools.lru_cache(maxsize=None)
    def flops_closure(name: str) -> float:
        return direct_flops.get(name, 0.0) + sum(
            flops_closure(c) for c in calls.get(name, []))

    total_f = total_b = 0.0
    for name, m_exec in mult.items():
        total_f += m_exec * (direct_flops.get(name, 0.0) + sum(
            flops_closure(c) for c in calls.get(name, [])))
        total_b += m_exec * direct_bytes.get(name, 0.0)
    return {"flops": total_f, "bytes": total_b}


def collective_summary(colls: list[Collective]) -> dict[str, Any]:
    by_op: dict[str, dict[str, float]] = {}
    for c in colls:
        d = by_op.setdefault(c.op, {"count": 0, "bytes": 0.0, "wire": 0.0})
        d["count"] += 1
        d["bytes"] += c.result_bytes
        d["wire"] += c.wire_bytes
    return by_op


def roofline_terms(flops_per_dev: float, bytes_per_dev: float,
                   colls: list[Collective]) -> dict[str, float]:
    peaks = mesh_mod.device_peaks(mesh_mod.TARGET_KIND)
    compute_s = flops_per_dev / peaks.bf16_flops
    memory_s = bytes_per_dev / peaks.hbm_bw
    ici = sum(c.wire_bytes for c in colls if not c.crosses_pod)
    dcn = sum(c.wire_bytes for c in colls if c.crosses_pod)
    collective_s = (ici / (peaks.ici_bw_per_link * peaks.ici_links_per_axis)
                    + dcn / peaks.dcn_bw)
    terms = {
        "compute_s": compute_s,
        "memory_s": memory_s,
        "collective_s": collective_s,
        "ici_wire_bytes": ici,
        "dcn_wire_bytes": dcn,
    }
    dom = max(("compute_s", "memory_s", "collective_s"),
              key=lambda k: terms[k])
    terms["bottleneck"] = dom
    terms["roofline_fraction"] = compute_s / max(
        compute_s, memory_s, collective_s)
    terms["step_time_lower_bound_s"] = max(compute_s, memory_s, collective_s)
    return terms


def model_flops(cfg, shape) -> float:
    """Useful-work FLOPs for the (arch, shape) cell (see DESIGN.md §7)."""
    n_active = cfg.active_param_count()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n_active * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n_active * tokens
    # decode: one token per sequence
    return 2.0 * n_active * shape.global_batch
