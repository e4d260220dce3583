"""Loop-aware post-SPMD HLO accounting for the roofline analysis.

``compiled.cost_analysis()`` counts each while-loop *body once*, which
undercounts scanned programs (layer stacks, attention chunks, SSM time steps)
by the trip count.  This module parses the per-device SPMD HLO module,
resolves the call graph (while / fusion / call / conditional), extracts
static trip counts from each while's condition computation, and accumulates:

  * ``flops``            — 2·M·N·K per dot (executed count, loop-multiplied),
  * ``wire_bytes``       — per-device collective traffic with ring-algorithm
                           factors (see below),
  * ``traffic_bytes``    — fusion-optimistic HBM traffic proxy: operand +
                           output bytes of dots, collective outputs, and
                           dynamic-(update-)slice/gather/scatter outputs.
                           Pure elementwise chains are assumed fused (TPU
                           behaviour), so they are *not* counted.

Per-device wire bytes (shapes in the SPMD module are already per-device):
    all-gather          out × (n-1)/n
    all-reduce          out × 2(n-1)/n
    reduce-scatter      out × (n-1)          (input = out × n)
    all-to-all          out × (n-1)/n
    collective-permute  out × 1
"""
from __future__ import annotations

import dataclasses
import re

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
    "f16": 2, "bf16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
    "f64": 8, "c64": 8, "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1,
}

_SHAPE_RE = re.compile(
    r"(?P<dt>" + "|".join(_DTYPE_BYTES) + r")\[(?P<dims>[0-9,]*)\]")
_COMP_HDR_RE = re.compile(r"^(ENTRY\s+)?%?(?P<name>[\w\.\-~]+)\s*\(.*\)\s*->")
_CALLED_RE = re.compile(
    r"(?:condition|body|calls|to_apply|branch_computations)=\{?%?"
    r"(?P<names>[\w\.\-~]+(?:, ?%[\w\.\-~]+)*)\}?")
_WHILE_RE = re.compile(
    r"while\(.*\), condition=%(?P<cond>[\w\.\-~]+), body=%(?P<body>[\w\.\-~]+)")
_CONST_RE = re.compile(r"%(?P<name>[\w\.\-~]+) = s32\[\] constant\((?P<val>\d+)\)")
_DOT_RE = re.compile(
    r"= (?P<result>[^ ]+) dot\((?P<args>[^)]*)\)(?P<attrs>.*)")
_CDIMS_RE = re.compile(r"lhs_contracting_dims=\{(?P<dims>[0-9,]*)\}")
_BDIMS_RE = re.compile(r"lhs_batch_dims=\{(?P<dims>[0-9,]*)\}")

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(?P<ng>\d+),(?P<gs>\d+)\]")
_GROUPS_RE = re.compile(r"replica_groups=\{(?P<g0>\{[^}]*\})")


def _shapes(text: str):
    for m in _SHAPE_RE.finditer(text):
        dims = [int(x) for x in m.group("dims").split(",") if x]
        n = 1
        for d in dims:
            n *= d
        yield m.group("dt"), dims, n * _DTYPE_BYTES[m.group("dt")]


def _bytes(text: str) -> int:
    return sum(b for _, _, b in _shapes(text))


def _group_size(line: str, default: int) -> int:
    m = _GROUPS_IOTA_RE.search(line)
    if m:
        return max(1, int(m.group("gs")))
    m = _GROUPS_RE.search(line)
    if m:
        ids = [x for x in m.group("g0").strip("{}").split(",") if x.strip()]
        return max(1, len(ids))
    return default


def _wire_bytes(kind: str, out_bytes: int, n: int) -> float:
    if n <= 1:
        return 0.0
    if kind == "all-gather":
        return out_bytes * (n - 1) / n
    if kind == "all-reduce":
        return out_bytes * 2 * (n - 1) / n
    if kind == "reduce-scatter":
        return out_bytes * (n - 1)
    if kind == "all-to-all":
        return out_bytes * (n - 1) / n
    return float(out_bytes)  # collective-permute


def _dot_flops(line: str, symbols: dict) -> float:
    m = _DOT_RE.search(line)
    if not m:
        return 0.0
    res = next(_shapes(m.group("result")), None)
    if res is None:
        return 0.0
    _, res_dims, _ = res
    # operands are referenced by name; resolve lhs shape via the symbol table
    args = [a.strip().lstrip("%") for a in m.group("args").split(",")]
    lhs_shape = symbols.get(args[0], "") if args else ""
    lhs = next(_shapes(lhs_shape), None)
    if lhs is None:
        # fallback: operand shapes printed inline (older HLO dumps)
        inline = list(_shapes(m.group("args")))
        if not inline:
            return 0.0
        lhs = inline[0]
    _, lhs_dims, _ = lhs
    cd = _CDIMS_RE.search(m.group("attrs"))
    contract = 1
    if cd:
        for d in cd.group("dims").split(","):
            if d:
                contract *= lhs_dims[int(d)]
    n_res = 1
    for d in res_dims:
        n_res *= d
    return 2.0 * n_res * contract


@dataclasses.dataclass
class Computation:
    name: str
    lines: list


_DEF_RE = re.compile(r"^(?:ROOT\s+)?%(?P<name>[\w\.\-~]+)\s*=\s*(?P<shape>\([^)]*\)|[^ ]+)")
_PARAM_RE = re.compile(r"%?(?P<name>[\w\.\-~]+):\s*(?P<shape>\([^)]*\)|[\w\[\],{}0-9]+)")


class Module:
    def __init__(self, text: str):
        self.comps: dict[str, Computation] = {}
        self.entry: str | None = None
        self.symbols: dict[str, str] = {}   # instruction/param name -> shape
        cur = None
        for line in text.splitlines():
            if not line.startswith(" ") and "{" in line:
                m = _COMP_HDR_RE.match(line.strip())
                if m:
                    cur = m.group("name")
                    self.comps[cur] = Computation(cur, [])
                    if line.startswith("ENTRY"):
                        self.entry = cur
                    # parameters declared in the header: name: shape
                    hdr = line[line.find("(") + 1: line.rfind("->")]
                    for pm in _PARAM_RE.finditer(hdr):
                        self.symbols[pm.group("name")] = pm.group("shape")
                    continue
            if line.startswith("}"):
                cur = None
                continue
            stripped = line.strip()
            if cur is not None and (stripped.startswith("%")
                                    or stripped.startswith("ROOT")):
                self.comps[cur].lines.append(stripped)
                dm = _DEF_RE.match(stripped)
                if dm:
                    self.symbols[dm.group("name")] = dm.group("shape")

    def trip_count(self, cond_name: str) -> int:
        """Static trip count from the condition computation's s32 constant."""
        comp = self.comps.get(cond_name)
        if comp is None:
            return 1
        consts = {}
        for line in comp.lines:
            m = _CONST_RE.search(line)
            if m:
                consts[m.group("name")] = int(m.group("val"))
        if not consts:
            return 1
        root = next((l for l in comp.lines if "ROOT" in l), "")
        for name, val in consts.items():
            if f"%{name}" in root:
                return max(1, val)
        return max(1, max(consts.values()))

    def analyze(self) -> dict:
        totals = {"flops": 0.0, "wire_bytes": 0.0, "traffic_bytes": 0.0,
                  "collectives": {}, "loops": []}
        visited_guard: set = set()

        def visit(comp_name: str, mult: float, depth: int):
            comp = self.comps.get(comp_name)
            if comp is None or depth > 32:
                return
            key = (comp_name, mult)
            for line in comp.lines:
                if " dot(" in line:
                    totals["flops"] += mult * _dot_flops(line, self.symbols)
                    # result + operand shapes (metadata carries no shapes)
                    totals["traffic_bytes"] += mult * _bytes(line)
                    continue
                coll = next((c for c in _COLLECTIVES
                             if f" {c}(" in line or f" {c}-start(" in line), None)
                if coll:
                    result = line.split("=", 1)[1].split(f" {coll}")[0]
                    ob = _bytes(result)
                    n = _group_size(line, default=2)
                    wb = mult * _wire_bytes(coll, ob, n)
                    totals["wire_bytes"] += wb
                    # XLA:CPU float-normalization upcasts bf16 dot partial
                    # sums to f32 *before* SPMD reduction; on TPU these
                    # all-reduces run in bf16 — corrected metric halves them.
                    wb_tpu = wb * (0.5 if (coll == "all-reduce"
                                           and "f32[" in result) else 1.0)
                    totals["wire_bytes_tpu"] = totals.get(
                        "wire_bytes_tpu", 0.0) + wb_tpu
                    totals["traffic_bytes"] += mult * ob
                    k = totals["collectives"].setdefault(
                        coll, {"count": 0.0, "out_bytes": 0.0,
                               "wire_bytes": 0.0})
                    k["count"] += mult
                    k["out_bytes"] += mult * ob
                    k["wire_bytes"] += wb
                    continue
                if " dynamic-update-slice(" in line:
                    # in-place on TPU: charge only the update operand (arg 1)
                    args = line.split("dynamic-update-slice(")[1].split(")")[0]
                    names = [a.strip().lstrip("%") for a in args.split(",")]
                    if len(names) >= 2:
                        totals["traffic_bytes"] += mult * _bytes(
                            self.symbols.get(names[1], ""))
                    continue
                if any(f" {op}(" in line for op in
                       ("dynamic-slice", "gather", "scatter")):
                    result = line.split("=", 1)[1].split("(")[0] if "=" in line else ""
                    totals["traffic_bytes"] += mult * _bytes(result)
                # recurse into called computations
                wm = _WHILE_RE.search(line)
                if wm:
                    trip = self.trip_count(wm.group("cond"))
                    totals["loops"].append({"body": wm.group("body"),
                                            "trip": trip, "mult": mult})
                    visit(wm.group("body"), mult * trip, depth + 1)
                    visit(wm.group("cond"), mult * trip, depth + 1)
                    continue
                cm = _CALLED_RE.search(line)
                if cm:
                    for name in cm.group("names").replace("%", "").split(","):
                        visit(name.strip(), mult, depth + 1)

        if self.entry:
            visit(self.entry, 1.0, 0)
        return totals


def analyze(hlo_text: str) -> dict:
    return Module(hlo_text).analyze()


def raw_cost_analysis(compiled) -> dict:
    """``compiled.cost_analysis()``: one dict of XLA's cost counters."""
    return dict(compiled.cost_analysis())
