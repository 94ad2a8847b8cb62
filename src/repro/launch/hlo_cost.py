"""Trip-count-aware cost model over optimized HLO text.

``compiled.cost_analysis()`` counts each while-loop body ONCE — useless for
scan-over-layers programs (a 126-layer llama3 shows up as one layer).  This
module re-derives the roofline inputs from the HLO module text:

  * FLOPs        — every ``dot`` (2 * numel(out) * prod(contracting dims))
                   and ``convolution`` — multiplied through enclosing
                   while-loop trip counts (``known_trip_count`` backend
                   config, emitted by XLA for lax.scan).
  * HBM bytes    — per top-level instruction: operand bytes + output bytes,
                   fusions counted as single ops (their internals are
                   on-chip), multiplied through trip counts.  This is the
                   standard post-fusion HBM-traffic model.
  * collectives  — counts and operand bytes per kind, multiplied through
                   trip counts (a collective inside a scanned layer fires
                   once per layer).

Branches of ``conditional`` are charged at full cost (upper bound; the
zamba2 shared-attention cond fires on 1-in-6 layers — we report both raw
and annotated numbers where it matters).
"""
from __future__ import annotations

import dataclasses
import re
from typing import Optional

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
    "s32": 4, "u32": 4, "s64": 8, "u64": 8,
    "f8e4m3fn": 1, "f8e5m2": 1, "bf16": 2, "f16": 2, "f32": 4, "f64": 8,
    "c64": 8, "c128": 16, "token": 0, "opaque": 0, "u1": 1, "s1": 1,
}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_COMP_HDR = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->.*\{\s*$")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*((?:\([^=]*?\)|[a-z0-9]+\[[0-9,]*\]\S*))\s+([\w\-]+)")
_TRIP = re.compile(r'known_trip_count[":{\\]+n[":\\]+(\d+)')


def _shape_dims(type_str: str) -> list[tuple[str, list[int]]]:
    out = []
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt in _DTYPE_BYTES:
            out.append((dt, [int(d) for d in dims.split(",") if d]))
    return out


def _shape_bytes(type_str: str) -> int:
    total = 0
    for dt, dims in _shape_dims(type_str):
        n = 1
        for d in dims:
            n *= d
        total += n * _DTYPE_BYTES[dt]
    return total


def _numel(type_str: str) -> int:
    n_total = 0
    for _, dims in _shape_dims(type_str):
        n = 1
        for d in dims:
            n *= d
        n_total += n
    return n_total


@dataclasses.dataclass
class Instr:
    name: str
    type_str: str
    opcode: str
    line: str
    operands: list[str]


@dataclasses.dataclass
class CostTotals:
    flops: float = 0.0
    bytes: float = 0.0
    collective_bytes: dict = dataclasses.field(default_factory=dict)
    collective_counts: dict = dataclasses.field(default_factory=dict)

    def add(self, other: "CostTotals", mult: float = 1.0):
        self.flops += other.flops * mult
        self.bytes += other.bytes * mult
        for k, v in other.collective_bytes.items():
            self.collective_bytes[k] = self.collective_bytes.get(k, 0.0) + v * mult
        for k, v in other.collective_counts.items():
            self.collective_counts[k] = self.collective_counts.get(k, 0) + v * mult


class HloCostModel:
    def __init__(self, hlo_text: str):
        self.comps: dict[str, list[Instr]] = {}
        self.entry: Optional[str] = None
        self._parse(hlo_text)
        self._memo: dict[str, CostTotals] = {}

    # -- parsing -----------------------------------------------------------
    @staticmethod
    def _split_instr(line: str):
        """Parse '%name = TYPE opcode(args), attrs' robustly (tuple types
        may contain '/*index=N*/' comments and nested brackets)."""
        s = line.strip()
        if s.startswith("ROOT "):
            s = s[5:]
        if not s.startswith("%") and not s[:1].isalpha():
            return None
        eq = s.find(" = ")
        if eq < 0:
            return None
        name = s[:eq].strip().lstrip("%")
        rest = s[eq + 3:]
        if rest.startswith("("):          # tuple type: balance parens
            depth, i = 0, 0
            for i, ch in enumerate(rest):
                if ch == "(":
                    depth += 1
                elif ch == ")":
                    depth -= 1
                    if depth == 0:
                        break
            ty = rest[: i + 1]
            tail = rest[i + 1:].lstrip()
        else:
            sp = rest.find(" ")
            if sp < 0:
                return None
            ty = rest[:sp]
            tail = rest[sp + 1:]
        par = tail.find("(")
        if par < 0:
            return None
        opcode = tail[:par].strip()
        args = tail[par + 1:]
        return name, ty, opcode, args

    def _parse(self, text: str):
        cur = None
        for raw in text.splitlines():
            line = raw.rstrip()
            stripped = line.strip()
            if stripped.endswith("{") and "->" in stripped:
                hdr = _COMP_HDR.match(stripped)
                if hdr:
                    cur = hdr.group(1)
                    self.comps[cur] = []
                    if stripped.startswith("ENTRY"):
                        self.entry = cur
                    continue
            if stripped == "}":
                cur = None
                continue
            if cur is None:
                continue
            parsed = self._split_instr(line)
            if parsed is None:
                continue
            name, ty, opcode, args = parsed
            operands = re.findall(r"%([\w.\-]+)", args)
            self.comps[cur].append(Instr(name, ty, opcode, line, operands))

    def _symtab(self, comp: str) -> dict[str, str]:
        return {i.name: i.type_str for i in self.comps.get(comp, [])}

    # -- per-opcode costs ----------------------------------------------------
    def _dot_flops(self, instr: Instr, symtab: dict) -> float:
        m = re.search(r"lhs_contracting_dims=\{([0-9,]*)\}", instr.line)
        if not m:
            return 0.0
        cdims = [int(x) for x in m.group(1).split(",") if x]
        lhs_ty = symtab.get(instr.operands[0] if instr.operands else "", "")
        dims = _shape_dims(lhs_ty)
        if not dims:
            return 0.0
        _, lhs_dims = dims[0]
        k = 1
        for c in cdims:
            if c < len(lhs_dims):
                k *= lhs_dims[c]
        return 2.0 * _numel(instr.type_str) * k

    def _conv_flops(self, instr: Instr, symtab: dict) -> float:
        rhs_ty = symtab.get(instr.operands[1] if len(instr.operands) > 1 else "", "")
        dims = _shape_dims(rhs_ty)
        if not dims:
            return 0.0
        _, kdims = dims[0]
        kernel = 1
        for d in kdims[:-1]:           # all but output-feature dim
            kernel *= d
        return 2.0 * _numel(instr.type_str) * kernel

    # -- HBM traffic model ------------------------------------------------------
    def _param_effective_bytes(self, comp: str, param_idx: int, full_ty: str) -> int:
        """Bytes a fused computation actually READS of parameter ``param_idx``.

        If every use is a dynamic-slice (scan reading one layer's weights out
        of the stacked [L, ...] buffer) charge the slice sizes; if the only
        use is operand 0 of a dynamic-update-slice (in-place scan output),
        charge nothing for the read (the buffer is written, not read).
        Otherwise charge the full parameter.
        """
        instrs = self.comps.get(comp, [])
        pname = None
        for i in instrs:
            if i.opcode == "parameter" and i.line.split("parameter(")[-1].startswith(str(param_idx)):
                pname = i.name
                break
        if pname is None:
            return _shape_bytes(full_ty)
        uses = [i for i in instrs if pname in i.operands]
        if not uses:
            return 0
        total = 0
        for u in uses:
            if u.opcode == "dynamic-slice" and u.operands and u.operands[0] == pname:
                total += _shape_bytes(u.type_str)
            elif u.opcode == "dynamic-update-slice" and u.operands and u.operands[0] == pname:
                total += 0          # written in place; update counted as output
            else:
                return _shape_bytes(full_ty)
        return total

    def _instr_bytes(self, ins: Instr, symtab: dict) -> float:
        """Post-fusion HBM traffic of one top-level instruction."""
        op = ins.opcode
        if op == "dynamic-slice":
            return 2.0 * _shape_bytes(ins.type_str)
        if op == "dynamic-update-slice":
            upd = _shape_bytes(symtab.get(ins.operands[1], "")) if len(ins.operands) > 1 else 0
            return 2.0 * upd
        if op == "fusion":
            called = re.search(r"calls=%?([\w.\-]+)", ins.line)
            out_b = _shape_bytes(ins.type_str)
            if not called or called.group(1) not in self.comps:
                return out_b + sum(_shape_bytes(symtab.get(o, "")) for o in ins.operands)
            comp = called.group(1)
            # fusion whose root is a dynamic-update-slice writes only the
            # update region (scan stacking its per-iteration output into the
            # carried [L, ...] buffer) — charge the update, not the buffer.
            root = next((i for i in self.comps[comp] if "ROOT" in i.line), None)
            if root is not None and root.opcode == "dynamic-update-slice" \
                    and len(root.operands) > 1:
                sub_tab = self._symtab(comp)
                out_b = _shape_bytes(sub_tab.get(root.operands[1], "")) or out_b
            in_b = 0
            for idx, o in enumerate(ins.operands):
                full_ty = symtab.get(o, "")
                if not full_ty:
                    continue
                in_b += self._param_effective_bytes(comp, idx, full_ty)
            return out_b + in_b
        return _shape_bytes(ins.type_str) + sum(
            _shape_bytes(symtab.get(o, "")) for o in ins.operands)

    # -- computation cost -----------------------------------------------------
    def comp_cost(self, comp: str, *, fused: bool = False) -> CostTotals:
        key = f"{comp}|{fused}"
        if key in self._memo:
            return self._memo[key]
        total = CostTotals()
        symtab = self._symtab(comp)
        for ins in self.comps.get(comp, []):
            op = ins.opcode
            if op == "dot":
                total.flops += self._dot_flops(ins, symtab)
            elif op == "convolution":
                total.flops += self._conv_flops(ins, symtab)
            elif op == "while":
                trip = 1
                tm = _TRIP.search(ins.line)
                if tm:
                    trip = int(tm.group(1))
                body = re.search(r"body=%?([\w.\-]+)", ins.line)
                cond = re.search(r"condition=%?([\w.\-]+)", ins.line)
                if body:
                    total.add(self.comp_cost(body.group(1)), trip)
                if cond:
                    total.add(self.comp_cost(cond.group(1)), trip)
                continue
            elif op == "fusion":
                called = re.search(r"calls=%?([\w.\-]+)", ins.line)
                if called:
                    sub = self.comp_cost(called.group(1), fused=True)
                    total.flops += sub.flops       # dots inside fusions count
            elif op == "conditional":
                for br in re.findall(r"(?:branch_computations=\{([^}]*)\}|"
                                     r"(?:true|false)_computation=%?([\w.\-]+))",
                                     ins.line):
                    names = [n for grp in br for n in re.findall(r"%?([\w.\-]+)", grp)]
                    for n in names:
                        if n in self.comps:
                            total.add(self.comp_cost(n), 1.0)
                continue
            elif op in ("call", "custom-call", "async-start"):
                called = re.search(r"(?:to_apply|called_computations=\{)%?([\w.\-]+)", ins.line)
                if called and called.group(1) in self.comps:
                    total.add(self.comp_cost(called.group(1)), 1.0)

            kind = next((c for c in _COLLECTIVES if op.startswith(c)), None)
            if kind:
                ob = sum(_shape_bytes(symtab.get(o, "")) for o in ins.operands)
                if ob == 0:
                    ob = _shape_bytes(ins.type_str)
                total.collective_bytes[kind] = total.collective_bytes.get(kind, 0.0) + ob
                total.collective_counts[kind] = total.collective_counts.get(kind, 0) + 1

            # HBM traffic: top-level instructions only; skip pure bookkeeping
            if not fused and op not in ("parameter", "constant", "tuple",
                                        "get-tuple-element", "bitcast", "while",
                                        "conditional"):
                total.bytes += self._instr_bytes(ins, symtab)
        self._memo[key] = total
        return total

    def entry_cost(self) -> CostTotals:
        if self.entry is None:
            # fall back: largest computation
            self.entry = max(self.comps, key=lambda c: len(self.comps[c]))
        return self.comp_cost(self.entry)


def analyze(hlo_text: str) -> CostTotals:
    return HloCostModel(hlo_text).entry_cost()
