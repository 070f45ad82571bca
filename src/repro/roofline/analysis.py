"""Roofline-term extraction from compiled HLO.

``compiled.cost_analysis()`` on the CPU backend reports per-device FLOPs
with every ``while`` body counted ONCE (verified empirically), so it
cannot price a scanned-layer model.  This module parses the optimized
HLO text instead:

  1. split into computations, map instruction name -> result shape;
  2. build the call graph (while condition/body, conditional branches,
     fusion/call ``calls=``/``to_apply=``);
  3. recover scan trip counts from the integer constant in each while's
     condition computation;
  4. FLOPs: every dot/convolution, weighted by its control-ancestor
     multiplier;
  5. HBM bytes: operands + results of top-level instructions in control
     computations (post-fusion, each such op is one HBM round trip);
  6. collective bytes: operand bytes of all-gather / all-reduce /
     reduce-scatter / all-to-all / collective-permute, same multipliers,
     split intra-pod (ICI) vs cross-pod (DCN) by replica-group span.

All numbers are PER DEVICE (shapes in SPMD-partitioned HLO are
per-device); the roofline terms divide by the per-chip peak rates of
the dry run's target, a v5e (:func:`repro.roofline.hw.peaks`), so
  compute_term    = flops_per_device / flops_bf16
  memory_term     = hbm_bytes_per_device / hbm_bw
  collective_term = ici_bytes / ici_bw + dcn_bytes / DCN_BW.
"""
from __future__ import annotations

import dataclasses
import json
import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

from repro.roofline import hw

# --------------------------------------------------------------------------
# HLO text parsing
# --------------------------------------------------------------------------
_COMP_HEADER = re.compile(r"^(ENTRY\s+)?%?([\w\.\-]+)\s*\(.*\)\s*->\s*.+\{\s*$")
_SHAPE = re.compile(r"(f64|f32|f16|bf16|f8e4m3fn|f8e5m2|s64|s32|s16|s8|u64|u32|u16|u8|pred|c64|c128)\[([\d,]*)\]")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%([\w\.\-]+)\s*=\s*(.+?)\s+([\w\-]+)\((.*)\)(.*)$")
_CALL_ATTR = re.compile(r"(?:condition|body|to_apply|calls)=%?([\w\.\-]+)")
_BRANCH_ATTR = re.compile(r"branch_computations=\{([^}]*)\}")
_CONST_S32 = re.compile(r"s32\[\]\s+constant\((\d+)\)")
_OPERAND = re.compile(r"%([\w\.\-]+)")
_CONTRACT = re.compile(r"lhs_contracting_dims=\{([\d,]*)\}")
_REPL_GROUPS = re.compile(r"replica_groups=\[(\d+),(\d+)\]")

COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")


def _shape_bytes(type_str: str) -> int:
    """Total bytes of a (possibly tuple) HLO type string."""
    total = 0
    for m in _SHAPE.finditer(type_str):
        dt, dims = m.group(1), m.group(2)
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * hw.DTYPE_BYTES[dt]
    return total


def _result_dims(type_str: str) -> List[int]:
    m = _SHAPE.search(type_str)
    if not m:
        return []
    dims = m.group(2)
    return [int(d) for d in dims.split(",")] if dims else []


@dataclasses.dataclass
class Instr:
    name: str
    type_str: str
    opcode: str
    args: str
    attrs: str


@dataclasses.dataclass
class Computation:
    name: str
    is_entry: bool
    instrs: List[Instr]


def parse_hlo(text: str) -> Dict[str, Computation]:
    comps: Dict[str, Computation] = {}
    cur: Optional[Computation] = None
    for line in text.splitlines():
        h = _COMP_HEADER.match(line.strip())
        if h and line.rstrip().endswith("{"):
            cur = Computation(name=h.group(2), is_entry=bool(h.group(1)),
                              instrs=[])
            comps[cur.name] = cur
            continue
        if cur is None:
            continue
        if line.strip() == "}":
            cur = None
            continue
        im = _INSTR.match(line)
        if im:
            cur.instrs.append(Instr(name=im.group(1), type_str=im.group(2),
                                    opcode=im.group(3), args=im.group(4),
                                    attrs=im.group(5)))
    return comps


_TRIP_CFG = re.compile(r'"known_trip_count":\{"n":"(\d+)"\}')


def _call_edges(comp: Computation) -> List[Tuple[str, str, str, int]]:
    """(instr, callee, kind, trip) edges out of a computation."""
    edges = []
    for ins in comp.instrs:
        blob = ins.args + " " + ins.attrs
        if ins.opcode == "while":
            tm = _TRIP_CFG.search(blob)
            trip = int(tm.group(1)) if tm else 0   # 0 = unknown, use cond
            for attr, kind in (("condition", "cond"), ("body", "body")):
                m = re.search(attr + r"=%?([\w\.\-]+)", blob)
                if m:
                    edges.append((ins.name, m.group(1), kind, trip))
        else:
            for m in _CALL_ATTR.finditer(blob):
                edges.append((ins.name, m.group(1), "call", 1))
            bm = _BRANCH_ATTR.search(blob)
            if bm:
                for c in bm.group(1).split(","):
                    edges.append((ins.name, c.strip().lstrip("%"), "call", 1))
    return edges


def _trip_count(cond: Computation) -> int:
    """Largest s32 constant in the while condition — the scan bound."""
    best = 1
    for ins in cond.instrs:
        for m in _CONST_S32.finditer(ins.type_str + " " + ins.opcode + "("
                                     + ins.args + ")" + ins.attrs):
            best = max(best, int(m.group(1)))
    return best


def _propagate_multipliers(comps: Dict[str, Computation]):
    """multiplier per computation; control[name]=True if reachable via
    entry/while/branch edges (not inside a fusion)."""
    entry = next((c for c in comps.values() if c.is_entry), None)
    if entry is None:
        entry = next(iter(comps.values()))
    mult: Dict[str, float] = defaultdict(float)
    control: Dict[str, bool] = defaultdict(bool)

    def visit(name: str, m: float, is_control: bool):
        comp = comps.get(name)
        if comp is None:
            return
        mult[name] += m
        control[name] = control[name] or is_control
        # a computation can be called from several sites; the additive
        # accumulation above is what we want.
        edges = _call_edges(comp)
        for instr_name, callee, kind, trip in edges:
            if kind == "body":
                if trip <= 0:
                    cond_name = next((c for i2, c, k2, _ in edges
                                      if i2 == instr_name and k2 == "cond"),
                                     None)
                    trip = _trip_count(comps[cond_name]) \
                        if cond_name and cond_name in comps else 1
                visit(callee, m * trip, is_control)
            elif kind == "call":
                visit(callee, m, False)
    visit(entry.name, 1.0, True)
    return mult, control, entry.name


def _dot_flops(ins: Instr, shapes: Dict[str, List[int]]) -> float:
    out = 1
    for d in _result_dims(ins.type_str):
        out *= d
    cm = _CONTRACT.search(ins.attrs) or _CONTRACT.search(ins.args)
    lhs_name_m = _OPERAND.search(ins.args)
    k = 1
    if cm and lhs_name_m:
        lhs_shape = shapes.get(lhs_name_m.group(1), [])
        dims = [int(x) for x in cm.group(1).split(",") if x]
        for d in dims:
            if d < len(lhs_shape):
                k *= lhs_shape[d]
    return 2.0 * out * k


def _conv_flops(ins: Instr, shapes: Dict[str, List[int]]) -> float:
    out = 1
    for d in _result_dims(ins.type_str):
        out *= d
    ops = _OPERAND.findall(ins.args)
    if len(ops) >= 2:
        kshape = shapes.get(ops[1], [])
        if kshape:
            # kernel HWIO: spatial*in_features multiply-adds per output
            k = 1
            for d in kshape[:-1]:
                k *= d
            return 2.0 * out * k
    return 2.0 * out


@dataclasses.dataclass
class RooflineTerms:
    flops: float                 # per device
    hbm_bytes: float             # per device
    ici_bytes: float             # per device, intra-pod collectives
    dcn_bytes: float             # per device, cross-pod collectives
    collective_ops: Dict[str, float]

    @property
    def compute_s(self) -> float:
        return self.flops / hw.peaks(hw.V5E).flops_bf16

    @property
    def memory_s(self) -> float:
        return self.hbm_bytes / hw.peaks(hw.V5E).hbm_bw

    @property
    def collective_s(self) -> float:
        return (self.ici_bytes / hw.peaks(hw.V5E).ici_bw
                + self.dcn_bytes / hw.DCN_BW)

    def dominant(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    def to_dict(self) -> Dict:
        return {
            "flops_per_device": self.flops,
            "hbm_bytes_per_device": self.hbm_bytes,
            "ici_bytes_per_device": self.ici_bytes,
            "dcn_bytes_per_device": self.dcn_bytes,
            "compute_s": self.compute_s,
            "memory_s": self.memory_s,
            "collective_s": self.collective_s,
            "dominant": self.dominant(),
            "collective_ops": self.collective_ops,
        }


_SKIP_BYTES_OPS = {
    "parameter", "constant", "tuple", "get-tuple-element", "bitcast",
    "after-all", "partition-id", "replica-id", "iota",
}


def analyze_hlo(text: str, *, pod_size: int = 256) -> RooflineTerms:
    comps = parse_hlo(text)
    mult, control, entry = _propagate_multipliers(comps)

    flops = 0.0
    hbm = 0.0
    ici = 0.0
    dcn = 0.0
    coll_ops: Dict[str, float] = defaultdict(float)

    # fusion-computation facts for in-place / staging normalization
    fusion_root: Dict[str, str] = {}
    fusion_dus_update: Dict[str, int] = {}
    for cname, comp in comps.items():
        if not comp.instrs:
            continue
        last = comp.instrs[-1]
        fusion_root[cname] = last.opcode
        for ins in comp.instrs:
            if ins.opcode == "dynamic-update-slice":
                ops = _OPERAND.findall(ins.args)
                local = {i.name: _shape_bytes(i.type_str)
                         for i in comp.instrs}
                if len(ops) > 1:
                    fusion_dus_update[cname] = local.get(ops[1], 0)

    for cname, comp in comps.items():
        m = mult.get(cname, 0.0)
        if m <= 0:
            continue
        shapes = {i.name: _result_dims(i.type_str) for i in comp.instrs}
        sizes = {i.name: _shape_bytes(i.type_str) for i in comp.instrs}
        dtypes = {}
        for i in comp.instrs:
            sm = _SHAPE.search(i.type_str)
            dtypes[i.name] = sm.group(1) if sm else "f32"
        for ins in comp.instrs:
            if ins.opcode == "dot":
                flops += m * _dot_flops(ins, shapes)
            elif ins.opcode == "convolution":
                flops += m * _conv_flops(ins, shapes)
            elif ins.opcode == "fusion":
                # dots/convs inside fused computations are picked up when
                # we walk those computations (they inherit the multiplier
                # through the call edge); nothing to do here for flops.
                pass
            if not control.get(cname):
                continue
            if ins.opcode in _SKIP_BYTES_OPS or ins.opcode == "while":
                continue
            op_bytes = sizes.get(ins.name, _shape_bytes(ins.type_str))
            operand_names = _OPERAND.findall(ins.args)
            operand_bytes = sum(sizes.get(o, 0) for o in operand_names)
            # indexed/windowed ops touch only slice-sized data, not the
            # full operand buffer:
            if ins.opcode in ("dynamic-slice", "gather", "slice",
                              "concatenate", "reshape", "transpose",
                              "broadcast", "reverse", "pad"):
                total = 2 * op_bytes
            elif ins.opcode in ("dynamic-update-slice", "scatter"):
                upd = sizes.get(operand_names[1], 0) if len(operand_names) > 1 \
                    else op_bytes
                total = 2 * upd
            elif ins.opcode == "fusion":
                callee = None
                cm = _CALL_ATTR.search(ins.args + " " + ins.attrs)
                if cm:
                    callee = cm.group(1)
                if callee in fusion_dus_update:
                    # in-place update fusion (scan save-stack / KV write):
                    # TPU aliases the big buffer; traffic = the slice
                    small_ops = sum(b for o in operand_names
                                    if (b := sizes.get(o, 0)) != op_bytes)
                    total = 2 * fusion_dus_update[callee] + small_ops
                else:
                    total = op_bytes + operand_bytes
                    # CPU stages bf16 values as f32 fusion results (convert
                    # roots) — a TPU build keeps bf16: halve those.
                    if (dtypes.get(ins.name) == "f32"
                            and fusion_root.get(callee) == "convert"):
                        total -= op_bytes // 2
            else:
                total = op_bytes + operand_bytes
            # CPU-backend normalization: XLA's CPU pipeline computes bf16
            # dots in f32 and "promotes" bf16 all-reduces to f32; a TPU
            # build keeps them bf16.  Normalize so the roofline reflects
            # the TPU program, not CPU staging (EXPERIMENTS.md §Dry-run).
            if ins.opcode in ("dot", "convolution") \
                    and dtypes.get(ins.name) == "f32" \
                    and any(dtypes.get(o) == "bf16"
                            for o in operand_names):
                total -= op_bytes // 2
            if ins.opcode in COLLECTIVES:
                # all-gather: per-device traffic ~ full (output) size;
                # others: operand size
                volume = op_bytes if ins.opcode == "all-gather" \
                    else (operand_bytes if operand_bytes else op_bytes)
                if ins.opcode == "all-reduce" and (
                        "_promoted" in ins.args or "_promoted" in ins.attrs):
                    volume *= 0.5        # bf16 on TPU, f32-promoted on CPU
                rg = _REPL_GROUPS.search(ins.attrs) or _REPL_GROUPS.search(ins.args)
                span = int(rg.group(2)) if rg else 1
                groups = int(rg.group(1)) if rg else 1
                cross_pod = (groups * span > pod_size and span > pod_size) \
                    or (groups > 1 and span > pod_size)
                # ring cost factor ~ 2*(n-1)/n for all-reduce, (n-1)/n else
                factor = 2.0 if ins.opcode == "all-reduce" else 1.0
                eff = volume * factor
                coll_ops[ins.opcode] += m * eff
                if span > pod_size:
                    dcn += m * eff
                else:
                    ici += m * eff
            else:
                hbm += m * total
    return RooflineTerms(flops=flops, hbm_bytes=hbm, ici_bytes=ici,
                         dcn_bytes=dcn, collective_ops=dict(coll_ops))
