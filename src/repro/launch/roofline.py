"""Trip-count-aware HLO cost model over `compiled.as_text()`.

XLA's `compiled.cost_analysis()` counts while-loop bodies ONCE (scan
bodies are not multiplied by trip count), which undercounts the FLOPs,
bytes and collectives of a scanned solver round by its trip count.
This module parses the compiled module instead:

  * module parsed into computations and instructions,
  * dot FLOPs = 2 * prod(out_shape) * prod(lhs contracting dims)
    (operand shapes resolved through a module-wide symbol table),
  * per-instruction HBM bytes = output + operand bytes (post-fusion HLO
    is ~one kernel per instruction, XLA's own accounting convention),
  * while(body, cond) scaled by `backend_config known_trip_count`,
  * fusion instructions contribute their own I/O bytes and recurse for
    any fused dot FLOPs,
  * collectives accumulated with ring-transfer factors and classified
    intra-pod vs cross-pod from replica_groups (incl. iota form
    [G,S]<=[dims]T(perm)).

`benchmarks/bench_comm.py`, `tests/test_multihost.py` and
`tests/test_roofline.py` use it to audit the CALL round's all-reduce
bytes and the scanned round's FLOPs and bytes.

All shapes in SPMD-partitioned HLO are per-device, so every number
below is per-chip.

Roofline terms (default machine: the TPU v5e-class MachineModel in
repro.obs.roofline, re-exported by launch/mesh.py; pass any other
MachineModel to `roofline_terms`):
  t_compute = flops_per_chip / 197e12
  t_memory  = bytes_per_chip / 819e9
  t_coll    = intra_bytes / 50e9 + cross_pod_bytes / 5e9
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional, Tuple

import numpy as np

from repro.obs import roofline as obs_roofline

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16, "token": 0,
    "s4": 1, "u4": 1,
}

_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w\.\-]+)\s*=\s*(\([^=]*\)|[a-z0-9]+\[[0-9,]*\]\S*)\s+"
    r"([\w\-]+)\(")
_COMP_RE = re.compile(r"^\s*(?:ENTRY\s+)?%?([\w\.\-]+)\s+\([^)]*\)\s*->")
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_TRIP_RE = re.compile(r'known_trip_count"?\s*:\s*\{"?n"?:"?(\d+)"?\}')
_OPERAND_RE = re.compile(r"%([\w\.\-]+)")
_GROUPS_LIST_RE = re.compile(r"replica_groups=\{\{([0-9,]+)\}")
_GROUPS_IOTA_RE = re.compile(
    r"replica_groups=\[(\d+),(\d+)\]<=\[([0-9,]+)\](?:T\(([0-9,]+)\))?")

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")
_SKIP_BYTES_OPS = {
    "parameter", "constant", "tuple", "get-tuple-element", "bitcast",
    "while", "conditional", "call", "after-all", "partition-id",
    "replica-id", "iota",
}


@dataclasses.dataclass
class Instr:
    name: str
    dtype: str
    dims: Tuple[int, ...]
    opcode: str
    line: str

    @property
    def nbytes(self) -> int:
        n = 1
        for d in self.dims:
            n *= d
        return n * _DTYPE_BYTES.get(self.dtype, 4)


@dataclasses.dataclass
class Costs:
    flops: float = 0.0
    bytes: float = 0.0
    coll_intra: float = 0.0
    coll_cross: float = 0.0
    op_counts: Dict[str, float] = dataclasses.field(default_factory=dict)
    op_bytes: Dict[str, float] = dataclasses.field(default_factory=dict)

    def add(self, other: "Costs", mult: float = 1.0):
        self.flops += other.flops * mult
        self.bytes += other.bytes * mult
        self.coll_intra += other.coll_intra * mult
        self.coll_cross += other.coll_cross * mult
        for k, v in other.op_counts.items():
            self.op_counts[k] = self.op_counts.get(k, 0) + v * mult
        for k, v in other.op_bytes.items():
            self.op_bytes[k] = self.op_bytes.get(k, 0.0) + v * mult


def _parse_shape(text: str) -> Tuple[str, Tuple[int, ...]]:
    m = _SHAPE_RE.search(text)
    if not m:
        return "f32", ()
    dims = tuple(int(x) for x in m.group(2).split(",")) if m.group(2) else ()
    return m.group(1), dims


def _first_group(line: str) -> Optional[np.ndarray]:
    m = _GROUPS_LIST_RE.search(line)
    if m:
        return np.array([int(x) for x in m.group(1).split(",")])
    m = _GROUPS_IOTA_RE.search(line)
    if m:
        g, s = int(m.group(1)), int(m.group(2))
        dims = [int(x) for x in m.group(3).split(",")]
        ids = np.arange(int(np.prod(dims))).reshape(dims)
        if m.group(4):
            perm = [int(x) for x in m.group(4).split(",")]
            ids = ids.transpose(perm)
        return ids.reshape(-1)[:s]   # first group after iota/permute
    return None


class HloCostModel:
    def __init__(self, hlo_text: str, chips_per_pod: int = 256):
        self.chips_per_pod = chips_per_pod
        self.symbols: Dict[str, Instr] = {}
        self.comps: Dict[str, List[Instr]] = {}
        self.entry: Optional[str] = None
        self._memo: Dict[str, Costs] = {}
        self._parse(hlo_text)

    _RHS_RE = re.compile(
        r"^(\([^)]*\)|[a-z0-9]+\[[0-9,]*\](?:\{[^}]*\})?)\s+([\w\-]+)\(")

    def _parse(self, text: str):
        cur: Optional[str] = None
        for raw in text.splitlines():
            s = raw.strip()
            if not s or s.startswith(("//", "#")):
                continue
            if s.endswith("{") and "->" in s and " = " not in s.split("->")[0]:
                head = s.split()
                if head[0] == "ENTRY":
                    cur = head[1].lstrip("%")
                    self.entry = cur
                else:
                    cur = head[0].lstrip("%")
                self.comps[cur] = []
                continue
            if " = " in s and cur is not None:
                lhs, rhs = s.split(" = ", 1)
                name = lhs.replace("ROOT", "").strip().lstrip("%")
                m = self._RHS_RE.match(rhs)
                if not m:
                    continue
                shape_txt, opcode = m.groups()
                dtype, dims = _parse_shape(shape_txt)
                ins = Instr(name, dtype, dims, opcode, s)
                self.symbols[name] = ins
                self.comps[cur].append(ins)

    # ---- per-instruction costs -------------------------------------------

    def _operands(self, ins: Instr) -> List[Instr]:
        # operand refs inside the top-level parens of the op call
        call = ins.line.split(ins.opcode + "(", 1)
        if len(call) < 2:
            return []
        args = call[1].split(")", 1)[0]
        out = []
        for m in _OPERAND_RE.finditer(args):
            ref = self.symbols.get(m.group(1))
            if ref is not None:
                out.append(ref)
        return out

    def _dot_flops(self, ins: Instr) -> float:
        ops = self._operands(ins)
        if not ops:
            return 0.0
        lhs = ops[0]
        m = re.search(r"lhs_contracting_dims=\{([0-9,]*)\}", ins.line)
        k = 1
        if m and m.group(1):
            for di in m.group(1).split(","):
                idx = int(di)
                if idx < len(lhs.dims):
                    k *= lhs.dims[idx]
        out_elems = 1
        for d in ins.dims:
            out_elems *= d
        return 2.0 * out_elems * k

    def _conv_flops(self, ins: Instr) -> float:
        ops = self._operands(ins)
        if len(ops) < 2:
            return 0.0
        kernel_elems = 1
        for d in ops[1].dims:
            kernel_elems *= d
        out_elems = 1
        for d in ins.dims:
            out_elems *= d
        # per output element: 2 * (kernel taps per output) ~ kernel/feat
        return 2.0 * out_elems * max(1, kernel_elems // max(1, ins.dims[-1]
                                                            if ins.dims else 1))

    def _collective(self, ins: Instr, costs: Costs):
        group = _first_group(ins.line)
        gsize = len(group) if group is not None else 2
        nb = ins.nbytes
        op = ins.opcode.replace("-start", "")
        if op == "all-reduce":
            moved = 2.0 * nb * (gsize - 1) / gsize
        elif op == "all-gather":
            moved = 1.0 * nb * (gsize - 1) / gsize
        elif op == "reduce-scatter":
            moved = 1.0 * nb * (gsize - 1)
        else:
            moved = 1.0 * nb
        cross = (group is not None
                 and len({int(g) // self.chips_per_pod for g in group}) > 1)
        if cross:
            costs.coll_cross += moved
        else:
            costs.coll_intra += moved
        costs.op_counts[op] = costs.op_counts.get(op, 0) + 1
        costs.op_bytes[op] = costs.op_bytes.get(op, 0.0) + moved

    # ---- computation totals ----------------------------------------------

    def comp_costs(self, comp: str) -> Costs:
        if comp in self._memo:
            return self._memo[comp]
        total = Costs()
        self._memo[comp] = total     # break cycles defensively
        for ins in self.comps.get(comp, []):
            op = ins.opcode
            base_op = op.replace("-start", "")
            if op == "while":
                n = 1
                m = _TRIP_RE.search(ins.line)
                if m:
                    n = int(m.group(1))
                bm = re.search(r"body=%?([\w\.\-]+)", ins.line)
                cm = re.search(r"condition=%?([\w\.\-]+)", ins.line)
                if bm:
                    total.add(self.comp_costs(bm.group(1)), n)
                if cm:
                    total.add(self.comp_costs(cm.group(1)), n)
                continue
            if op == "conditional":
                branches = re.findall(
                    r"(?:true_computation|false_computation|branch_computations=\{[^}]*)"
                    r"=?%?([\w\.\-]+)", ins.line)
                if branches:
                    sub = [self.comp_costs(b) for b in branches]
                    best = max(sub, key=lambda c: c.flops + c.bytes)
                    total.add(best)
                continue
            if op == "call":
                tm = re.search(r"to_apply=%?([\w\.\-]+)", ins.line)
                if tm:
                    total.add(self.comp_costs(tm.group(1)))
                continue
            if op == "fusion":
                fm = re.search(r"calls=%?([\w\.\-]+)", ins.line)
                if fm:
                    inner = self.comp_costs(fm.group(1))
                    total.flops += inner.flops     # fused dots only
                total.bytes += ins.nbytes + sum(o.nbytes
                                                for o in self._operands(ins))
                continue
            if base_op in _COLLECTIVES:
                self._collective(ins, total)
                total.bytes += ins.nbytes + sum(o.nbytes
                                                for o in self._operands(ins))
                continue
            if op == "dot":
                total.flops += self._dot_flops(ins)
            elif op == "convolution":
                total.flops += self._conv_flops(ins)
            if op not in _SKIP_BYTES_OPS and not op.endswith("-done"):
                total.bytes += ins.nbytes + sum(o.nbytes
                                                for o in self._operands(ins))
        return total

    def entry_costs(self) -> Costs:
        assert self.entry is not None, "no ENTRY computation found"
        return self.comp_costs(self.entry)


def analyze_hlo(hlo_text: str, chips_per_pod: int = 256) -> Costs:
    return HloCostModel(hlo_text, chips_per_pod).entry_costs()


def roofline_terms(costs: Costs,
                   machine: Optional[obs_roofline.MachineModel] = None
                   ) -> Dict[str, float]:
    """Roofline time terms for `costs` on `machine` (default: the
    TPU-v5e model in `repro.obs.roofline` — the same constants
    launch/mesh.py re-exports, so existing reports are unchanged)."""
    m = machine or obs_roofline.TPU_V5E
    t_compute = costs.flops / m.peak_flops
    t_memory = costs.bytes / m.hbm_bw
    t_coll = (costs.coll_intra / m.ici_bw
              + costs.coll_cross / m.dci_bw)
    terms = {"t_compute": t_compute, "t_memory": t_memory,
             "t_collective": t_coll}
    dom = max(terms, key=terms.get)
    return {**terms, "bottleneck": dom, "t_total_max": terms[dom]}

