"""HLO-module analysis: roofline terms from the compiled dry-run.

XLA:CPU's ``compiled.cost_analysis()`` counts each ``lax.scan`` body ONCE
(while-loop trip counts are ignored), which under-reports flops/bytes by
~n_layers for scanned models — useless for roofline work.  This module
parses the optimized HLO text instead:

  * per-computation symbol tables (instruction -> shape);
  * dot FLOPs = 2 · prod(output dims) · prod(lhs contracting dims);
  * HBM bytes ≈ Σ operand+output bytes of materializing top-level ops
    (post-fusion HLO materializes exactly fusion/dot/copy/collective
    outputs, so this approximates true traffic well);
  * while loops multiply their body by the trip count recovered from the
    loop-condition constant;
  * collective bytes per kind (all-gather / all-reduce / reduce-scatter /
    all-to-all / collective-permute), trip-count aware.

All numbers are PER-DEVICE (the partitioned module is per-device).
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional, Tuple

# v5e hardware constants (task spec)
PEAK_FLOPS = 197e12          # bf16 per chip
HBM_BW = 819e9               # per chip
ICI_BW = 50e9                # per link

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16, "token": 0,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_COMP_HDR = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->\s*.*\{")
# the type is a tuple in parentheses or one word: a TPU layout holds
# parentheses of its own (``bf16[8,128]{1,0:T(8,128)(2,1)}``)
_INSTR = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(\(.*?\)|\S+)\s+"
    r"([\w\-]+)\((.*)$")
_OPERAND = re.compile(r"%([\w.\-]+)")
_ATTR_COMP = re.compile(r"(?:body|to_apply|condition|branch_computations)="
                        r"[{]?%?([\w.\-]+(?:,\s*%?[\w.\-]+)*)[}]?")

_SKIP_BYTES_OPS = {
    "parameter", "constant", "get-tuple-element", "tuple", "bitcast",
    "after-all", "iota", "partition-id", "replica-id",
    # `convert` at top level is XLA:CPU's bf16<->f32 staging (the TPU MXU
    # and VPU are bf16-native); counting it would charge the roofline for
    # traffic that does not exist on the target. (DESIGN.md §3)
    "convert",
    # loop/branch state is accounted inside their bodies, not at the op
    "while", "conditional",
}


def _shape_dims(shape_str: str) -> List[Tuple[str, List[int]]]:
    return [(d, [int(x) for x in dims.split(",") if x])
            for d, dims in _SHAPE_RE.findall(shape_str)]


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for dtype, dims in _shape_dims(shape_str):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims:
            n *= d
        total += n * _DTYPE_BYTES[dtype]
    return total


@dataclasses.dataclass
class _Instr:
    name: str
    shape: str
    op: str
    rest: str          # everything after the opening '('


class _Computation:
    def __init__(self, name: str):
        self.name = name
        self.instrs: List[_Instr] = []
        self.shapes: Dict[str, str] = {}

    def add(self, instr: _Instr):
        self.instrs.append(instr)
        self.shapes[instr.name] = instr.shape


def parse_module(hlo_text: str) -> Tuple[Dict[str, _Computation], str]:
    comps: Dict[str, _Computation] = {}
    entry = None
    cur: Optional[_Computation] = None
    for line in hlo_text.splitlines():
        line = re.sub(r"/\*.*?\*/", "", line)   # strip /*index=N*/ comments
        stripped = line.strip()
        if not stripped:
            continue
        hdr = _COMP_HDR.match(line if not line.startswith(" ") else "")
        if hdr:
            cur = _Computation(hdr.group(1))
            comps[cur.name] = cur
            if line.startswith("ENTRY"):
                entry = cur.name
            continue
        if cur is None:
            continue
        m = _INSTR.match(line)
        if m:
            cur.add(_Instr(m.group(1), m.group(2), m.group(3), m.group(4)))
    assert entry is not None, "no ENTRY computation found"
    return comps, entry


def _trip_count(comps, cond_name: str) -> int:
    """Scan conds compare the induction var against the trip count: find
    the compare instruction and resolve its constant operand."""
    cond = comps.get(cond_name)
    if cond is None:
        return 1
    consts = {}
    for ins in cond.instrs:
        if ins.op == "constant":
            m = re.match(r"(\d+)\)?", ins.rest)
            if m:
                consts[ins.name] = int(m.group(1))
    for ins in cond.instrs:
        if ins.op == "compare":
            for opnd in _OPERAND.findall(ins.rest):
                if opnd in consts:
                    return max(1, consts[opnd])
    return max(consts.values(), default=1)


def _dot_flops(comp: _Computation, ins: _Instr) -> float:
    out = 1
    for _, dims in _shape_dims(ins.shape):
        for d in dims:
            out *= d
    ops = _OPERAND.findall(ins.rest)
    k = 1
    m = re.search(r"lhs_contracting_dims=\{([\d,]*)\}", ins.rest)
    if m and ops:
        lhs_shape = comp.shapes.get(ops[0], "")
        dims_list = _shape_dims(lhs_shape)
        if dims_list:
            lhs_dims = dims_list[0][1]
            for idx in (int(x) for x in m.group(1).split(",") if x):
                if idx < len(lhs_dims):
                    k *= lhs_dims[idx]
    return 2.0 * out * k


@dataclasses.dataclass
class ModuleStats:
    flops: float = 0.0
    bytes: float = 0.0
    coll: Dict[str, float] = dataclasses.field(
        default_factory=lambda: {k: 0.0 for k in _COLLECTIVES})

    def scaled(self, n: int) -> "ModuleStats":
        return ModuleStats(self.flops * n, self.bytes * n,
                           {k: v * n for k, v in self.coll.items()})

    def __iadd__(self, o: "ModuleStats"):
        self.flops += o.flops
        self.bytes += o.bytes
        for k in self.coll:
            self.coll[k] += o.coll[k]
        return self


def _module_fused_names(comps) -> set:
    """Module-wide fixpoint: instruction names whose values are
    kernel-internal (vmem_fused / grouped_mm support tensors), propagated
    through metadata-less layout ops AND loop/tuple boundaries (the dense
    ragged-VJP intermediates travel through while carries — §Perf 2c)."""
    _PASS = ("transpose", "copy", "reshape", "convert", "bitcast",
             "broadcast", "get-tuple-element")
    fused = set()
    for comp in comps.values():
        for ins in comp.instrs:
            if "vmem_fused:" in ins.rest or (
                    "grouped_mm:" in ins.rest
                    and ins.op not in ("dot", "dot_general")):
                fused.add(ins.name)
    for _ in range(6):            # fixpoint (chains are short)
        grew = False
        for comp in comps.values():
            for ins in comp.instrs:
                if ins.name in fused or ins.op not in _PASS:
                    continue
                if "op_name=" in ins.rest and "fused" not in ins.rest:
                    continue
                ops0 = _OPERAND.findall(ins.rest)
                if ops0 and ops0[0] in fused:
                    fused.add(ins.name)
                    grew = True
        if not grew:
            break
    return fused


def _body_fused_fraction(comp) -> float:
    """Fraction of metadata-carrying instrs inside a vmem_fused scope —
    GSPMD drops metadata on some rewritten ops, so whole-body majority
    vote beats per-op checks for the kernel-fusion model."""
    with_md = [i for i in comp.instrs if "op_name=" in i.rest]
    if not with_md:
        return 0.0
    return sum("vmem_fused:" in i.rest for i in with_md) / len(with_md)


def _eval_comp(comps, name: str, memo, trace=None, mult=1,
               fused_kernels=False, force_fused=False,
               fused_names=None) -> ModuleStats:
    key = (name, force_fused)
    if key in memo and trace is None:
        return memo[key]
    comp = comps.get(name)
    stats = ModuleStats()
    if comp is None:
        memo[key] = stats
        return stats
    memo[key] = stats        # guard cycles
    fused_names = fused_names if fused_names is not None else set()
    for ins in comp.instrs:
        opb = 0
        fused_away = force_fused or (
            fused_kernels and (ins.name in fused_names
                               or "vmem_fused:" in ins.rest))
        if ins.op not in _SKIP_BYTES_OPS and not fused_away \
                and not _is_pure_convert(comps, ins):
            out_b = _shape_bytes(ins.shape)
            operand_bytes = [
                _shape_bytes(comp.shapes.get(opnd, ""))
                for opnd in _OPERAND.findall(
                    ins.rest.split("), ")[0] if ")" in ins.rest
                    else ins.rest)]
            if ins.op == "fusion" and not _fusion_reduces(comps, ins):
                # kLoop fusions stream element-wise (or slice a window out
                # of a big operand): each operand contributes at most what
                # the fusion actually touches ~ its output extent.
                operand_bytes = [min(b, out_b) for b in operand_bytes]
            elif ins.op in ("dynamic-slice", "slice", "gather"):
                # reads only the slice, and the "write" fuses into the
                # consumer on TPU: count the slice once.
                operand_bytes = []
            opb = out_b + sum(operand_bytes)
            if _is_inplace_update(comps, comp, ins) and operand_bytes:
                # dynamic-update-slice / scatter execute IN PLACE under
                # buffer donation: true HBM traffic is ~2x the update
                # slice, not target+output.  Drop the aliased target.
                big = max(operand_bytes)
                opb = max(0, opb - big - min(out_b, big))
        kind = next((c for c in _COLLECTIVES if ins.op.startswith(c)), None)
        if kind and not ins.op.endswith("-done"):
            stats.coll[kind] += _shape_bytes(ins.shape)
            stats.bytes += opb
        elif ins.op in ("dot", "dot_general"):
            f = _dot_flops(comp, ins)
            gm = re.search(r"grouped_mm:(\d+)", ins.rest)
            if gm:
                # XLA:CPU lowers ragged_dot densely (all E experts per
                # row); the TPU grouped matmul computes active rows only.
                f /= max(int(gm.group(1)), 1)
            stats.flops += f
            stats.bytes += opb
        elif ins.op == "while":
            m = _ATTR_COMP_BODY.search(ins.rest)
            body = cond = None
            mb = re.search(r"body=%?([\w.\-]+)", ins.rest)
            mc = re.search(r"condition=%?([\w.\-]+)", ins.rest)
            if mb:
                body = mb.group(1)
            if mc:
                cond = mc.group(1)
            trips = _trip_count(comps, cond) if cond else 1
            if body:
                bf = force_fused or (
                    fused_kernels and
                    _body_fused_fraction(comps.get(body, _Computation("")))
                    > 0.5)
                stats += _eval_comp(comps, body, memo, trace, mult * trips,
                                    fused_kernels, bf,
                                    fused_names).scaled(trips)
        elif ins.op in ("fusion", "reduce", "map", "sort", "scatter",
                        "reduce-window", "select-and-scatter"):
            m = re.search(r"(?:calls|to_apply)=%?([\w.\-]+)", ins.rest)
            if m:
                stats += _eval_comp(comps, m.group(1), memo, trace, mult,
                                    fused_kernels, force_fused,
                                    fused_names)
            stats.bytes += opb
        elif ins.op == "call":
            m = re.search(r"to_apply=%?([\w.\-]+)", ins.rest)
            if m:
                stats += _eval_comp(comps, m.group(1), memo)
        elif ins.op == "conditional":
            m = re.search(r"branch_computations=\{([^}]*)\}", ins.rest)
            if m:
                branches = [_eval_comp(comps, b.strip().lstrip("%"), memo)
                            for b in m.group(1).split(",")]
                if branches:
                    big = max(branches, key=lambda s: s.flops + s.bytes)
                    stats += big
            stats.bytes += opb
        elif ins.op in ("convolution",):
            stats.flops += 2.0 * _shape_bytes(ins.shape)  # coarse fallback
            stats.bytes += opb
        else:
            stats.bytes += opb
        if trace is not None and opb * mult > trace:
            print(f"  [trace] {opb*mult/2**30:8.2f}GiB x{mult:<4d} {ins.op:>18s} {ins.shape[:52]} {ins.rest[:60]}")
    memo[key] = stats
    return stats


_ATTR_COMP_BODY = re.compile(r"body=%?([\w.\-]+)")

_INPLACE_OPS = {"dynamic-update-slice", "scatter", "select-and-scatter"}


def _fusion_reduces(comps, ins) -> bool:
    m = re.search(r"calls=%?([\w.\-]+)", ins.rest)
    callee = comps.get(m.group(1)) if m else None
    if callee is None:
        return False
    return any(i.op in ("reduce", "reduce-window") for i in callee.instrs)


def _is_pure_convert(comps, ins) -> bool:
    """bf16->f32 convert fusions are XLA:CPU artifacts — the TPU MXU eats
    bf16 natively, so their traffic must not count toward the roofline."""
    if ins.op != "fusion":
        return False
    m = re.search(r"calls=%?([\w.\-]+)", ins.rest)
    callee = comps.get(m.group(1)) if m else None
    if callee is None:
        return False
    real = [i for i in callee.instrs
            if i.op not in ("parameter", "bitcast", "copy", "transpose",
                            "reshape")]
    return bool(real) and all(i.op == "convert" for i in real)


def _is_inplace_update(comps, comp, ins) -> bool:
    if ins.op in _INPLACE_OPS:
        return True
    if ins.op == "fusion":
        m = re.search(r"calls=%?([\w.\-]+)", ins.rest)
        callee = comps.get(m.group(1)) if m else None
        if callee and callee.instrs:
            return any(i.op in _INPLACE_OPS for i in callee.instrs[-2:])
    return False


def module_stats(hlo_text: str, trace=None,
                 fused_kernels: bool = False) -> ModuleStats:
    """fused_kernels=True models ops inside `vmem_fused:*` named scopes
    as VMEM-resident (zero HBM bytes) — they correspond 1:1 to the Pallas
    kernels in repro/kernels (flash_prefill, flash_decode, wkv6), so this
    is the roofline of the kernel-enabled deployment.  FLOPs and
    collectives are unaffected."""
    comps, entry = parse_module(hlo_text)
    fused_names = _module_fused_names(comps) if fused_kernels else set()
    return _eval_comp(comps, entry, {}, trace, 1, fused_kernels, False,
                      fused_names)


def collective_bytes(hlo_text: str) -> Dict[str, float]:
    return dict(module_stats(hlo_text).coll)


_CALLS = re.compile(r"(?:calls|to_apply|branch_computations)="
                    r"[{]?(%?[\w.\-]+(?:,\s*%?[\w.\-]+)*)[}]?")
_KNOWN_TRIPS = re.compile(r'"known_trip_count":\{"n":"(\d+)"')


def collective_calls(hlo_text: str) -> Tuple[int, int]:
    """(collectives, bytes) that one call of a compiled, partitioned
    program runs on each device: every collective instruction, an
    asynchronous ``-start``/``-done`` pair once, times the trips of the
    loops around it, and the bytes of its operands (the device's own
    shard).  The text is the compiled module's (``compiled.as_text()``)."""
    comps, entry = parse_module(hlo_text)
    memo: Dict[str, Tuple[int, int]] = {}

    def walk(name: str) -> Tuple[int, int]:
        if name in memo:
            return memo[name]
        memo[name] = (0, 0)                       # guard cycles
        comp = comps.get(name)
        n = b = 0
        for ins in comp.instrs if comp is not None else ():
            if ins.op.endswith(("-done", "-update")):
                continue
            if any(ins.op.startswith(c) for c in _COLLECTIVES):
                n += 1
                b += sum(_shape_bytes(comp.shapes.get(o, ""))
                         for o in _OPERAND.findall(ins.rest.split("), ")[0]))
            if ins.op == "while":
                body = re.search(r"body=%?([\w.\-]+)", ins.rest)
                cond = re.search(r"condition=%?([\w.\-]+)", ins.rest)
                known = _KNOWN_TRIPS.search(ins.rest)
                trips = int(known.group(1)) if known else \
                    _trip_count(comps, cond.group(1)) if cond else 1
                if body:
                    bn, bb = walk(body.group(1))
                    n, b = n + trips * bn, b + trips * bb
                continue
            for group in _CALLS.findall(ins.rest):
                for callee in group.split(","):
                    cn, cb = walk(callee.strip().lstrip("%"))
                    n, b = n + cn, b + cb
        memo[name] = (n, b)
        return n, b
    return walk(entry)


@dataclasses.dataclass
class RooflineTerms:
    """All per-device: the partitioned HLO module is one device's program."""
    flops_per_device: float
    bytes_per_device: float
    coll_bytes_per_device: float
    n_devices: int
    model_flops: float           # global useful flops (6ND / 2ND)

    @property
    def compute_s(self) -> float:
        return self.flops_per_device / PEAK_FLOPS

    @property
    def memory_s(self) -> float:
        return self.bytes_per_device / HBM_BW

    @property
    def collective_s(self) -> float:
        return self.coll_bytes_per_device / ICI_BW

    @property
    def dominant(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def useful_ratio(self) -> float:
        return self.model_flops / max(self.flops_per_device
                                      * self.n_devices, 1.0)

    def as_dict(self) -> dict:
        return {
            "flops_per_device": self.flops_per_device,
            "bytes_per_device": self.bytes_per_device,
            "coll_bytes_per_device": self.coll_bytes_per_device,
            "n_devices": self.n_devices, "model_flops": self.model_flops,
            "compute_s": self.compute_s, "memory_s": self.memory_s,
            "collective_s": self.collective_s, "dominant": self.dominant,
            "useful_ratio": self.useful_ratio,
        }


def analyze(compiled, n_devices: int, model_flops: float,
            fused_kernels: bool = False) -> RooflineTerms:
    stats = module_stats(compiled.as_text(), fused_kernels=fused_kernels)
    return RooflineTerms(
        flops_per_device=stats.flops,
        bytes_per_device=stats.bytes,
        coll_bytes_per_device=sum(stats.coll.values()),
        n_devices=n_devices,
        model_flops=model_flops,
    )
