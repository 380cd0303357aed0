"""Graph-mode per-op profiling: XLA HLO cost breakdown.

Reference parity: the reference times every graph node with cudaEvent
pairs inside `Graph::Run` and prints a per-op table via
`Device::PrintTimeProfiling` (src/core/scheduler/scheduler.cc,
SURVEY.md §5). In the TPU design the whole training step is ONE fused
XLA program, so "per-op kernel times" do not exist post-fusion; the
honest equivalent is:

  * measured wall time of the compiled step (recorded by `_JitStep`
    into the device's op-time table), plus
  * a per-HLO-instruction cost breakdown of the optimized program —
    FLOPs computed analytically from dot/convolution dimension numbers,
    bytes from operand/result shapes — with each top-level instruction
    attributed back to the layer and framework op that produced it
    via the `op_name` metadata that `autograd.Operator` stamps with
    `jax.named_scope` (`<layer path>/<Op>`, entered inside the
    differentiated function so the backward carries it too).

Estimated per-region time = (region FLOPs / program FLOPs) x measured
step time; the table says "estimated" on every such time: they are
cost-model shares, not per-kernel measurements. Rows are grouped by
layer path and direction (`_group_key`).

Measured per-scope DEVICE times come from a profiler trace joined to
the same text: a trace's event names an executed instruction
(`%fusion.786 = f32[768]{0} fusion(...)`) and carries no scope, the
optimised text carries every instruction's `op_name`, so `scope_map`
gives instruction -> scope for the process's step programs
(`step_programs`) and `scope_times` reduces plain
`(event name, t0, t1)` tuples to device self-time by scope, with the
time it could not place and why.

No TensorFlow/profiler-plugin dependency: this parses the HLO text
that PJRT already returns (`compiled.as_text()`).
"""
from __future__ import annotations

import math
import re
from collections import OrderedDict
from typing import Dict, List, Optional, Tuple

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16, "s4": 1, "u4": 1, "f8e4m3fn": 1, "f8e5m2": 1,
}

# `%name = f32[2,3]{1,0} opcode(...)` (also matches tuple-typed results
# loosely; those get shape=None).
_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?(?P<name>[\w.\-]+)\s*=\s*"
    r"(?P<dtype>[a-z][a-z0-9]*)\[(?P<shape>[0-9,]*)\]\S*\s+"
    r"(?P<opcode>[\w\-]+)\(")
_TUPLE_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?(?P<name>[\w.\-]+)\s*=\s*\("
    r".*?\)\s+(?P<opcode>[\w\-]+)\(")
_OPERANDS_RE = re.compile(r"\(([^)]*)\)")
_OPNAME_RE = re.compile(r'op_name="([^"]*)"')
_CALLS_RE = re.compile(r"calls=%?([\w.\-]+)")
_CONTRACT_RE = re.compile(r"lhs_contracting_dims=\{([0-9,]*)\}")
_DIMLABELS_RE = re.compile(r"dim_labels=(\w+)_(\w+)->(\w+)")


def _shape_of(type_str: str):
    m = re.match(r"([a-z][a-z0-9]*)\[([0-9,]*)\]", type_str)
    if not m:
        return None
    dims = [int(d) for d in m.group(2).split(",") if d] if m.group(2) else []
    return m.group(1), dims


def _numel(dims: List[int]) -> int:
    return int(math.prod(dims)) if dims else 1


def _split_operands(s: str) -> List[str]:
    """Split an operand list on top-level commas only — inline types
    (`f32[8,32]{1,0} %arg`) carry commas inside brackets/braces that a
    naive split would tear."""
    out, depth, cur = [], 0, []
    for ch in s:
        if ch in "[{(":
            depth += 1
        elif ch in "]})":
            depth -= 1
        if ch == "," and depth == 0:
            out.append("".join(cur).strip())
            cur = []
        else:
            cur.append(ch)
    tail = "".join(cur).strip()
    if tail:
        out.append(tail)
    return out


def _operand_shape(op_str: str, shapes: Dict[str, tuple]):
    """(dtype, dims) for one operand reference. Post-optimization text
    spells operands WITH their inline type (`f32[8,32]{1,0} %Arg_0.1`)
    — parse that directly; pre-optimization text spells just `%name`,
    which resolves through the module-wide shape table."""
    toks = op_str.split()
    if not toks:
        return None
    sh = _shape_of(toks[0])
    if sh:
        return sh
    return shapes.get(toks[-1].lstrip("%"))


class _Instr:
    __slots__ = ("name", "dtype", "dims", "opcode", "line")

    def __init__(self, name, dtype, dims, opcode, line):
        self.name, self.dtype, self.dims = name, dtype, dims
        self.opcode, self.line = opcode, line


def _parse_computations(hlo_text: str) -> Dict[str, List[_Instr]]:
    """Split module text into computations -> instruction lists."""
    comps: Dict[str, List[_Instr]] = {}
    current: Optional[str] = None
    for raw in hlo_text.splitlines():
        line = raw.rstrip()
        if current is None:
            s = line.strip()
            # Header forms: post-optimization text spells
            # `[ENTRY] %name (params) -> result {` and pre-optimization
            # HLO (`lowered.as_text(dialect="hlo")`) just
            # `[ENTRY] name {`. Matched structurally rather than by a
            # single regex: a while/scan BODY computation carries its
            # carry tuple as a parameter, and tuple-typed params nest
            # parens a regex can't bound (`%region_0.180.clone (arg:
            # (s32[], f32[5]{0}, ...)) -> (...) {`) — those bodies are
            # exactly what peak_bytes_estimate must see inside.
            if s.endswith("{") and " = " not in s.split("->")[0]:
                head = (s.split("->")[0] if "->" in s
                        else s[:-1].strip())
                tok = head.split()
                name = None
                if "->" in s and tok:
                    name = (tok[1] if tok[0] == "ENTRY"
                            and len(tok) > 1 else tok[0])
                elif len(tok) == 2 and tok[0] == "ENTRY":
                    name = tok[1]
                elif len(tok) == 1:
                    name = tok[0]
                if name:
                    name = name.lstrip("%").split("(")[0]
                if name:
                    current = name
                    comps[current] = []
            continue
        if line.strip() == "}":
            current = None
            continue
        m = _INSTR_RE.match(line)
        if m:
            dims = ([int(d) for d in m.group("shape").split(",") if d]
                    if m.group("shape") else [])
            comps[current].append(_Instr(
                m.group("name"), m.group("dtype"), dims,
                m.group("opcode"), line))
            continue
        m = _TUPLE_INSTR_RE.match(line)
        if m:
            comps[current].append(_Instr(
                m.group("name"), None, None, m.group("opcode"), line))
    return comps


def _instr_flops(ins: _Instr, shapes: Dict[str, tuple]) -> float:
    """Analytic FLOPs for one instruction (0 for data movement)."""
    op = ins.opcode
    if op in ("parameter", "constant", "tuple", "get-tuple-element",
              "bitcast", "copy", "reshape", "transpose", "broadcast",
              "slice", "concatenate", "gather", "scatter", "pad",
              "dynamic-slice", "dynamic-update-slice", "iota",
              "convert", "reverse", "copy-start", "copy-done",
              "all-gather", "all-reduce", "reduce-scatter",
              "collective-permute", "partition-id", "replica-id"):
        return 0.0
    out_n = _numel(ins.dims) if ins.dims is not None else 0
    if op == "dot":
        m = _OPERANDS_RE.search(ins.line)
        c = _CONTRACT_RE.search(ins.line)
        if m and c:
            ops = _split_operands(m.group(1))
            lhs = _operand_shape(ops[0], shapes) if ops else None
            if lhs:
                cdims = [int(d) for d in c.group(1).split(",") if d]
                k = _numel([lhs[1][d] for d in cdims if d < len(lhs[1])])
                return 2.0 * out_n * k
        return 2.0 * out_n  # fallback
    if op == "convolution":
        m = _OPERANDS_RE.search(ins.line)
        dl = _DIMLABELS_RE.search(ins.line)
        if m and dl:
            ops = _split_operands(m.group(1))
            rhs = (_operand_shape(ops[1], shapes)
                   if len(ops) > 1 else None)
            if rhs:
                o_pos = dl.group(2).index("o")
                rhs_n = _numel(rhs[1])
                o_size = rhs[1][o_pos] if o_pos < len(rhs[1]) else 1
                return 2.0 * out_n * rhs_n / max(o_size, 1)
        return 2.0 * out_n
    if op in ("exponential", "log", "tanh", "logistic", "power", "rsqrt",
              "sqrt", "sine", "cosine", "erf", "atan2", "expm1",
              "log-plus-one", "cbrt"):
        return 8.0 * out_n  # transcendental: several flops each
    if op == "reduce":
        # ~1 flop per reduced input element; approximate via operand.
        m = _OPERANDS_RE.search(ins.line)
        if m:
            ops = _split_operands(m.group(1))
            src = _operand_shape(ops[0], shapes) if ops else None
            if src:
                return float(_numel(src[1]))
        return float(out_n)
    if op in ("reduce-window", "select-and-scatter"):
        return float(out_n) * 9.0  # window size unknown; assume 3x3-ish
    if op == "rng-bit-generator":
        return 16.0 * out_n
    # default: elementwise-ish, 1 flop/element
    return float(out_n)


def _instr_bytes(ins: _Instr) -> float:
    if ins.dims is None or ins.dtype is None:
        return 0.0
    return float(_numel(ins.dims)) * _DTYPE_BYTES.get(ins.dtype, 4)


def _entry_name(comps: Dict[str, List[_Instr]]) -> str:
    """The ENTRY computation: jax names it e.g. "main.123"; fall back
    to the last computation parsed."""
    entry = None
    for name in comps:
        if name.startswith("main"):
            entry = name
    return entry if entry is not None else list(comps.keys())[-1]


def _module_shapes(comps: Dict[str, List[_Instr]]) -> Dict[str, tuple]:
    """name -> (dtype, dims) over every instruction in the module."""
    shapes: Dict[str, tuple] = {}
    for instrs in comps.values():
        for ins in instrs:
            if ins.dims is not None:
                shapes[ins.name] = (ins.dtype, ins.dims)
    return shapes


def _op_label(ins: _Instr) -> str:
    """Framework-op attribution for one instruction: the named_scope
    op_name path (jit prefix stripped), else the HLO value name."""
    opname = _OPNAME_RE.search(ins.line)
    # XLA joins the op_names of instructions it merges with ";": the
    # first is the instruction's own
    label = opname.group(1).split(";")[0] if opname else ins.name
    return re.sub(r"^jit\([^)]*\)/", "", label)


# Segments of an op_name that say how a program is built, not where in
# the model an operation is: transforms' and control flow's own.
_CONTROL = r"while|body|cond|branch_\d+_fun"
_CONTROL_RE = re.compile(rf"^(?:{_CONTROL})$")
_STRUCTURAL_RE = re.compile(
    rf"^(?:{_CONTROL}|checkpoint|rematted_computation|shard_map|pjit|"
    r"closed_call|core_call|custom_[jv][vj]p_call\w*|jit\(.*\)|"
    r"vmap\(.*\))$")
_DIFF_RE = re.compile(r"^(transpose\()?jvp\((.*)\)$")


def _segments(label: str) -> List[str]:
    """`label` split on the slashes outside brackets: a differentiated
    scope, `transpose(jvp(a.b/Op))`, is one segment."""
    return [seg for seg in _split_top(label, "/") if seg]


def _place(segs: List[str]) -> Tuple[str, bool]:
    """(scope, under a transpose?) of an op_name's segments, the
    primitive's own name already off."""
    bwd, plain = False, []
    for seg in segs:
        m = _DIFF_RE.match(seg)
        if not m:
            plain.append(seg)
            continue
        bwd = bwd or bool(m.group(1))
        inner = m.group(2)[:-1] if m.group(1) else m.group(2)
        scope, inner_bwd = _place(_segments(inner))
        if scope:
            return scope, bwd or inner_bwd
        # an empty wrapper (`transpose(jvp())` around a rematerialised
        # region): what follows it says where
    # what a loop's body or a branch holds is placed by the scope it
    # entered itself; the loop's own bookkeeping by the scope around it
    cut = max((i + 1 for i, x in enumerate(plain) if _CONTROL_RE.match(x)),
              default=0)
    for part in (plain[cut:], plain[:cut]):
        scope = "/".join(x for x in part if not _STRUCTURAL_RE.match(x))
        if scope:
            return scope, bwd
    return "", bwd


def scope_of(label: str) -> Tuple[str, str]:
    """(scope, direction) of an instruction's op_name, its `jit(..)/`
    prefix off. The scope is what the program entered: `<layer
    path>/<Op>` (autograd.Operator) or `opt/<class>/<parameter>`
    (opt.Optimizer), without the primitive's name at the end and
    without the segments transforms and control flow add; inside a
    loop's body or a conditional's branch, the scope entered there.
    The direction is "bwd" for `transpose(jvp(<scope>))/…` (and for
    what a rematerialised region recomputes under an empty
    `transpose(jvp())`), "fwd" for `jvp(<scope>)/…` and for a scope
    that was not differentiated, "" for the optimizer's and where
    there is no scope ("", "")."""
    scope, bwd = _place(_segments(label)[:-1])
    if not scope:
        return "", ""
    if scope.startswith("opt/"):
        return scope, ""
    return scope, "bwd" if bwd else "fwd"


def _group_key(label: str, fallback: str) -> str:
    """Group label (how both aggregate() and bytes_accessed() bucket):
    the layer path with the direction, `<layer path> fwd|bwd`, so the
    forward and the backward of one layer are two rows side by side
    whatever ops the layer is made of; the optimizer's instructions
    by `opt/<class>` (or `opt/<glue>`); `fallback` where an
    instruction has no scope."""
    scope, direction = scope_of(label)
    if not scope:
        return fallback
    if not direction:
        return "/".join(scope.split("/")[:2])
    return f"{scope.split('/', 1)[0]} {direction}"


def profile_hlo(hlo_text: str) -> List[dict]:
    """Per top-level-instruction cost rows for the ENTRY computation.

    Returns rows {op, hlo, flops, out_bytes} where `op` is the
    framework-level op_name path (from named_scope metadata) and
    fusions include their fused computation's FLOPs.
    """
    comps = _parse_computations(hlo_text)
    if not comps:
        return []
    entry = _entry_name(comps)
    shapes = _module_shapes(comps)

    # FLOPs per computation (for fusion attribution); resolve nested
    # calls iteratively to a fixed point.
    comp_flops: Dict[str, float] = {}
    for _ in range(4):
        for cname, instrs in comps.items():
            total = 0.0
            for ins in instrs:
                if ins.opcode == "fusion" or ins.opcode in ("call", "map"):
                    cm = _CALLS_RE.search(ins.line)
                    if cm:
                        total += comp_flops.get(cm.group(1), 0.0)
                        continue
                total += _instr_flops(ins, shapes)
            comp_flops[cname] = total

    rows: List[dict] = []
    for ins in comps[entry]:
        if ins.opcode in ("parameter", "constant", "tuple",
                          "get-tuple-element"):
            continue
        if ins.opcode in ("fusion", "call", "map"):
            cm = _CALLS_RE.search(ins.line)
            flops = comp_flops.get(cm.group(1), 0.0) if cm else 0.0
        else:
            flops = _instr_flops(ins, shapes)
        rows.append({"op": _op_label(ins), "hlo": ins.opcode,
                     "flops": flops, "out_bytes": _instr_bytes(ins)})
    return rows


def _operand_bytes(ins: _Instr, shapes: Dict[str, tuple]) -> float:
    """Bytes read by one instruction: sum of operand shapes. Operand
    tokens in optimized HLO text carry their type (`f32[2,3]{1,0}
    %name`) — parse it directly; bare `%name` tokens fall back to the
    module-wide shape map."""
    m = _OPERANDS_RE.search(ins.line)
    if not m:
        return 0.0
    total = 0.0
    # split on ", " (the operand separator): dims inside `f32[8,12]`
    # carry bare commas and must not split
    for tok in m.group(1).split(", "):
        tok = tok.strip()
        sh = _shape_of(tok)
        if sh is None:
            name = tok.lstrip("%").split(" ")[0]
            sh = shapes.get(name)
        if sh is not None:
            total += float(_numel(sh[1])) * _DTYPE_BYTES.get(sh[0], 4)
    return total


def bytes_accessed(hlo_text: str) -> dict:
    """Estimated HBM bytes accessed by the program's ENTRY computation:
    per top-level instruction, operand bytes (reads) + result bytes
    (writes). Fusion-internal temporaries don't count — exactly the
    property that makes this the byte-diet meter: a knob that keeps
    data half-width ACROSS fusion boundaries (bf16 optimizer slots,
    bf16 BN statistics) shows up here, CPU-verifiable, no chip needed.

    Returns {"total": float, "reads": float, "writes": float,
    "by_op": {framework-op-path: bytes}} — `by_op` groups by the same
    named_scope attribution `aggregate()` uses.
    """
    comps = _parse_computations(hlo_text)
    if not comps:
        return {"total": 0.0, "reads": 0.0, "writes": 0.0, "by_op": {}}
    shapes = _module_shapes(comps)
    reads = writes = 0.0
    by_op: Dict[str, float] = {}
    for ins in comps[_entry_name(comps)]:
        if ins.opcode in ("parameter", "constant", "tuple",
                          "get-tuple-element", "bitcast"):
            continue
        r = _operand_bytes(ins, shapes)
        w = _instr_bytes(ins)
        reads += r
        writes += w
        key = _group_key(_op_label(ins), ins.opcode)
        by_op[key] = by_op.get(key, 0.0) + r + w
    return {"total": reads + writes, "reads": reads, "writes": writes,
            "by_op": by_op}


# Instructions that call other computations whose internals DO
# materialize buffers (control flow). Fusions are deliberately opaque:
# a fusion's intermediates live in registers/VMEM, not HBM — counting
# them would overstate every fused program's peak.
_PEAK_RECURSE_OPS = ("while", "call", "conditional")
_CALLEE_RE = re.compile(
    r"(?:calls|to_apply|body|condition|branch_computations)="
    r"(\{[^}]*\}|%?[\w.\-]+)")


def _instr_callees(ins: _Instr) -> List[str]:
    out = []
    for m in _CALLEE_RE.finditer(ins.line):
        val = m.group(1)
        if val.startswith("{"):
            out.extend(v.strip().lstrip("%")
                       for v in val[1:-1].split(",") if v.strip())
        else:
            out.append(val.lstrip("%"))
    return out


def _split_top(seg: str, sep: str = ",") -> List[str]:
    """Split on `sep` at bracket depth 0 — operand TYPES carry
    commas of their own (`f32[8,8]`, tuple types `(f32[], s32[])`),
    and a differentiated scope its slashes (`jvp(a.b/Op)`)."""
    out, cur, depth = [], [], 0
    for ch in seg:
        if ch in "([{":
            depth += 1
        elif ch in ")]}":
            depth -= 1
        if ch == sep and depth == 0:
            out.append("".join(cur))
            cur = []
        else:
            cur.append(ch)
    if cur:
        out.append("".join(cur))
    return out


def _operand_names(ins: _Instr) -> List[str]:
    """Operand value names of one instruction. The operand list is
    the balanced paren group FOLLOWING the opcode — `_OPERANDS_RE`
    (first paren group on the line) would grab the result TYPE of
    tuple-typed instructions (`%t = (f32[], s32[]) tuple(%a, %b)`),
    mis-freeing every value whose last use is a tuple/while/ROOT
    tuple and under-counting the peak."""
    idx = ins.line.find(ins.opcode + "(")
    if idx < 0:
        return []
    start = idx + len(ins.opcode)
    depth, end = 0, None
    for j in range(start, len(ins.line)):
        ch = ins.line[j]
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
            if depth == 0:
                end = j
                break
    if end is None:
        return []
    out = []
    for tok in _split_top(ins.line[start + 1:end]):
        tok = tok.strip()
        if tok:
            out.append(tok.split(" ")[-1].lstrip("%"))
    return out


def _comp_peak(name: str, comps: Dict[str, List[_Instr]],
               memo: Dict[str, float]) -> float:
    """Max live bytes over one computation's instruction sequence:
    parameters are live throughout (the caller holds them), each
    result is live from its definition to its last textual use (the
    ROOT result to the end), and control-flow instructions add their
    callee computation's own peak as a transient at the call point
    (while takes max(body, condition) — they never run
    simultaneously). An analytic estimate, not a buffer-assignment
    readout — but it moves with the program's real liveness, which is
    what makes a remat knob's effect visible on CPU."""
    if name in memo:
        return memo[name]
    memo[name] = 0.0  # cycle guard (HLO call graphs are acyclic)
    instrs = comps.get(name, [])
    opnames = [_operand_names(ins) for ins in instrs]
    last_use: Dict[str, int] = {}
    for i, names in enumerate(opnames):
        for nm in names:
            last_use[nm] = i
    base = sum(_instr_bytes(i) for i in instrs
               if i.opcode == "parameter")
    cur = peak = base
    live: Dict[str, float] = {}
    for i, ins in enumerate(instrs):
        if ins.opcode == "parameter":
            continue
        b = _instr_bytes(ins)
        live[ins.name] = b
        cur += b
        transient = 0.0
        if ins.opcode in _PEAK_RECURSE_OPS:
            subs = [_comp_peak(c, comps, memo)
                    for c in _instr_callees(ins) if c in comps]
            if ins.opcode == "while" and subs:
                transient = max(subs)
            else:
                transient = sum(subs) if ins.opcode == "call" \
                    else (max(subs) if subs else 0.0)
        if cur + transient > peak:
            peak = cur + transient
        for nm in opnames[i]:
            if last_use.get(nm) == i:
                cur -= live.pop(nm, 0.0)
        if (not ins.line.lstrip().startswith("ROOT")
                and last_use.get(ins.name, i) <= i):
            cur -= live.pop(ins.name, 0.0)
    memo[name] = peak
    return peak


def peak_bytes_estimate(hlo_text: str) -> float:
    """Estimated peak live bytes of the program's ENTRY computation:
    the max, over the instruction sequence, of (parameters + results
    still awaiting a later use + the internal peak of any control-flow
    callee active at that point). The memory-side companion of
    `bytes_accessed`: a byte-DIET knob (bf16 slots/stats) moves the
    traffic meter; a REMAT knob (`device.set_remat_policy`) moves this
    one — fewer activations survive the fwd→bwd boundary, so the max
    live set shrinks even though recompute adds traffic. CPU-
    verifiable via `Model.step_hlo_text`, no chip needed
    (tests/test_remat_policy.py pins that `dots_saveable` strictly
    lowers it for a conv model under grad accumulation)."""
    comps = _parse_computations(hlo_text)
    if not comps:
        return 0.0
    return _comp_peak(_entry_name(comps), comps, {})


def aggregate(rows: List[dict], top: int = 0) -> List[dict]:
    """Group rows by layer path and direction (`_group_key`)."""
    groups: Dict[str, dict] = {}
    for r in rows:
        key = _group_key(r["op"], r["hlo"])
        g = groups.setdefault(key, {"op": key, "flops": 0.0,
                                    "out_bytes": 0.0, "count": 0})
        g["flops"] += r["flops"]
        g["out_bytes"] += r["out_bytes"]
        g["count"] += 1
    out = sorted(groups.values(), key=lambda g: -g["flops"])
    return out[:top] if top else out


def format_table(rows: List[dict], measured_step_s: Optional[float] = None,
                 top: int = 25) -> str:
    """Human-readable graph profile table (printed by
    Device.PrintTimeProfiling when graph-mode profiles exist): one row
    a layer and direction. The step time is measured; a row's time is
    its FLOP share of it, and says "estimated"."""
    agg = aggregate(rows, top=top)
    total_flops = sum(r["flops"] for r in rows) or 1.0
    lines = ["Graph (XLA) cost profile"
             + (f"  [measured step: {measured_step_s * 1e3:.2f} ms]"
                if measured_step_s else "")
             + f"  total ~{total_flops / 1e9:.2f} GFLOP:"]
    for g in agg:
        pct = 100.0 * g["flops"] / total_flops
        est = (f"  estimated "
               f"{measured_step_s * g['flops'] / total_flops * 1e3:8.3f} ms"
               if measured_step_s else "")
        lines.append(
            f"  OP = {g['op']:<48} FLOPs = {g['flops'] / 1e6:12.2f} M "
            f"({pct:5.1f}%) x {g['count']:<4d}{est}")
    return "\n".join(lines)


# ---------------------------------------------------------------------------
# The join: a device trace's events -> the scopes of the program that
# ran them, through the program's own optimised HLO.
# ---------------------------------------------------------------------------
# Instructions the device runs no operation of its own for.
_NO_EVENT_OPS = ("parameter", "constant", "tuple", "get-tuple-element",
                 "bitcast")
_EVENT_CALLERS = ("while", "call", "conditional", "async-start")
_MODULE_RE = re.compile(r"^HloModule\s+([\w.\-]+)")


def _shape_str(ins: _Instr) -> Optional[str]:
    if ins.dims is None:
        return None
    return f"{ins.dtype}[{','.join(str(d) for d in ins.dims)}]"


def _fused_label(ins: _Instr, comps) -> str:
    """A fusion without metadata of its own (the compiler made it:
    `wrapped_reduce-window`) takes the op_name most of its fused
    computation's instructions carry."""
    cm = _CALLS_RE.search(ins.line)
    votes: Dict[str, int] = {}
    for inner in comps.get(cm.group(1), []) if cm else []:
        if _OPNAME_RE.search(inner.line):
            lab = _op_label(inner)
            votes[lab] = votes.get(lab, 0) + 1
    return max(votes, key=votes.get) if votes else ""


def scope_map(hlo_text: str) -> dict:
    """instruction -> scope for every computation of an optimised HLO
    text whose instructions the device runs as operations of their
    own: the entry, `while` bodies and conditions, called
    computations, a conditional's branches (a fusion's computation is
    ONE operation: the fusion, under its own metadata).

    {"module": the HLO module's name (a trace's "XLA Modules" event is
    named `<module>(<id>)`), "instructions": {name: {"shape":
    "f32[8,4]" or None for a tuple, "opcode", "scope", "dir"}} as
    `scope_of` gives them, "scoped" / "unscoped": how many carry a
    scope — the map's own coverage, which a program handed back by a
    compile cache that an older tree warmed brings down (jax's default
    key leaves metadata out; `device.use_compile_cache` puts it in)}."""
    comps = _parse_computations(hlo_text)
    m = _MODULE_RE.match(hlo_text.lstrip())
    out = {"module": m.group(1) if m else "", "instructions": {},
           "scoped": 0, "unscoped": 0}
    if not comps:
        return out
    todo, seen = [_entry_name(comps)], set()
    while todo:
        cname = todo.pop()
        if cname in seen or cname not in comps:
            continue
        seen.add(cname)
        for ins in comps[cname]:
            if ins.opcode in _EVENT_CALLERS:
                todo.extend(_instr_callees(ins))
            if ins.opcode in _NO_EVENT_OPS:
                continue
            has = _OPNAME_RE.search(ins.line)
            label = (_op_label(ins) if has else
                     _fused_label(ins, comps) if ins.opcode == "fusion"
                     else "")
            scope, direction = scope_of(label) if label else ("", "")
            out["instructions"][ins.name] = {
                "shape": _shape_str(ins), "opcode": ins.opcode,
                "scope": scope, "dir": direction}
            out["scoped" if scope else "unscoped"] += 1
    return out


def _event_instr(name: str):
    """(instruction name, result shape or None, opcode) of a trace
    event, which is named by its instruction's whole text without
    `metadata=`; a bare name ("fusion.3") is its own."""
    m = _INSTR_RE.match(name)
    if m:
        return (m.group("name"), f"{m.group('dtype')}[{m.group('shape')}]",
                m.group("opcode"))
    m = _TUPLE_INSTR_RE.match(name)
    if m:
        return m.group("name"), None, m.group("opcode")
    bare = name.strip().lstrip("%")
    return bare, None, bare.split(".", 1)[0]


def _self_times(events) -> List[int]:
    """Per event, its duration less what events nested inside it cover
    (a `while` holds its body's operations), so a sum over scopes
    counts no nanosecond twice. `events` sorted by start."""
    out = [0] * len(events)
    stack: List[int] = []
    for i, (_, t0, t1) in enumerate(events):
        while stack and events[stack[-1]][2] <= t0:
            stack.pop()
        if stack:
            out[stack[-1]] -= min(t1, events[stack[-1]][2]) - t0
        out[i] += t1 - t0
        stack.append(i)
    return out


def scope_times(events, smap: dict, modules=None) -> dict:
    """Device self-time by scope, from one chip's `(event name, t0,
    t1)` tuples (any one time unit) and a `scope_map`. An event is
    matched by its instruction's name AND result shape and, where
    `modules` gives the chip's "XLA Modules" events, only inside an
    event of the map's module.

    {"total": self-time of the events considered, "rows": [{"scope",
    "dir", "time", "events"}] heaviest first, "unplaced": {"not in
    map": …, "no scope": …} (an event of no instruction of the map, or
    of one with another shape: another program's, or a stale text; an
    instruction the program entered no scope for: the compiler's
    copies, an unscoped piece of glue), "unplaced_by_opcode": the same
    time by HLO opcode, "matched" / "unmatched": events, "elsewhere":
    self-time inside other modules' events, not in "total"}. The rows
    and the unplaced parts add up to "total"."""
    events = sorted(events, key=lambda e: (e[1], -e[2]))
    selfs = _self_times(events)
    spans = None
    if modules is not None:
        spans = sorted((t0, t1) for name, t0, t1 in modules
                       if name.split("(")[0] == smap["module"])
    instrs = smap["instructions"]
    rows: Dict[tuple, list] = {}
    unplaced = {"not in map": 0, "no scope": 0}
    by_opcode: Dict[str, int] = {}
    total = elsewhere = matched = unmatched = 0
    i = 0
    for (name, t0, _), dt in zip(events, selfs):
        if spans is not None:
            while i < len(spans) and spans[i][1] <= t0:
                i += 1
            if i == len(spans) or t0 < spans[i][0]:
                elsewhere += dt
                continue
        total += dt
        iname, shape, opcode = _event_instr(name)
        ent = instrs.get(iname)
        if ent is None or (shape is not None and ent["shape"] is not None
                           and shape != ent["shape"]):
            why = "not in map"
            unmatched += 1
        else:
            matched += 1
            if ent["scope"]:
                row = rows.setdefault((ent["scope"], ent["dir"]), [0, 0])
                row[0] += dt
                row[1] += 1
                continue
            why = "no scope"
        unplaced[why] += dt
        by_opcode[opcode] = by_opcode.get(opcode, 0) + dt
    return {
        "total": total,
        "rows": [{"scope": k[0], "dir": k[1], "time": v[0], "events": v[1]}
                 for k, v in sorted(rows.items(), key=lambda kv: -kv[1][0])],
        "unplaced": unplaced,
        "unplaced_by_opcode": dict(sorted(by_opcode.items(),
                                          key=lambda kv: -kv[1])),
        "matched": matched, "unmatched": unmatched,
        "elsewhere": elsewhere}


# The process's most recent step programs: `model._JitStep` (and its
# sharded subclass) hands over the `jax.stages.Lowered` of each step it
# is about to call. A `Lowered` holds a module and its compile
# arguments, no array and nothing of the model, so a program can be
# read after its model is gone (a benchmark's readers run when the
# training loop has returned); the newest `_KEEP_PROGRAMS` stay.
_KEEP_PROGRAMS = 8
_STEP_PROGRAMS: "OrderedDict[int, object]" = OrderedDict()


def note_step_program(step, lowered) -> None:
    """`step`'s program is now `lowered` (one program a step: the one
    it called last)."""
    _STEP_PROGRAMS[id(step)] = lowered
    _STEP_PROGRAMS.move_to_end(id(step))
    while len(_STEP_PROGRAMS) > _KEEP_PROGRAMS:
        _STEP_PROGRAMS.popitem(last=False)


def step_programs() -> List[Tuple[str, str]]:
    """(module name, optimised HLO text) of the process's most recent
    compiled training steps, newest last, each as last called: what
    `scope_map` wants for a trace of this process. Compiled here, on
    demand (jax's in-memory or persistent cache has the executable):
    nothing is compiled until this is called."""
    out = []
    for lowered in list(_STEP_PROGRAMS.values()):
        text = lowered.compile().as_text()
        m = _MODULE_RE.match(text.lstrip())
        out.append((m.group(1) if m else "", text))
    return out
