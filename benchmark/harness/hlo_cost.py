"""Operations and bytes of device operations, from their HLO text.

The benchmark's own arithmetic for a kernel's roofline bound: the profiler
names every device operation by its HLO instruction (operand and result
types inline), which gives the bytes; the operations of a fusion come from
the convolutions and dots inside the computation it calls, read from the
optimized module's text.  Copied in substance from ``tools/profile_step.py``
(the byte rule) and extended by the operation count, which that tool took
from the profiler's cost model.
"""

import re

DTYPE_BYTES = {
    "f32": 4, "f16": 2, "bf16": 2, "f64": 8, "s32": 4, "u32": 4, "s64": 8,
    "u64": 8, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "pred": 1, "s4": 1,
    "u4": 1, "f8e4m3fn": 1, "f8e5m2": 1,
}
# a typed shape with its optional layout, e.g.
#   bf16[128,56,56,256]{3,0,2,1:T(8,128)(2,1)S(1)}
# S(1) in the layout is memory space 1 (VMEM): the compiler staged that
# buffer with an overlapped copy, so reading it does not cross HBM.
_SHAPE = re.compile(r"\b(%s)\[([0-9,]*)\](\{[^}]*\})?"
                    % "|".join(sorted(DTYPE_BYTES, key=len, reverse=True)))
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->.*\{\s*$")
_CALLED = re.compile(r"(?:calls|body|condition|to_apply|branch_computations)"
                     r"=\{?%?([\w.\-]+(?:,\s*%?[\w.\-]+)*)\}?")
_OP_NAME = re.compile(r'op_name="([^"]*)"')

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute", "collective-broadcast")
# instructions that only contain or wait for others: no time of their own
CONTAINERS = ("while", "call", "conditional", "async-start", "async-done",
              "async-update")


def shape_dims(shape_text):
    """[(dtype, [dims], layout text)] of every typed shape in the text."""
    return [(dt, [int(d) for d in dims.split(",") if d], layout or "")
            for dt, dims, layout in _SHAPE.findall(shape_text)]


def split_bytes(text):
    """(bytes in HBM, bytes in VMEM) over the typed shapes in ``text``."""
    hbm = vmem = 0
    for dt, dims, layout in shape_dims(text):
        n = DTYPE_BYTES[dt]
        for d in dims:
            n *= d
        if "S(" in layout:
            vmem += n
        else:
            hbm += n
    return hbm, vmem


def min_hbm_bytes(instruction_text):
    """Least HBM traffic of one instruction: every HBM-resident operand
    read once and every HBM-resident result written once.  What sits in
    VMEM was paid for by the prefetch copy that put it there.  An async
    copy or slice moves the smaller of its two sides."""
    _, opcode, _ = split_instruction(instruction_text)
    hbm, vmem = split_bytes(instruction_text.split(", calls=")[0]
                            .split(", metadata=")[0])
    if opcode.endswith("-start") and vmem:
        return min(hbm, vmem)
    return hbm


def split_instruction(text):
    """``%name = <result type> opcode(operands), attrs`` ->
    (name, opcode, (result type text, operand text, attribute text))."""
    m = _INSTR.match(text)
    if not m:
        return "", "", ("", "", "")
    name, rest = m.group(1), m.group(2)
    # the result type: a tuple in parentheses, or one typed shape
    if rest.startswith("("):
        depth = 0
        for i, ch in enumerate(rest):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                break
        result, rest = rest[:i + 1], rest[i + 1:].lstrip()
    else:
        result, _, rest = rest.partition(" ")
    opcode, _, rest = rest.partition("(")
    depth, i = 1, 0
    for i, ch in enumerate(rest):
        depth += ch == "("
        depth -= ch == ")"
        if depth == 0:
            break
    return name, opcode.strip(), (result, rest[:i], rest[i + 1:])


def is_collective(opcode):
    return opcode.startswith(COLLECTIVES)


def _product(values):
    n = 1
    for v in values:
        n *= v
    return n


def _window(attrs, key, n, default):
    m = re.search(r"\b%s=([0-9x_\-]+)" % key, attrs)
    return m.group(1).split("x") if m else [default] * n


def _valid_pairs(size_in, size_out, window, stride, pad_lo, lhs_dilate,
                 rhs_dilate):
    """(output position, window tap) pairs of one spatial dimension that
    read a real input element: not padding, not a hole of a dilated input."""
    dilated = (size_in - 1) * lhs_dilate + 1
    pairs = 0
    for o in range(size_out):
        for k in range(window):
            p = o * stride + k * rhs_dilate - pad_lo
            pairs += 0 <= p < dilated and p % lhs_dilate == 0
    return pairs


def convolution_flops(result_dims, lhs_dims, rhs_dims, attrs):
    """2 x the multiply-adds the mathematics requires: per batch element,
    output feature and input feature (of the group), the window taps that
    land on a real input element.  Padding and the zeros a dilated input
    inserts are not counted, so a convolution costs the same however the
    compiler phrases it (a 1x1 convolution as a 56x56 window over padding,
    a strided convolution's input gradient as a dilated one)."""
    labels = re.search(r"dim_labels=(\w+)_(\w+)->(\w+)", attrs)
    if not labels or not lhs_dims or not rhs_dims or not result_dims:
        return 0
    lhs_l, rhs_l, out_l = labels.groups()
    n = sum(c.isdigit() for c in out_l)
    size = [int(v) for v in _window(attrs, "size", n, "1")]
    stride = [int(v) for v in _window(attrs, "stride", n, "1")]
    pad_lo = [int(v.split("_")[0]) for v in _window(attrs, "pad", n, "0_0")]
    lhs_dilate = [int(v) for v in _window(attrs, "lhs_dilate", n, "1")]
    rhs_dilate = [int(v) for v in _window(attrs, "rhs_dilate", n, "1")]
    macs = result_dims[out_l.index("b")] * result_dims[out_l.index("f")] \
        * rhs_dims[rhs_l.index("i")]
    for d in range(n):
        macs *= _valid_pairs(
            lhs_dims[lhs_l.index(str(d))], result_dims[out_l.index(str(d))],
            size[d], stride[d], pad_lo[d], lhs_dilate[d], rhs_dilate[d])
    return 2 * macs


def dot_flops(result_dims, lhs_dims, attrs):
    m = re.search(r"lhs_contracting_dims=\{([0-9,]*)\}", attrs)
    contracting = [lhs_dims[int(i)] for i in m.group(1).split(",") if i] \
        if m and lhs_dims else []
    return 2 * _product(result_dims) * _product(contracting)


def _total_and_heaviest(pairs, default_name):
    """(sum of flops, op_name of the heaviest named one) over (flops, name)
    pairs."""
    total, heaviest, heaviest_name = 0, -1, default_name
    for flops, name in pairs:
        total += flops
        if flops > heaviest and name:
            heaviest, heaviest_name = flops, name
    return total, heaviest_name


class Module:
    """Operation counts and source names of one optimized HLO module."""

    def __init__(self, text):
        self._comps = {}        # computation -> {instr: (opcode, parts)}
        current = None
        for line in text.splitlines():
            head = _COMPUTATION.match(line)
            if head:
                current = self._comps.setdefault(head.group(1), {})
                continue
            if current is None or " = " not in line:
                continue
            name, opcode, parts = split_instruction(line)
            if name:
                current[name] = (opcode, parts)
        self._flops = {}
        self.instructions = {}  # instr -> (flops, op_name of its heaviest)
        for comp in self._comps.values():
            for name, (opcode, parts) in comp.items():
                self.instructions[name] = self._instruction(comp, opcode,
                                                            parts)

    def _dims(self, comp, operand):
        """Dims of one operand: typed inline, or named and looked up."""
        inline = shape_dims(operand)
        if inline:
            return inline[0][1]
        ref = comp.get(operand.strip().lstrip("%"))
        if ref:
            shapes = shape_dims(ref[1][0])
            return shapes[0][1] if shapes else []
        return []

    def _instruction(self, comp, opcode, parts):
        result, operands, attrs = parts
        op_name = _OP_NAME.search(attrs)
        op_name = op_name.group(1) if op_name else ""
        if opcode in ("convolution", "dot"):
            ops = re.split(r",\s*(?![^\[\{]*[\]\}])", operands)
            shapes = shape_dims(result)
            dims = shapes[0][1] if shapes else []
            if opcode == "convolution" and len(ops) >= 2:
                return convolution_flops(dims, self._dims(comp, ops[0]),
                                         self._dims(comp, ops[1]),
                                         attrs), op_name
            if opcode == "dot" and ops:
                return dot_flops(dims, self._dims(comp, ops[0]),
                                 attrs), op_name
            return 0, op_name
        return _total_and_heaviest(
            (self._computation(called.lstrip("%"))
             for group in _CALLED.findall(attrs)
             for called in re.split(r",\s*", group)), op_name)

    def _computation(self, comp_name):
        """(flops, op_name of the heaviest instruction) of a computation."""
        if comp_name in self._flops:
            return self._flops[comp_name]
        self._flops[comp_name] = (0, "")    # guards against a cycle
        comp = self._comps.get(comp_name, {})
        self._flops[comp_name] = _total_and_heaviest(
            (self._instruction(comp, opcode, parts)
             for opcode, parts in comp.values()), "")
        return self._flops[comp_name]
