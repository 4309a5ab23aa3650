"""HBM bytes a kernel's own operands move, from the shapes in its trace
event's name (the HLO instruction, as the profiler names it)."""
import re

# the V-wide stencil kernel's instruction: "%stencil_update_v.3 = f32[R,V]..."
STENCIL_UPDATE_V = r"^%stencil_update_v(\.[\w.]+)? = "

_SHAPE = re.compile(r"(f32|s32)\[([\d,]*)\]")


def _bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        n *= int(d)
    return 4 * n if dtype in ("f32", "s32") else 0


def stencil_update_v(name: str) -> int | None:
    """One call of the kernel: it reads one array of gathered neighbour
    rows (R, 128) per slot, or per face in its face pass, then the
    centres (R, V), the masks (R, K) int32 and the coefficients (R, K),
    and writes (R, V). The shapes are the output's and the operands' in
    ``operand_layout_constraints`` (None where the name does not hold
    at least one gathered array and the last three)."""
    if " = " not in name or "operand_layout_constraints={" not in name:
        return None
    out = _SHAPE.search(name.split(" = ", 1)[1])
    ops = _SHAPE.findall(name.split("operand_layout_constraints={", 1)[1].split("}}", 1)[0])
    if out is None or len(ops) < 4:
        return None
    *vals, centres, masks, coeffs = ops
    if centres[0] != "f32" or masks[0] != "s32" or coeffs[0] != "f32" or centres[1] != out.group(2):
        return None
    return sum(_bytes(t, d) for t, d in ops) + _bytes(*out.groups())
