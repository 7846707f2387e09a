"""The W8A8 GEMM (``kernels/int8_gemm.py``): int8 activation rows against
int8 weights, costed from the HLO line the trace names each call by."""
import devtrace

KIND = "gemm"
PEAK = "int8_ops"


def cost_from_hlo(line: str) -> tuple[float, float, tuple[str, ...]]:
    """Operations, bytes and VMEM-fed weights of one call: 2 m k n per
    int8 weight (k, n) against the int8 activation (m, k); the bytes of
    the result and of every operand, wherever XLA placed them; and the
    names of the weights XLA had already placed in VMEM (``S(1)``), whose
    HBM bytes moved in the ops that produced them, not in the kernel."""
    head, rest = line.split(" custom-call(", 1)
    args = rest.split("), custom_call_target", 1)[0]
    res = devtrace.operand_shapes(head)[0]
    operands = devtrace.operand_shapes(args)
    mats = [o for o in operands if o[0] == "s8" and len(o[1]) == 2]
    (m, k), weights = mats[0][1], mats[1:]
    ops = 2.0 * m * k * sum(w[1][1] for w in weights)
    nbytes = sum(devtrace.array_bytes(t, d) for t, d, _, _ in
                 [res, *operands])
    feeds = tuple(name for _, _, vmem, name in weights if vmem and name)
    return ops, float(nbytes), feeds
