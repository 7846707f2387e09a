"""The dual-gated W8A8 GEMM (``kernels/int8_gemm.py``): one int8
activation against the gate and up weights in one call, costed as
``int8_gemm`` is, every weight operand counted."""
import byname

KIND = "gemm"
PEAK = "int8_ops"

cost_from_hlo = byname.sibling(__file__, "int8_gemm").cost_from_hlo
