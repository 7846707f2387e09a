"""Sum of the roofline-least times over the sum of device times of the
kernels of kind ``gemm`` (the W8A8 GEMMs, bench/kernels/) in the traced
stretch, in percent.  Each call's least time is max(ops / int8 peak,
bytes / HBM bandwidth), counted from the call's shapes in its HLO line by
the kernel's cost file: every operand and the result, wherever XLA placed
them."""


def read(run):
    k = (run.trace or {}).get("kernels", {}).get("gemm")
    if not k or k["s"] <= 0 or k["min_s"] <= 0:
        return None
    return 100.0 * k["min_s"] / k["s"]
