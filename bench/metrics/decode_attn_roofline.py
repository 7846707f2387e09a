"""The paged int8 decode-attention kernel's roofline-least time over its
device time in the traced stretch, in percent (kind ``decode_attn``).
Its work is that of every query it answered there: the program calls it
in the bucket-1 steps, for every planned lane, over the int8 K/V, scales
and positions of the lane's own context (bench/devtrace.decode_contexts),
every layer over its own reach, as the configuration's family counts it
(its cost file in bench/kernels/)."""


def read(run):
    k = (run.trace or {}).get("kernels", {}).get("decode_attn")
    if not k or k["s"] <= 0 or k["min_s"] <= 0:
        return None
    return 100.0 * k["min_s"] / k["s"]
