"""The paged int8 decode-attention kernel (``kernels/paged_attention.py``):
its work is that of every query it answered in the traced stretch, over
the int8 K/V, scales and positions of the lane's own context, as the
configuration's family counts it (``decode_attn_work``)."""
import devtrace

KIND = "decode_attn"


def cost_from_run(run) -> tuple[float, float, str]:
    ops, nbytes = run.family.reference.decode_attn_work(
        run.arch, devtrace.decode_contexts(run.traced_steps()))
    return ops, nbytes, "bf16_flops"
