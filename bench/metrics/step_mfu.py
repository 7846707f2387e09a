"""Operations the model requires for the tokens the window processed,
over the window's seconds times the chip's int8 peak, in percent.

Counted per step from the engine's plan, by the configuration's family
(``run.family.reference``): every prompt token computed (prefix hits and
pads excluded) and every decode token pays each layer's projections and
attention over its own reach; every token emitted in the window pays the
head."""


def read(run):
    if not run.peaks or not run.steps:
        return None
    ref, a = run.family.reference, run.arch
    per_tok = ref.ops_per_token(a)
    ops = 0.0
    for s in run.steps:
        for p0, c, _ in s.spans:
            ops += per_tok * c
            ops += ref.span_attention_ops(a, p0, c)
    emitted = sum(run.in_window(t) for r in run.requests for t in r.token_t)
    ops += ref.head_ops(a) * emitted
    return 100.0 * ops / (run.window_s * run.peaks["int8_ops"])
