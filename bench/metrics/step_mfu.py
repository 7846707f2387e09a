"""Operations the model requires for the tokens the window processed,
over the window's seconds times the chip's int8 peak, in percent.

Counted per step from the engine's plan: every prompt token computed
(prefix hits and pads excluded) and every decode token pays each layer's
projections and attention over its own context; every token emitted in
the window pays the head."""
import roofline


def read(run):
    if not run.peaks or not run.steps:
        return None
    m = run.arch
    per_tok = roofline.layer_ops_per_token(m)
    ops = 0.0
    for s in run.steps:
        for p0, c, _ in s.spans:
            ops += per_tok * c
            # contexts p0 + 1 .. p0 + c
            ops += roofline.attention_ops(1, m) * (c * p0 + c * (c + 1) / 2)
    emitted = sum(run.in_window(t) for r in run.requests for t in r.token_t)
    ops += roofline.head_ops(m) * emitted
    return 100.0 * ops / (run.window_s * run.peaks["int8_ops"])
