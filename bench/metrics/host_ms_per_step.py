"""Mean over the traced stretch's ``engine.step`` spans of the span's
duration minus its ``engine.wait`` child: the host's own time per engine
step, outside waiting on the chip (the program's spans, from the trace)."""
import progtrace


def read(run):
    rec = progtrace.of_run(run, __file__)
    return progtrace.host_ms_per_step(rec) if rec else None
