"""Device time of the ops under the packed step's ``attn.gather`` scope
(the XLA gather-then-attend over the paged view) as a percentage of the
traced stretch.  Logs the device seconds per scope."""
import progtrace


def read(run):
    rec = progtrace.of_run(run, __file__)
    return progtrace.scope_share(rec, "attn.gather") if rec else None
