"""The device's idle time in the traced stretch that a program span other
than ``engine.wait`` covers (each gap split over the spans covering it, by
time), as a percentage of the stretch: the part of ``idle_share`` the
engine's host work causes.  Logs the idle seconds per span name and the
ten longest gaps' split."""
import progtrace


def read(run):
    rec = progtrace.of_run(run, __file__)
    return progtrace.host_idle_share(rec) if rec else None
