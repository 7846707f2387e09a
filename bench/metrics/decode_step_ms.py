"""Host clock around ``engine.step()`` (which ends on the sampled tokens'
host copy) for the window's steps that computed no prompt token: total
time over count."""


def read(run):
    ts = [s.t1 - s.t0 for s in run.steps
          if s.prompt_tokens == 0 and s.decode_tokens > 0]
    return 1e3 * sum(ts) / len(ts) if ts else None
