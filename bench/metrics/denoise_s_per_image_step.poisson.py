"""Device seconds of the programs that ``process_group`` ran (text
encoder, noise draw and the denoise loop), over the guided DDIM steps
they served: sum over groups of n_cloud x batch (device trace)."""


def read(run):
    if run.trace is None:
        return None
    spans = run.trace.spans_named("bench.process_group")
    dev = sum(run.trace.module_time_in(s, e) for s, e, _ in spans)
    steps = sum(g.n_cloud * len(g.members) for g in run.groups if g.ok)
    if not spans or not steps or dev <= 0:
        return None
    return dev / steps
