"""Wall seconds of the engine's ``process_group`` calls, summed, over the
requests they served.  Groups run one at a time, so the chip is held for
all of that time (host clock)."""


def read(run):
    groups = [g for g in run.groups if g.ok]
    n = sum(len(g.members) for g in groups)
    if not n:
        return None
    return sum(g.end - g.start for g in groups) / n
