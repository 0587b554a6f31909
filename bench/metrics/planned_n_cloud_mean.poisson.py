"""Mean ``n_final`` the planner gave the requests due in the window (an
exact count of cloud DDIM steps per request)."""


def read(run):
    n = list(run.planned.values())
    return sum(n) / len(n) if n else None
