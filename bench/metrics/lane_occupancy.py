"""Share of the scheduler's lanes that held a trial, averaged over the
window's scheduler steps (the scheduler's own ``ServeStats``)."""


def read(ctx):
    return ctx["occupancy"] if ctx["steps"] else None
