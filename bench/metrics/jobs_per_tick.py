"""Jobs the planner made and the executor ran, per tick of the window
(``Report.n_jobs``, mean over the window's ticks)."""


def read(ctx):
    if not ctx.ticks:
        return None
    return sum(t.n_jobs for t in ctx.ticks) / len(ctx.ticks)
