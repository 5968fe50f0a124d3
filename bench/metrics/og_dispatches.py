"""Device dispatches per grouping plan over the window
(``PlannerStats.og_dispatches / og_plans``)."""


def read(run):
    return run.og_dispatches / run.og_plans if run.og_plans else None
