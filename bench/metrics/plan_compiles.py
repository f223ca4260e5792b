"""plan_compiles: programs the server's plan cache traced during the
window (``PlanCache.stats()['traces']`` at its end less at its start).
Every shape is warmed in set-up, so this should read 0."""


def read(run):
    return run.delta["plan_traces"]
