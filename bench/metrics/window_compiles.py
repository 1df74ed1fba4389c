"""XLA backend compiles during the window's steps: the sum of
`StepReport.compiles`. A compile there is time the steady state would
not spend; 0 is the steady state."""
from bench import runstate


def read(run):
    vals = runstate.field_of(run, "compiles")
    return None if vals is None else int(sum(vals))
