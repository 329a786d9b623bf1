"""Share of time inside engine steps with no device operation running, %."""
from layer_metrics import idle_in_steps


def read(ctx):
    return idle_in_steps(ctx)
