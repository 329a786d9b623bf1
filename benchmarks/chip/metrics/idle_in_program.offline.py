"""Share of time inside engine steps in which a program runs on the device
but no operation does, %."""
from layer_metrics import phase_share


def read(ctx):
    return phase_share(ctx, "idle_in_program")
