"""Share of time inside engine steps with the device idle while the host
dispatches the step's program, compiles included (span
``engine.dispatch``), %."""
from layer_metrics import phase_share


def read(ctx):
    return phase_share(ctx, "idle_in_dispatch")
