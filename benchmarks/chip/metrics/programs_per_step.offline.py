"""Programs the device starts in the window per engine step (``engine.step``
spans), a count."""
from layer_metrics import phase_share


def read(ctx):
    return phase_share(ctx, "programs_per_step")
