"""Share of the window's prompt tokens served from the prefix cache, %."""
from layer_metrics import hit_share


def read(ctx):
    return hit_share(ctx)
