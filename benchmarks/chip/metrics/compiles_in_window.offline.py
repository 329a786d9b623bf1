"""Backend compiles that ended inside the measured window."""
from layer_metrics import compiles


def read(ctx):
    return compiles(ctx)
