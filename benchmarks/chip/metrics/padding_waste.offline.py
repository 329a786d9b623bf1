"""Share of paid forward slots that were padding, over window steps, %."""
from layer_metrics import padding_waste


def read(ctx):
    return padding_waste(ctx)
