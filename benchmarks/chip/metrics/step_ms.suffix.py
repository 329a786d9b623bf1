"""Mean wall time of window steps on the solo suffix (cache-hit) path, ms."""
from layer_metrics import step_ms


def read(ctx):
    return step_ms(ctx, "suffix")
