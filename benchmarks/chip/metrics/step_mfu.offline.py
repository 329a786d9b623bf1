"""FLOPs the window's scored work needs over step wall time x peak bf16, %."""
from layer_metrics import step_mfu


def read(ctx):
    return step_mfu(ctx)
