"""Share of time inside engine steps with the device idle while the host
cuts state snapshots and inserts them into the prefix cache (span
``engine.state_insert``), %."""
from layer_metrics import phase_share


def read(ctx):
    return phase_share(ctx, "idle_in_state_insert")
