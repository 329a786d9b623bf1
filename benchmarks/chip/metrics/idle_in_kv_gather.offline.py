"""Share of time inside engine steps with the device idle while the host
gathers a cache hit's prefix KV (span ``engine.kv_gather``), %."""
from layer_metrics import phase_share


def read(ctx):
    return phase_share(ctx, "idle_in_kv_gather")
