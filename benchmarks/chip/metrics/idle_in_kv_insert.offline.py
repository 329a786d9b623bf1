"""Share of time inside engine steps with the device idle while the host
inserts fresh KV into the prefix cache (span ``engine.kv_insert``), %."""
from layer_metrics import phase_share


def read(ctx):
    return phase_share(ctx, "idle_in_kv_insert")
