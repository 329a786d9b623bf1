"""Mean host time of the state insert (a hybrid's Mamba-state snapshots cut
into cache payloads and inserted with their blocks) in window steps on the
fresh (cache-miss) path, ms, from the engine's ``BatchRecord.phases``."""


def read(ctx):
    ms = [1000.0 * b.phases["state_insert"] for b in ctx.batches
          if b.jit_path == "fresh"
          and "state_insert" in getattr(b, "phases", {})]
    return sum(ms) / len(ms) if ms else None
