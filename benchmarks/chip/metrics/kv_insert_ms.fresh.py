"""Mean host time of the KV insert (the fresh KV sliced into block payloads
and ``cache.insert``) in window steps on the fresh (cache-miss) path, ms,
from the engine's ``BatchRecord.phases``."""


def read(ctx):
    ms = [1000.0 * b.phases["kv_insert"] for b in ctx.batches
          if b.jit_path == "fresh" and "kv_insert" in getattr(b, "phases", {})]
    return sum(ms) / len(ms) if ms else None
