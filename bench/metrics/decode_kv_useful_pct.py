"""Share of the KV positions the decode steps' dense attention reads
that hold the lanes' context, over the decode steps of the traced
window: the ``ctx_tokens`` over the ``kv_positions`` attributes of the
program's ``serve.decode_step`` spans."""
from harness import spans


def read(rec):
    steps = [a for _, _, _, a in spans.within(rec, "serve.decode_step")]
    kv = sum(a["kv_positions"] for a in steps)
    return None if not kv else 100.0 * sum(a["ctx_tokens"]
                                           for a in steps) / kv
