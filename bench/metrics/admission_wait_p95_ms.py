"""95th percentile, over the admits in the traced window, of the host
time a request waited in the program's admission queue: the
``queued_us`` attribute of the program's ``serve.admit`` span, from the
queue's push to the start of the admit."""
from harness import common, spans


def read(rec):
    waits = [a["queued_us"] for _, _, _, a in spans.within(rec, "serve.admit")
             if "queued_us" in a]
    return None if not waits else common.percentile(waits, 95) / 1e3
