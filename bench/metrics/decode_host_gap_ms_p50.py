"""Median, over the decode steps of the traced window, of the host time
from the end of the program's ``serve.decode.sync`` span (the previous
step's tokens are on the host) to the end of its
``serve.decode.dispatch`` span (the next step is queued): the stretch
in which the chip has nothing queued."""
from harness import common, spans


def read(rec):
    steps = spans.within(rec, "serve.decode_step")
    gaps = [spans.end(d) - spans.end(s)
            for s, d in zip(spans.children(rec, steps, "serve.decode.sync"),
                            spans.children(rec, steps,
                                           "serve.decode.dispatch"))
            if s is not None and d is not None]
    return None if not gaps else common.percentile(gaps, 50) / 1e6
