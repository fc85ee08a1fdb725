"""Backend compiles that ended inside the serve window (should be 0:
every program the traffic uses is warmed in set-up)."""


def read(rec):
    if getattr(rec, "serve", None) is None:
        return None
    return rec.watch.between(rec.t_open_abs, rec.t_open_abs + rec.serve.close)
