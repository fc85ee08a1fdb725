"""Seconds the backend spent compiling during set-up, from
``jax.monitoring``'s compile durations; with a warm persistent cache only
programs that miss it compile."""


def read(rec):
    return sum(s for t, s in rec.watch.compiles if t <= rec.t_open_abs)
