"""Share of its roofline that the expert-FFN kernel reaches in the
decode programs of the traced window: the least time the chip needs for
the routed rows (tokens x top-k) and the experts they reach, over the
kernel's device time."""
from harness import serve


def read(rec):
    if rec.trace is None or getattr(rec, "serve", None) is None:
        return None
    t = serve.kernel_in_runs(rec, "moe_expert_ffn", "decode")
    if t <= 0:
        return None
    pk = rec.peaks
    need = 0.0
    for k, ctx, _, _ in serve.traced_runs(rec):
        if k == "decode":
            w = rec.work.decode(rec.conf, ctx)["moe_expert_ffn"]
            need += max(w["flops"] / pk["bf16_flops_per_s"],
                        w["bytes"] / pk["hbm_bytes_per_s"])
    return 100.0 * need / t
