"""Share of its roofline that the flash-attention kernel reaches in the
admit programs of the traced window: the least time the chip needs for
the attention of the prompts' real tokens (causal, from the
configuration's work counts) over the kernel's device time."""
from harness import serve


def read(rec):
    if rec.trace is None or getattr(rec, "serve", None) is None:
        return None
    t = serve.kernel_in_runs(rec, "flash_attention", "admit")
    if t <= 0:
        return None
    pk = rec.peaks
    need = 0.0
    for k, n, _, _ in serve.traced_runs(rec):
        if k == "admit":
            w = rec.work.prefill(rec.conf, n)["flash_attention"]
            need += max(w["flops"] / pk["bf16_flops_per_s"],
                        w["bytes"] / pk["hbm_bytes_per_s"])
    return 100.0 * need / t
