"""step_mfu: model FLOPs of the traced steps (forward and backward, no
recomputation) over the traced window's length times the chips' bf16
peak: the whole step's share of the peak, in the traced run beside the
kernels' rooflines.  A kernel taken off the path leaves its roofline
silent; this share still bounds the gain claimed for it."""


def read(run: dict) -> float | None:
    trace = run["trace"]
    if trace is None or not run["traced_microbatches"]:
        return None
    model_flops = run["family"].model_flops
    flops = sum(model_flops(run["dims"], b, s) for b, s in run["traced_microbatches"])
    return 100.0 * flops / (trace.window_s * run["chips"] * run["peaks"]["bf16_flops_per_s"])
