"""adaln_roofline: least time of the LayerNorm-Modulate work the traced
steps need (forward and backward, bound by HBM bytes) over the summed
trace time of the AdaLN kernels (forward, dx, dmod)."""


def read(run: dict) -> float | None:
    trace = run["trace"]
    if trace is None:
        return None
    spent = trace.kernel_s("adaln_")
    if spent <= 0:
        return None
    peaks = run["peaks"]
    least = run["family"].step_kernel_seconds(
        run["dims"], run["traced_microbatches"],
        peaks["bf16_flops_per_s"], peaks["hbm_bytes_per_s"],
    )["adaln"]
    return 100.0 * least / spent
