"""flash_roofline: least time of the attention work the traced steps need
(self- and cross-attention forward and backward at the unpadded lengths,
no recomputation) over the summed trace time of the flash kernels
(forward, dq, dkv)."""


def read(run: dict) -> float | None:
    trace = run["trace"]
    if trace is None:
        return None
    spent = trace.kernel_s("flash_")
    if spent <= 0:
        return None
    peaks = run["peaks"]
    least = run["family"].step_kernel_seconds(
        run["dims"], run["traced_microbatches"],
        peaks["bf16_flops_per_s"], peaks["hbm_bytes_per_s"],
    )["flash"]
    return 100.0 * least / spent
