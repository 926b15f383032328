"""The whole step's share of the chips' peak: the forward and backward
FLOPs the configuration needs at the batch's real per-layer node and edge
counts (layer-wise trimming included, nothing recomputed; counted by
``bench/models/<model>.py``), times the steps of the traced window, over
its seconds, over chips x the bf16 matrix peak. The step's float32
matmuls run at JAX's default TPU precision, one bfloat16 pass, so the
bf16 peak is the one they can reach."""


def read(rec):
    if not rec.get("steps") or not rec.get("window_s"):
        return None
    rate = rec["step_flops"] * rec["steps"] / rec["window_s"]
    return 100.0 * rate / (rec["chips"] * rec["peaks"]["flops_per_s"])
