"""Share of the chips' bf16 peak that the window's training steps
required: forward and backward GEMM FLOPs over real rows (padding and
recompute not counted), over window time, chips and peak, in percent."""


def read(r):
    if "flops.train" not in r or not r.get("window_s"):
        return None
    return 100.0 * r["flops.train"] / (
        r["window_s"] * r["chips"] * r["peaks"]["bf16_flops"])
