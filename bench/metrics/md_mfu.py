"""Share of the chip's bf16 peak that the window's MD force evaluations
required: forward GEMM FLOPs over real rows (plus the position backward
under the autodiff readout), over window time, chips and peak, in
percent."""


def read(r):
    if "flops.md" not in r or not r.get("window_s"):
        return None
    return 100.0 * r["flops.md"] / (
        r["window_s"] * r["chips"] * r["peaks"]["bf16_flops"])
