"""round_mfu: FLOPs the window's rounds require (chipbench/flops.py) over
the window's seconds, the chips and the chip's bf16 peak, in percent."""


def read(r):
    if not r.rounds:
        return None
    return 100.0 * r.win["flops"] / (r.window_s * r.chips
                                     * r.peaks["bf16_flops_per_s"])
