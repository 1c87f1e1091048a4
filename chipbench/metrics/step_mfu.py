"""Model FLOPs of the traced window's steps (``flops.train_step_flops``:
3 x the forward's matmuls and unpadded attention, adaLN once per sample,
no recompute) over window seconds x chips x the chip's bf16 peak."""
from chipbench import flops


def read(m):
    t = m.traffic
    work = flops.train_step_flops(m.model, t["batch"], t["temporal"],
                                  t["spatial"]) * m.steps
    return 100.0 * work / (m.trace.window_s * m.chips
                           * m.peaks["bf16_flops"])
