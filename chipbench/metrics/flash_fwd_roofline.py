"""Share of the flash forward's roofline: for each kernel op in the trace,
the least time its call could take (the larger of its FLOPs over the bf16
peak and its bytes over HBM bandwidth, for the unpadded attention the call
computes on activations in the configuration's dtype:
``flops.flash_forward_cost``), summed, over the ops' summed device time.

A call's sequences and heads come from its output, (B', H, L, D); its
length is the traffic's S or T that the kernel padded to L (the query is
padded to a multiple of 8, or of the 128-row block past it)."""
from chipbench import flops, hlo

ITEMSIZE = {"bfloat16": 2, "float16": 2, "float32": 4}


def _length(padded, candidates):
    for n in sorted(candidates, reverse=True):
        if n == padded or (n < padded and padded - n < 128
                           and padded % 8 == 0):
            return n
    return None


def read(m):
    calls = m.trace.of_kind("pallas")
    if not calls:
        return None
    t = m.traffic
    itemsize = ITEMSIZE[m.config["dtype"]]
    spent = least = 0.0
    for seconds, ins in calls:
        _, (seqs, heads, padded, dh) = hlo.shape_of(ins.result)
        n = _length(padded, (t["temporal"], t["spatial"]))
        if n is None:
            raise ValueError(f"{ins.name}: length {padded} is neither T "
                             f"nor S")
        f, b = flops.flash_forward_cost(seqs, n, heads, dh, itemsize)
        least += max(f / m.peaks["bf16_flops"],
                     b / m.peaks["hbm_bytes_per_s"])
        spent += seconds
    return 100.0 * least / spent
