"""Kernels of the serving step (``decode_chunk``): over the traced ticks,
the least time the chip could take (the larger of operations over peak
FLOP/s and bytes over peak bandwidth, both counted from the shapes and
each slot's live length by the family's cost module) over the device
time the step's program took, in %."""


def read(rec):
    ticks = {t.index: t for t in rec.ticks}
    least = spent = 0.0
    for i, dev in rec.traced_ticks.items():
        if dev <= 0:
            continue
        t = ticks[i]
        flops, nbytes = rec.costs.tick(rec.config, t.pos, t.adv)
        least += max(flops / rec.peaks["bf16_flops_per_s"],
                     nbytes / rec.peaks["hbm_bytes_per_s"])
        spent += dev
    return 100.0 * least / spent if spent > 0 else None
