"""Whole serving step: model operations of the traced ticks (family cost
module, at each token's context length) over the ticks' whole time, the
harness span around ``engine.step()`` (host and device), times the
chip's bf16 peak, in %. It bounds every kernel's roofline share of the
same ticks from above, so a kernel taken off the path cannot hide a
slower step."""


def read(rec):
    ticks = {t.index: t for t in rec.ticks}
    flops = spent = 0.0
    for i, dev in rec.traced_ticks.items():
        if dev <= 0:
            continue
        t = ticks[i]
        flops += rec.costs.tick(rec.config, t.pos, t.adv)[0]
        spent += t.t1 - t.t0
    if spent <= 0:
        return None
    return 100.0 * flops / (spent * rec.peaks["bf16_flops_per_s"])
