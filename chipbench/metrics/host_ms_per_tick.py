"""Engine host tick: mean over the traced ticks of the harness span
around ``engine.step()`` less the device time of the step's program
executions, in ms."""


def read(rec):
    ticks = {t.index: t for t in rec.ticks}
    host = [ticks[i].t1 - ticks[i].t0 - dev
            for i, dev in rec.traced_ticks.items() if dev > 0]
    return 1e3 * sum(host) / len(host) if host else None
