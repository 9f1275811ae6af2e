"""Model step: mean device time of the step's program (``decode_chunk``)
over the traced ticks, prompt chunks and decode alike, in ms."""


def read(rec):
    dev = [d for d in rec.traced_ticks.values() if d > 0]
    return 1e3 * sum(dev) / len(dev) if dev else None
