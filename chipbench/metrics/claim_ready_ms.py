"""Control plane: claim submit -> Ready of the replica's workload, as the
workload's status records it (``phase_latency_s.total``), in ms."""


def read(rec):
    return rec.claim_ready_s * 1e3
