"""Exchange layer: device milliseconds per step of the collective
operations (the gossip ppermute, the batch ring shuffle, metric
all-reduces; their start and done ops) in the trace, averaged over chips."""
from bench import tracing


def read(rec):
    tr = rec["trace"]
    if tr is None or len(tr.ops) < 2:
        return None
    wait = tracing.mean_op_time_s(tr, tracing.is_collective)
    return 1e3 * wait / rec["steps"]
