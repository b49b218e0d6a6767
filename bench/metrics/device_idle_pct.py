"""Device layer: the share of the traced window in which no operation ran,
in percent, averaged over chips."""
from bench import tracing


def read(rec):
    tr = rec["trace"]
    if tr is None or not tr.ops:
        return None
    w0, w1 = tr.window()
    return 100.0 * (1.0 - tracing.mean_busy_s(tr) / (w1 - w0))
