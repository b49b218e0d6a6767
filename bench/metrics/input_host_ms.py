"""Input layer: mean host milliseconds of one ``Trainer.batch`` call (pool
draw, ring rotation, ``device_put``) inside the traced window, timed around
the call on the host clock."""


def read(rec):
    spans = rec["input_spans_s"]
    if not spans:
        return None
    return 1e3 * sum(spans) / len(spans)
