"""Launcher layer: seconds from the first step to the end of the schedule's
first pass: every phase's first call, which compiles or loads its program
(host clock)."""


def read(rec):
    return rec["setup_warm_s"]
