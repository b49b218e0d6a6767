"""Launcher layer: seconds of ``build_trainer`` plus the seeded state and
the token pool (host clock)."""


def read(rec):
    return rec["setup_build_s"]
