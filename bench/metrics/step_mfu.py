"""Step program layer: model FLOPs per token (bench/flops.py, recompute not
counted) times the traced window's tokens per second per chip, over the
chip's bf16 peak, in percent."""


def read(rec):
    peaks = rec["peaks"]
    if not peaks or rec["tokens_per_s_per_chip"] <= 0:
        return None
    achieved = rec["flops_per_token"] * rec["tokens_per_s_per_chip"]
    return 100.0 * achieved / peaks["bf16_flops"]
