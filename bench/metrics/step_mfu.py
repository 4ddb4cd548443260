"""The whole train step's share of the chip's bf16 peak: model FLOPs per
token (`bench/flops.py`, no recomputation) times the traced window's tokens
per second, over the peak of the device kind (`bench/peaks.json`)."""


def read(r):
    if "tokens_per_s" not in r.facts:
        return None
    return 100.0 * r.facts["flops_per_token"] * r.facts["tokens_per_s"] / r.peak["bf16_flops_per_s"]
