"""Whole step: model FLOPs of the edge-served requests' prompts prefilled
and tokens decoded inside the window (no padding, pow2 overshoot or masked
slots), over window x chips x the chip's peak bf16 FLOP/s, in %."""
from bench import work


def read(run):
    return work.mfu(run)
