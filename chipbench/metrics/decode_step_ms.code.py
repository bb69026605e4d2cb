"""Device milliseconds per run of the engine's decode program, in the
traced stretch, averaged over the chips (moves the gap between tokens)."""
from chipbench.derive import DECODE, step_ms


def read(run):
    return step_ms(run, DECODE)
