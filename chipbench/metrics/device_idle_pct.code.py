"""Share of the traced stretch in which no op ran on the device, averaged
over the chips (moves the gap between tokens)."""
from chipbench.derive import idle_pct


def read(run):
    return idle_pct(run)
