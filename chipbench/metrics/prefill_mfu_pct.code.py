"""Model FLOPs of the prefills in the traced stretch (2 N per real prompt
token, causal attention over the prompt, one row of logits) over the prefill
program's device time and the peak of every chip the cell holds (moves the
gap between tokens: a tick that prefills holds every slot's next token)."""
from chipbench.derive import PREFILL, mfu_pct, prefill_flops


def read(run):
    return mfu_pct(run, PREFILL, prefill_flops(run))
