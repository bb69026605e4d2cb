"""Share of its roofline that the Pallas ``flash_attention`` kernel reaches
in the traced stretch, averaged over the chips.  Under a tensor-parallel
plan each chip runs the kernel on its own heads (``shard_map``), so the
work comes from the per-shard shapes of each call
(``kernels/flash_attention.py``)."""
from chipbench.derive import roofline_pct


def read(run):
    return roofline_pct(run, "flash_attention")
