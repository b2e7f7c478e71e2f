"""``groupby_roofline``: the whole groupby's share of its roofline, in %.

The same computation as ``join_roofline``: the least time, from the
operator's essential bytes over the peak bandwidths, over the device busy
time per call of the busiest chip.  Here the bytes are
``groupbyref.essential_bytes``: every input column read once and every
output row written once, counted from the shapes and the reference's row
count, so any groupby algorithm is held to the same bytes."""

from chipbench.metrics.join_roofline import read  # noqa: F401
