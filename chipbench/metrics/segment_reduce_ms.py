"""``segment_reduce_ms``: device time under the ``segment_reduce`` stage per
call, in ms, on the chip that spends the most: ``groupby_agg``'s segment ids
(a blocked scan of the key changes), its segment reductions, one per output
column, and the zeroing of the output's padding (``chipbench/stages.py``)."""

from chipbench import devtrace, stages


def read(ctx: devtrace.Context) -> float | None:
    return stages.per_call_ms(ctx, ("segment_reduce",))
