"""``partition_ms``: device time under the ``partition_hash`` and
``partition_pack`` stages per call, in ms, on the chip that spends the most:
the shuffle's row hash and its pack of the rows into the ``[P, cap]`` send
buffers, for both tables (``chipbench/stages.py``)."""

from chipbench import devtrace, stages


def read(ctx: devtrace.Context) -> float | None:
    return stages.per_call_ms(ctx, ("partition_hash", "partition_pack"))
