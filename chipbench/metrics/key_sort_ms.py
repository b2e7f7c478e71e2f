"""``key_sort_ms``: device time under the ``key_sort`` stage per call, in ms,
on the chip that spends the most: ``sort_by_key``'s argsort of the keys and
its gather of every column into key order (``chipbench/stages.py``)."""

from chipbench import devtrace, stages


def read(ctx: devtrace.Context) -> float | None:
    return stages.per_call_ms(ctx, ("key_sort",))
