"""``output_pack_ms``: device time under the ``output_pack`` stage per call, in
ms, on the chip that spends the most: ``join_unique``'s stable pack of the
matched rows to the front of the joined table (``chipbench/stages.py``)."""

from chipbench import devtrace, stages


def read(ctx: devtrace.Context) -> float | None:
    return stages.per_call_ms(ctx, ("output_pack",))
