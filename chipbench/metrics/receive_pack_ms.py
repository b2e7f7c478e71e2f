"""``receive_pack_ms``: device time under the ``receive_pack`` stage per call,
in ms, on the chip that spends the most: ``from_stacked``'s pack of the
``P x cap`` received slots into one table, for both tables
(``chipbench/stages.py``)."""

from chipbench import devtrace, stages


def read(ctx: devtrace.Context) -> float | None:
    return stages.per_call_ms(ctx, ("receive_pack",))
