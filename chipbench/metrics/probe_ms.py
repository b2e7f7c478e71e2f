"""``probe_ms``: device time under the ``probe`` stage per call, in ms, on the
chip that spends the most: ``join_unique``'s binary search of every left key
in the sorted right keys, and its hit test (``chipbench/stages.py``)."""

from chipbench import devtrace, stages


def read(ctx: devtrace.Context) -> float | None:
    return stages.per_call_ms(ctx, ("probe",))
