"""``exchange_wait_ms``: the part of the exchange's ``all-to-all`` operations
in which a chip waits for its last peer to enter them, per call, in ms, on the
chip that waits most (``stages.collective_wait_ns``).  What of
``all_to_all_ms`` is not wait is the transfer itself."""

from chipbench import devtrace, stages


def read(ctx: devtrace.Context) -> float | None:
    exchange = {n for n, st in stages.instruction_stages(ctx.hlo).items() if st == "exchange"}
    wait = stages.collective_wait_ns(
        ctx.summary, lambda name, opcode: name in exchange and devtrace.is_all_to_all(name, opcode))
    if not wait:
        return None
    return max(wait.values()) / ctx.summary.calls / 1e6
