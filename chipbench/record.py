"""Record a small profiler trace of one configuration's operator on the chip,
with its compiled program's text, for the CPU tests of the trace readers.

    python3 chipbench/record.py --config cylon-join-9m-w4 --rows 20000 --calls 10 \\
        --out chipbench/testdata/join_spmd_stages

makes the configuration's tables at `--rows` rows per worker from `--seed`,
compiles its operator, warms it up with one call, and traces `--calls` calls,
each in a ``call`` span as ``run.py`` times them.  It writes
``<out>.xplane.pb`` and ``<out>.hlo.txt``: ``compiled.as_text()`` without its
source-location tables, which name the recording checkout's files (the
instructions' ``op_name`` metadata stays).  It prints the row counts and the
reference's.  It needs as many TPU chips as the configuration has workers.
"""

from __future__ import annotations

import argparse
import importlib
import json
import re
import shutil
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402
import numpy as np  # noqa: E402

from chipbench import devtrace, run  # noqa: E402

SOURCE_TABLES = re.compile(r"^(FileNames|FunctionNames|FileLocations|StackFrames)\n.*?\n\n",
                           re.M | re.S)


def record(config: dict, devices: list, seed: int, calls: int, out: Path) -> dict:
    op = importlib.import_module(f"chipbench.ops.{config['operator']}")
    args = jax.block_until_ready(op.inputs(config, devices, seed))
    compiled = jax.jit(op.program(config, devices)).lower(*args).compile()
    jax.block_until_ready(compiled(*args))
    counts = []
    with tempfile.TemporaryDirectory() as tmp:
        jax.profiler.start_trace(tmp)
        try:
            for _ in range(calls):
                with jax.profiler.TraceAnnotation("call"):
                    res = jax.block_until_ready(compiled(*args))
                    counts.append(int(np.asarray(op.row_count(res)).sum()))
        finally:
            jax.profiler.stop_trace()
        shutil.copyfile(devtrace.find_xspace(Path(tmp)), out.with_suffix(".xplane.pb"))
    out.with_suffix(".hlo.txt").write_text(SOURCE_TABLES.sub("", compiled.as_text()))
    want = op.expected_rows(op.reference(config, args))
    return {"calls": calls, "row_counts": sorted(set(counts)), "expected_rows": want}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--rows", type=int, required=True, help="rows per worker per table")
    ap.add_argument("--calls", type=int, default=10)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--out", type=Path, required=True, help="path without suffix")
    args = ap.parse_args(argv)

    config = json.loads((ROOT / "chipbench" / "configs" / f"{args.config}.json").read_text())
    config = dict(config, rows_per_worker=args.rows)
    devices = run.require_devices(config["workers"])
    with jax.enable_x64(bool(config.get("x64", False))):
        print(json.dumps(record(config, devices, args.seed, args.calls, args.out)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
