"""The control of the groupby cells' check: it has to come out as not correct.

    python3 chipbench/groupby_control.py --workload groupby-50m-1chip --seeds 11 12 13

For each seed it makes the cell's columns on one device exactly as a run does
(the same generator and key), then compares with the plain reference both the
reference itself and the control, ``groupbyref.control``: the reference
computed with every column narrowed to int32, which breaks the
configuration's exact-result guarantee.  Prints one JSON line per seed with
both ``wrong_rows`` readings and the cell's limit.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402
import numpy as np  # noqa: E402

from chipbench import groupbyref, run  # noqa: E402


def host_columns(config: dict, seed: int) -> dict[str, np.ndarray]:
    """The cell's columns on the host, valid rows of every worker in order."""
    blocks = jax.jit(lambda key: groupbyref.generate(config, key))(groupbyref.seed_key(seed))
    rows = config["rows_per_worker"]
    return {c: np.asarray(b)[:, :rows].reshape(-1) for c, b in zip(config["columns"], blocks)}


def readings(config: dict, seed: int) -> dict:
    with jax.enable_x64(bool(config.get("x64", False))):
        columns = host_columns(config, seed)
    key, aggs = config["key"], config["aggs"]
    want = groupbyref.reference(columns, key, aggs)
    return {"seed": seed, "rows_out": len(want[0]),
            "reference_wrong_rows": groupbyref.wrong_rows(want, want),
            "control_wrong_rows": groupbyref.wrong_rows(
                groupbyref.control(columns, key, aggs), want),
            "limit": 0}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    _, _, config, _ = run.load_cell(args.workload)
    run.require_devices(1)
    for seed in args.seeds:
        print(json.dumps(dict(readings(config, seed), workload=args.workload)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
