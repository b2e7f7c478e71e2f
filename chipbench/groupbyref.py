"""The paper's groupby, kept with the benchmark as the yardstick.

For the operator ``groupby_local``:

- ``generate``: the key and value columns of a configuration, made on the
  device from the seed in one jitted call, as ``[workers, capacity]`` blocks;
- ``reference``: the plain NumPy groupby, one row per distinct key, sorted by key;
- ``wrong_rows``: how far an output is from the reference, in rows;
- ``control``: the reference computed with every column narrowed to int32;
- ``essential_bytes``: the bytes any groupby of these columns has to move.

Nothing here imports the program under test.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.joinref import capacity, seed_key  # noqa: F401  (re-exported)

COLUMN_BYTES = {"int64": 8}
UFUNCS = {"sum": np.add, "max": np.maximum}
MASK63 = (1 << 63) - 1
MIXERS = (0xBF58476D1CE4E5B9, 0x94D049BB133111EB)  # splitmix64's odd multipliers


def check(config: dict) -> None:
    """Refuse a configuration this module cannot make or check exactly."""
    cols, key, aggs = config["columns"], config["key"], config["aggs"]
    if set(cols.values()) != {"int64"} or set(cols) != {key, *aggs}:
        raise ValueError(f"{config['name']}: want int64 columns, the key and one per "
                         f"aggregate; got {cols}, key {key!r}, aggs {aggs}")
    if set(aggs.values()) - set(UFUNCS) or set(config["value_bits"]) != set(aggs):
        raise ValueError(f"{config['name']}: aggregates in {sorted(UFUNCS)}, each with "
                         f"its value_bits; got {aggs}, {config['value_bits']}")
    if config["row_bytes"] != sum(COLUMN_BYTES[t] for t in cols.values()):
        raise ValueError(f"{config['name']}: row_bytes {config['row_bytes']} is not "
                         f"the columns' {cols}")
    if not all(0 < b < 64 for b in config["value_bits"].values()):
        raise ValueError(f"{config['name']}: value_bits must lie in (0, 64)")
    if not jax.config.jax_enable_x64:
        raise ValueError(f"{config['name']}: int64 columns need jax_enable_x64, without "
                         "which JAX narrows them to 32 bits")


def distinct_keys(key: jax.Array, groups: int) -> jax.Array:
    """`groups` distinct int64 keys over [0, 2**63): 0, 1, ... shifted by a
    seeded offset and put through a bijection of [0, 2**63) (xor-shifts and
    multiplications by odd numbers, modulo 2**63), so they are distinct by
    construction and look uniform."""
    u64 = jnp.uint64
    x = (jnp.arange(groups, dtype=u64) + jax.random.bits(key, (), u64)) & u64(MASK63)
    for mult, shift in zip(MIXERS, (30, 27)):
        x = ((x ^ (x >> shift)) * u64(mult)) & u64(MASK63)
    return (x ^ (x >> 31)).astype(jnp.int64)


def generate(config: dict, key: jax.Array) -> tuple[jax.Array, ...]:
    """The columns of ``config["columns"]``, in its order, each ``[workers, capacity]``.

    The ``groups`` distinct keys are drawn over [0, 2**63) and each row's key
    is uniform over them; each aggregated column ``c`` is uniform over
    ``[0, 2**value_bits[c])``.  Row r of worker s holds global row
    ``s * rows + r``; rows past ``rows_per_worker`` are zero padding.  Trace
    under ``jax.jit``.
    """
    check(config)
    workers, rows = config["workers"], config["rows_per_worker"]
    n = rows * workers
    kk, kg, *kv = jax.random.split(key, 2 + len(config["aggs"]))
    keys = distinct_keys(kk, config["groups"])
    made = {config["key"]: keys[jax.random.randint(kg, (n,), 0, config["groups"])]}
    for k, (col, bits) in zip(kv, config["value_bits"].items()):
        made[col] = (jax.random.bits(k, (n,), jnp.uint64) >> (64 - bits)).astype(jnp.int64)
    pad = capacity(config) - rows
    return tuple(jnp.pad(made[c].reshape(workers, rows), ((0, 0), (0, pad)))
                 for c in config["columns"])


def reference(columns: dict, key: str, aggs: dict[str, str]) -> tuple[np.ndarray, ...]:
    """(key, then each aggregate in `aggs`' order): one row per distinct key of
    `columns` (host arrays of the valid rows), sorted by key, each aggregate
    accumulated in its column's type."""
    keys, group = np.unique(columns[key], return_inverse=True)
    out = [keys]
    for col, op in aggs.items():
        vals = columns[col]
        init = 0 if op == "sum" else np.iinfo(vals.dtype).min
        acc = np.full(len(keys), init, vals.dtype)
        UFUNCS[op].at(acc, group, vals)
        out.append(acc)
    return tuple(out)


def control(columns: dict, key: str, aggs: dict[str, str]) -> tuple[np.ndarray, ...]:
    """The reference computed with every column narrowed to int32, the next
    integer width below the configured int64, and widened back: what a
    program that halved the bytes would return.  The keys and values use the
    whole width, so it breaks the configuration's exact-result guarantee, and
    the check must fail it."""
    narrow = {c: v.astype(np.int32) for c, v in columns.items()}
    return tuple(x.astype(np.int64) for x in reference(narrow, key, aggs))


def wrong_rows(got: tuple[np.ndarray, ...], want: tuple[np.ndarray, ...]) -> int:
    """Rows in the symmetric difference of two groupby results, in any row order.

    `want` is the reference, sorted by its distinct keys.  A got row counts as
    right when its key is in `want` with every aggregate equal, once per key;
    every other got row, and every reference row not matched so, counts as
    wrong.
    """
    wk, gk = np.asarray(want[0]), np.asarray(got[0])
    if not len(wk):
        return len(gk)
    j = np.minimum(np.searchsorted(wk, gk), len(wk) - 1)
    ok = wk[j] == gk
    for g, w in zip(got[1:], want[1:]):
        ok &= np.asarray(w)[j] == np.asarray(g)
    right = int(np.count_nonzero(np.bincount(j[ok], minlength=len(wk))))
    return (len(gk) - right) + (len(wk) - right)


def rows_per_call(config: dict) -> int:
    """Input rows of one call, over all workers."""
    return config["rows_per_worker"] * config["workers"]


def expected_rows(want: tuple[np.ndarray, ...]) -> int:
    return len(want[0])


def essential_bytes(config: dict, want: tuple[np.ndarray, ...]) -> dict[str, float]:
    """Bytes per chip per call that any groupby of these columns must move.

    ``hbm``: every input column read once and every output row (the key and
    one column per aggregate, of the configured types) written once,
    averaged over the chips.  ``ici``: on P workers, each group's partial
    aggregates from the P-1 workers that do not hold it (0 on one worker).
    Counted from the shapes and the reference's row count (`want`), never
    from the program.
    """
    workers, cols = config["workers"], config["columns"]
    out_row = COLUMN_BYTES[cols[config["key"]]] + sum(COLUMN_BYTES[cols[c]]
                                                       for c in config["aggs"])
    groups = expected_rows(want)
    return {"hbm": config["rows_per_worker"] * config["row_bytes"] + groups * out_row / workers,
            "ici": groups * out_row * (workers - 1) / workers}
