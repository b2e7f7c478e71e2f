"""DDMF layer: oracles + hypothesis property tests (deliverable (c))."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # bare env: deterministic shim (see requirements-dev.txt)
    from _hypothesis_fallback import given, settings, st

from repro.core import make_communicator
from repro.dataframe import Table, ops_dist, ops_local
from repro.dataframe.partition import (
    build_partition_payload,
    bucket_ids,
    hash32,
    hash_columns,
    partition_counts,
)
from repro.dataframe.table import concat, from_stacked


def make_table(keys, vals, cap=None, names=("k", "v")):
    return Table.from_dict(
        {names[0]: np.asarray(keys, np.int32), names[1]: np.asarray(vals, np.int32)},
        capacity=cap,
    )


class TestTable:
    def test_from_dict_and_padding(self):
        t = make_table([1, 2, 3], [4, 5, 6], cap=8)
        assert t.capacity == 8 and int(t.count) == 3
        out = t.to_numpy()
        np.testing.assert_array_equal(out["k"], [1, 2, 3])

    def test_filter_packs(self):
        t = make_table(range(10), range(10), cap=16)
        f = t.filter(t.columns["v"] % 2 == 0)
        np.testing.assert_array_equal(f.to_numpy()["v"], [0, 2, 4, 6, 8])

    def test_concat(self):
        a = make_table([1, 2], [1, 2], cap=4)
        b = make_table([3], [3], cap=4)
        c = concat([a, b])
        assert int(c.count) == 3
        np.testing.assert_array_equal(np.sort(c.to_numpy()["k"]), [1, 2, 3])

    def test_ragged_rejected(self):
        with pytest.raises(ValueError):
            Table.from_dict({"a": np.zeros(3), "b": np.zeros(4)})

    def test_from_stacked_packs_buckets(self):
        cols = {"k": jnp.arange(12).reshape(3, 4)}
        counts = jnp.asarray([2, 0, 3], jnp.int32)
        t = from_stacked(cols, counts)
        assert int(t.count) == 5
        np.testing.assert_array_equal(np.sort(t.to_numpy()["k"]), [0, 1, 8, 9, 10])


class TestPartition:
    def test_hash_deterministic_and_seeded(self):
        keys = jnp.arange(100, dtype=jnp.int32)
        h1, h2 = hash32(keys), hash32(keys)
        np.testing.assert_array_equal(np.asarray(h1), np.asarray(h2))
        assert not np.array_equal(np.asarray(hash32(keys, seed=1)), np.asarray(h1))

    @given(
        st.lists(st.integers(-(2**31), 2**31 - 1), min_size=1, max_size=200),
        st.integers(2, 16),
    )
    @settings(max_examples=30, deadline=None)
    def test_partition_totality(self, keys, p):
        """Every valid row lands in exactly one partition; none invented."""
        t = make_table(keys, [0] * len(keys), cap=max(len(keys), 1) + 7)
        payload, counts = build_partition_payload(t, p, ["k"])
        assert int(counts.sum()) == len(keys)
        got = np.concatenate(
            [np.asarray(payload["k"][d][: int(counts[d])]) for d in range(p)]
        )
        assert sorted(got.tolist()) == sorted(np.asarray(keys, np.int32).tolist())

    def test_partition_respects_bucket_ids(self):
        keys = np.arange(64)
        t = make_table(keys, keys, cap=80)
        b = np.asarray(bucket_ids(t, ["k"], 4))[:64]
        payload, counts = build_partition_payload(t, 4, ["k"])
        for d in range(4):
            rows = np.asarray(payload["k"][d][: int(counts[d])])
            assert set(rows.tolist()) == set(keys[b == d].tolist())

    def test_counts_match(self):
        keys = np.arange(1000)
        t = make_table(keys, keys, cap=1024)
        counts = np.asarray(partition_counts(t, ["k"], 8))
        _, counts2 = build_partition_payload(t, 8, ["k"])
        np.testing.assert_array_equal(counts, np.asarray(counts2))

    def test_capacity_clamp(self):
        keys = np.zeros(32, np.int64)  # all same key -> one bucket
        t = make_table(keys, keys, cap=32)
        payload, counts = build_partition_payload(t, 4, ["k"], cap_per_dest=8)
        assert int(counts.max()) == 8  # clamped, reflected in counts

    def test_multi_column_hash(self):
        t = Table.from_dict(
            {"a": np.arange(50, dtype=np.int32), "b": (np.arange(50) % 3).astype(np.int32)}
        )
        h = hash_columns(t, ["a", "b"])
        h2 = hash_columns(t, ["b", "a"])
        assert h.shape == (50,)
        assert not np.array_equal(np.asarray(h), np.asarray(h2))  # order-sensitive


class TestLocalOps:
    @given(
        st.lists(st.integers(0, 30), min_size=1, max_size=120),
        st.integers(0, 3),
    )
    @settings(max_examples=30, deadline=None)
    def test_groupby_matches_dict_oracle(self, keys, seed):
        rng = np.random.default_rng(seed)
        vals = rng.integers(-50, 50, len(keys))
        t = make_table(keys, vals, cap=len(keys) + 5)
        g = ops_local.groupby_agg(t, "k", {"v": "sum"})
        got = {int(a): int(b) for a, b in zip(*[g.to_numpy()[c] for c in ("k", "v_sum")])}
        oracle = {}
        for k, v in zip(keys, vals):
            oracle[k] = oracle.get(k, 0) + int(v)
        assert got == oracle

    I32, I64 = np.iinfo(np.int32), np.iinfo(np.int64)
    GROUPBY_AGGS = {"a": "sum", "b": "max", "c": "min", "d": "count"}
    groupby = staticmethod(jax.jit(
        lambda t: ops_local.groupby_agg(t, "k", TestLocalOps.GROUPBY_AGGS)))

    def check_groupby(self, keys, vals, count):
        """groupby_agg over int64 columns k, a = vals >> 24 (its sums fit), b = c =
        vals, against a dict oracle; the rows past `count` are padding and hold
        keys and values too."""
        keys, vals = np.asarray(keys, np.int64), np.asarray(vals, np.int64)
        cols = {"k": keys, "a": vals >> 24, "b": vals, "c": vals, "d": vals}
        oracle: dict[int, tuple[int, int, int, int]] = {}
        for k, a, v in zip(keys[:count].tolist(), cols["a"][:count].tolist(), vals.tolist()):
            s, hi, lo, n = oracle.get(k, (0, v, v, 0))
            oracle[k] = (s + a, max(hi, v), min(lo, v), n + 1)
        with jax.enable_x64(True):
            t = Table({c: jnp.asarray(v) for c, v in cols.items()}, jnp.asarray(count, jnp.int32))
            g = self.groupby(t)
            out = {c: np.asarray(v) for c, v in g.columns.items()}
        n = int(g.count)
        assert n == len(oracle) and g.capacity == len(keys)
        names = ["a_sum", "b_max", "c_min", "d_count"]
        assert [out[c].dtype for c in ["k", *names]] == [np.int64] * 4 + [np.int32]
        assert out["k"][:n].tolist() == sorted(oracle)
        got = {k: tuple(out[c][i].item() for c in names)
               for i, k in enumerate(out["k"][:n].tolist())}
        assert got == oracle
        assert all(not out[c][n:].any() for c in out)  # the output's padding is zero

    @given(st.lists(st.integers(-20, 20), min_size=0, max_size=90), st.integers(0, 2**32))
    @settings(max_examples=30, deadline=None)
    def test_groupby_int64_aggs_match_dict_oracle(self, keys, seed):
        """sum, max, min and count together, values over the whole int64 range,
        extremes included, and keys and values in the padding drawn the same way.
        The drawn keys repeat up to 40 times, so segments span the scans' rows
        of 128."""
        rng = np.random.default_rng(seed)
        keys = np.repeat(keys, rng.integers(1, 41)).tolist()
        pad = -(-(len(keys) + 1) // 1024) * 1024 - len(keys)  # few capacities, few compiles
        keys = keys + rng.integers(-20, 21, pad).tolist()
        vals = rng.integers(self.I64.min, self.I64.max, len(keys), endpoint=True)
        extreme = rng.random(len(keys)) < 0.2
        vals[extreme] = rng.choice([self.I64.min, self.I64.max], int(extreme.sum()))
        self.check_groupby(keys, vals, len(keys) - pad)

    # (keys, values, count): the rows past `count` are padding
    GROUPBY_CASES = {
        "max_min_count": ([1, 1, 2, 2, 2, 0, 0, 0], [5, -3, 7, 7, 1, 0, 0, 0], 5),
        "empty": ([3, 1, 3, 2], [I64.max, 5, I64.min, -1], 0),
        "one_group": ([7] * 9 + [8], [I64.max, 1, I64.min, -5, 0, I64.max, 2, 3, -2**40, 9], 9),
        "all_distinct": ([9, -4, I64.max, 0, I64.min, 5, 2, 6], [3, 1, 4, 1, 5, 9, 2, 6], 8),
        "count_equals_capacity": ([2, 1, 2, 1, 1, 3], [I64.min, I64.max, 0, -7, 7, 1], 6),
        "garbage_in_padding": ([5, 1, 5, 1, 5, 1, I64.max, 1],
                               [1, 2, 3, 4, I64.max, I64.min, I64.max, I64.min], 4),
        # more than 128 x 128 rows: the scans recurse twice, and segments span both
        "long_segments": ((np.arange(40_000) % 3).tolist(),
                          np.random.default_rng(5).integers(-2**62, 2**62, 40_000), 39_990),
    }

    @pytest.mark.parametrize("case", sorted(GROUPBY_CASES))
    def test_groupby_max_min_count(self, case):
        self.check_groupby(*self.GROUPBY_CASES[case])

    @given(st.data())
    @settings(max_examples=30, deadline=None)
    def test_join_unique_matches_nested_loop(self, data):
        lk = data.draw(st.lists(st.integers(0, 60), min_size=1, max_size=80))
        rk = data.draw(
            st.lists(st.integers(0, 60), min_size=1, max_size=60, unique=True)
        )
        lv = list(range(len(lk)))
        rv = [k * 10 for k in rk]
        l = make_table(lk, lv, cap=len(lk) + 3)
        r = make_table(rk, rv, cap=len(rk) + 3, names=("k", "w"))
        j = ops_local.join_unique(l, r, "k")
        got = sorted(zip(*[j.to_numpy()[c].tolist() for c in ("k", "v", "w")]))
        rmap = dict(zip(rk, rv))
        exp = sorted((k, v, rmap[k]) for k, v in zip(lk, lv) if k in rmap)
        assert got == exp

    # (left keys, right keys (unique among the valid), left count, right count, dtype);
    # the rows past each count are padding and hold keys too
    JOIN_EDGE_CASES = {
        "left_duplicates": ([5, 3, 5, 5, 9, 3], [3, 5, 7], 6, 3, np.int32),
        "dtype_max": ([I32.max, 1, I32.max, 2], [2, I32.max, 0], 4, 3, np.int32),
        "dtype_min": ([I32.min, 1, I32.min], [I32.min, 1], 3, 2, np.int32),
        "negative": ([-7, -1, 0, -7, 4, -3], [-3, -7, 4, -100], 6, 4, np.int32),
        "int64_full_range": ([I64.max, I64.min, 0, -(2**62), 2**62 + 1, I64.max, 3],
                             [2**62 + 1, I64.min, I64.max, -(2**62), 5], 7, 5, np.int64),
        "left_count_0": ([1, 2, 3], [1, 2, 3], 0, 3, np.int32),
        "right_count_0": ([1, 2, 3], [1, 2, 3], 3, 0, np.int32),
        "padding_equals_valid": ([4, 8, 2, 4, 8, 2], [8, 4, 2, 8, 4, 2], 3, 2, np.int32),
        "padding_at_dtype_max": ([I32.max, 6, I32.max], [6, I32.max, I32.max], 2, 1, np.int32),
    }

    @pytest.mark.parametrize("case", sorted(JOIN_EDGE_CASES))
    def test_join_unique_edge_case_matches_nested_loop(self, case):
        lk, rk, nl, nr, dtype = self.JOIN_EDGE_CASES[case]
        lv, rv = list(range(100, 100 + len(lk))), list(range(200, 200 + len(rk)))
        exp = sorted((a, v, w) for a, v in zip(lk[:nl], lv) for b, w in zip(rk[:nr], rv)
                     if a == b)
        with jax.enable_x64(dtype == np.int64):
            def table(keys, vals, count, val_name):
                return Table({"k": jnp.asarray(np.asarray(keys, dtype)),
                              val_name: jnp.asarray(np.asarray(vals, dtype))},
                             jnp.asarray(count, jnp.int32))

            j = ops_local.join_unique(table(lk, lv, nl, "v"), table(rk, rv, nr, "w"), "k")
            cols = j.to_numpy()
        assert cols["k"].dtype == dtype
        assert sorted(zip(*[cols[c].tolist() for c in ("k", "v", "w")])) == exp

    @given(st.data())
    @settings(max_examples=25, deadline=None)
    def test_join_expand_matches_nested_loop(self, data):
        lk = data.draw(st.lists(st.integers(0, 12), min_size=1, max_size=30))
        rk = data.draw(st.lists(st.integers(0, 12), min_size=1, max_size=30))
        lv = list(range(len(lk)))
        rv = [100 + i for i in range(len(rk))]
        exp = sorted(
            (k, v, w) for k, v in zip(lk, lv) for k2, w in zip(rk, rv) if k == k2
        )
        l = make_table(lk, lv, cap=len(lk) + 2)
        r = make_table(rk, rv, cap=len(rk) + 2, names=("k", "w"))
        j = ops_local.join_sorted_expand(l, r, "k", out_capacity=len(exp) + 8)
        got = sorted(zip(*[j.to_numpy()[c].tolist() for c in ("k", "v", "w")]))
        assert got == exp

    def test_sort_by_key(self):
        t = make_table([3, 1, 2], [30, 10, 20], cap=6)
        s = ops_local.sort_by_key(t, "k")
        np.testing.assert_array_equal(s.to_numpy()["v"], [10, 20, 30])


class TestDistributedSim:
    """Distributed ops through the communicator == local oracle (C2)."""

    def _split(self, keys, vals, p, cap, names=("k", "v")):
        per = len(keys) // p
        return [
            make_table(keys[i * per : (i + 1) * per], vals[i * per : (i + 1) * per],
                       cap=cap, names=names)
            for i in range(p)
        ]

    @pytest.mark.parametrize("env", ["direct", "redis", "s3"])
    def test_join_same_result_any_substrate(self, env):
        """Paper C4: substrates differ in cost, never in semantics."""
        rng = np.random.default_rng(0)
        keys = rng.permutation(128).astype(np.int64)
        vals = rng.integers(0, 99, 128)
        rk = rng.permutation(128)[:64]
        rv = rk * 7
        comm = make_communicator(4, env)
        res = ops_dist.sim_join(
            self._split(keys, vals, 4, 64),
            self._split(rk, rv, 4, 64, names=("k", "w")),
            "k", comm,
        )
        got = sorted(
            r for t in res
            for r in zip(*[t.to_numpy()[c].tolist() for c in ("k", "v", "w")])
        )
        rmap = dict(zip(rk.tolist(), rv.tolist()))
        exp = sorted(
            (int(k), int(v), rmap[int(k)])
            for k, v in zip(keys, vals) if int(k) in rmap
        )
        assert got == exp
        assert comm.comm_time_s > 0

    def test_substrate_latency_ordering(self):
        """direct < redis < s3 for identical exchanges (Fig 10 order)."""
        times = {}
        for env in ("direct", "redis", "s3"):
            rng = np.random.default_rng(1)
            keys = rng.permutation(256).astype(np.int64)
            comm = make_communicator(4, env)
            ops_dist.sim_groupby(
                self._split(keys, keys, 4, 128), "k", {"v": "sum"}, comm
            )
            times[env] = comm.comm_time_s
        assert times["direct"] < times["redis"] < times["s3"]

    def test_compressed_shuffle_keys_bit_exact(self):
        """Codec path lands every row at the same rank with identical keys."""
        rng = np.random.default_rng(7)
        keys = rng.integers(-(2**31), 2**31 - 1, 512).astype(np.int64)
        tables = self._split(keys, keys, 4, 256)
        raw = ops_dist._shuffle_sim(tables, "k", make_communicator(4, "direct"))
        comp = ops_dist._shuffle_sim(
            tables, "k", make_communicator(4, "direct"), compress=True
        )
        for t_raw, t_comp in zip(raw, comp):
            a, b = t_raw.to_numpy(), t_comp.to_numpy()
            assert b["k"].dtype == np.asarray(tables[0].columns["k"]).dtype
            np.testing.assert_array_equal(a["k"], b["k"])  # same rows, same order

    @pytest.mark.parametrize("env", ["direct", "redis", "s3"])
    def test_compressed_join_matches_uncompressed(self, env):
        """Same row multiset as the raw path, >= 1.5x fewer wire bytes."""
        rng = np.random.default_rng(3)
        keys = rng.permutation(512).astype(np.int64)
        vals = rng.integers(0, 999, 512)
        rk = rng.permutation(512)[:256]
        rv = rk * 3
        rows, wire = {}, {}
        for compress in (False, True):
            comm = make_communicator(4, env)
            res = ops_dist.sim_join(
                self._split(keys, vals, 4, 256),
                self._split(rk, rv, 4, 256, names=("k", "w")),
                "k", comm, compress=compress,
            )
            rows[compress] = sorted(
                r for t in res
                for r in zip(*[t.to_numpy()[c].tolist() for c in ("k", "v", "w")])
            )
            wire[compress] = comm.bytes_on_wire
        assert rows[True] == rows[False]  # all-int tables: bit-exact join
        assert wire[True] * 1.5 <= wire[False]

    def test_compressed_float_values_error_bounded(self):
        """Block-int8 value error stays inside one quantization step."""
        rng = np.random.default_rng(11)
        keys = rng.permutation(256).astype(np.int32)
        vals = (rng.normal(size=256) * 50).astype(np.float32)
        tables = [
            Table.from_dict(
                {"k": keys[i * 64:(i + 1) * 64], "v": vals[i * 64:(i + 1) * 64]},
                capacity=128,
            )
            for i in range(4)
        ]
        raw = ops_dist._shuffle_sim(tables, "k", make_communicator(4, "direct"))
        comp = ops_dist._shuffle_sim(
            tables, "k", make_communicator(4, "direct"), compress=True
        )
        bound = np.abs(vals).max() / 254 * 1.01 + 1e-9
        for t_raw, t_comp in zip(raw, comp):
            a, b = t_raw.to_numpy(), t_comp.to_numpy()
            np.testing.assert_array_equal(a["k"], b["k"])
            assert b["v"].dtype == np.float32
            if a["v"].size:
                assert np.abs(a["v"] - b["v"]).max() <= bound

    def test_compressed_groupby_matches(self):
        rng = np.random.default_rng(5)
        keys = rng.integers(0, 16, 1024).astype(np.int64)
        vals = rng.integers(-99, 99, 1024)
        for combine in (False, True):
            merged = {}
            for compress in (False, True):
                comm = make_communicator(4, "direct")
                res = ops_dist.sim_groupby(
                    self._split(keys, vals, 4, 512), "k", {"v": "sum"}, comm,
                    combine=combine, compress=compress,
                )
                merged[compress] = {
                    int(k): int(s)
                    for t in res
                    for k, s in zip(t.to_numpy()["k"], t.to_numpy()["v_sum"])
                }
            assert merged[True] == merged[False]  # int aggregates stay exact

    def test_groupby_combiner_reduces_wire_bytes(self):
        """Paper §IV-C: local pre-aggregation shrinks the shuffle."""
        rng = np.random.default_rng(2)
        keys = rng.integers(0, 10, 4096).astype(np.int64)  # few groups
        vals = rng.integers(0, 9, 4096)
        merged = {}
        byte_counts = {}
        for combine in (False, True):
            comm = make_communicator(4, "direct")
            res = ops_dist.sim_groupby(
                self._split(keys, vals, 4, 2048), "k", {"v": "sum"}, comm, combine=combine
            )
            byte_counts[combine] = comm.bytes_on_wire
            merged[combine] = {}
            for t in res:
                d = t.to_numpy()
                for k, s in zip(d["k"].tolist(), d["v_sum"].tolist()):
                    assert k not in merged[combine]
                    merged[combine][k] = s
        assert merged[True] == merged[False]
        assert byte_counts[True] < 0.1 * byte_counts[False]
