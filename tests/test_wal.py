"""WAL codec + replay invariants: encode/decode identity, LSN
monotonicity, torn-tail semantics, and the core durability property —
replay of ANY prefix of a logged delta stream bit-equals both a scorer
that applied the same prefix directly and the full-recompute oracle.

Property-based via hypothesis where available (seeded example loops
otherwise — see tests/_hypothesis_compat.py)."""
import os

import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:                       # pragma: no cover
    from _hypothesis_compat import given, settings, st

from repro.core import Booster, BoostConfig
from repro.incremental import MaintainedScorer, TableDelta
from repro.incremental.wal import (
    MAGIC, WalCorruptError, WalFollower, WalReader, WalWriter,
    decode_record, encode_record, read_records, scan_wal, wal_path,
)
from repro.relational.generators import (
    chain_schema, delta_stream, snowflake_schema, star_schema,
)
from repro.serving import compile_ensemble


def _fit(sch, n_trees=2, depth=2):
    b = Booster(sch, BoostConfig(n_trees=n_trees, depth=depth,
                                 mode="sketch", ssr_mode="off"))
    return b.fit()[0]


def _small(shape):
    if shape == "star":
        return star_schema(seed=11, n_fact=120, n_dim=12)
    if shape == "chain":
        return chain_schema(seed=12, n_rows=60, n_tables=3, fanout=2)
    return snowflake_schema(seed=13, n_fact=80, n_dim=8, n_sub=4)


def _arrays_equal(a, b) -> bool:
    a, b = np.asarray(a), np.asarray(b)
    return (a.dtype == b.dtype and a.shape == b.shape
            and a.tobytes() == b.tobytes())      # bitwise: NaNs compare too


def _deltas_equal(xs, ys) -> bool:
    if len(xs) != len(ys):
        return False
    for x, y in zip(xs, ys):
        if x.table != y.table:
            return False
        if (x.inserts is None) != (y.inserts is None):
            return False
        if x.inserts is not None:
            if set(x.inserts) != set(y.inserts):
                return False
            if not all(_arrays_equal(v, y.inserts[c])
                       for c, v in x.inserts.items()):
                return False
        if (x.deletes is None) != (y.deletes is None):
            return False
        if x.deletes is not None and not _arrays_equal(x.deletes, y.deletes):
            return False
        if (x.updates is None) != (y.updates is None):
            return False
        if x.updates is not None:
            if not _arrays_equal(x.updates[0], y.updates[0]):
                return False
            if set(x.updates[1]) != set(y.updates[1]):
                return False
            if not all(_arrays_equal(v, y.updates[1][c])
                       for c, v in x.updates[1].items()):
                return False
    return True


def _random_delta(rng) -> TableDelta:
    dtypes = [np.float32, np.float64, np.int64, np.int32]
    ins = dele = upd = None
    if rng.random() < 0.7:
        k = int(rng.integers(1, 5))
        ins = {f"c{i}": rng.standard_normal(k).astype(rng.choice(dtypes))
               for i in range(int(rng.integers(1, 4)))}
    if rng.random() < 0.5:
        dele = rng.integers(0, 1000, int(rng.integers(1, 6))).astype(np.int64)
    if rng.random() < 0.5:
        k = int(rng.integers(1, 4))
        upd = (rng.integers(0, 1000, k).astype(np.int64),
               {f"u{i}": rng.standard_normal(k).astype(rng.choice(dtypes))
                for i in range(int(rng.integers(1, 3)))})
    return TableDelta(table=f"t{int(rng.integers(3))}", inserts=ins,
                      deletes=dele, updates=upd)


# ------------------------------------------------------------------- codec --

def test_record_roundtrip_identity_seeded():
    """Seeded sweep: encode→decode reproduces every array bit-for-bit,
    dtype and shape included."""
    rng = np.random.default_rng(0)
    for lsn in range(1, 60):
        deltas = [_random_delta(rng) for _ in range(int(rng.integers(1, 4)))]
        lsn2, out, tw = decode_record(encode_record(lsn, deltas, t_wall=123.5))
        assert lsn2 == lsn
        assert tw == 123.5
        assert _deltas_equal(deltas, out)


@settings(max_examples=40, deadline=None)
@given(st.integers(min_value=1, max_value=2**31),
       st.lists(st.integers(min_value=0, max_value=255),
                min_size=0, max_size=32),
       st.lists(st.floats(width=32, allow_nan=True), min_size=1, max_size=16))
def test_record_roundtrip_identity_property(lsn, dele, vals):
    """Property: roundtrip identity holds for arbitrary payloads,
    including NaN floats (bitwise compare) and empty delete sets."""
    deltas = [TableDelta(
        table="t",
        inserts={"a": np.asarray(vals, np.float32),
                 "b": np.arange(len(vals), dtype=np.int64)},
        deletes=np.asarray(dele, np.int64) if dele else None,
    )]
    lsn2, out, _ = decode_record(encode_record(lsn, deltas))
    assert lsn2 == lsn
    assert _deltas_equal(deltas, out)


@settings(max_examples=25, deadline=None)
@given(sizes=st.lists(st.integers(min_value=1, max_value=6),
                      min_size=1, max_size=20))
def test_lsn_monotonic_property(sizes, tmp_path_factory):
    """Property: whatever batch sizes arrive, the log carries strictly
    consecutive LSNs and the writer refuses any other sequence."""
    d = str(tmp_path_factory.mktemp("walp"))
    w = WalWriter(d, sync_every=4)
    rng = np.random.default_rng(1)
    for i, k in enumerate(sizes, start=1):
        w.append(i, [_random_delta(rng) for _ in range(k)])
    with pytest.raises(ValueError):
        w.append(len(sizes) + 2, [])      # gap
    with pytest.raises(ValueError):
        w.append(len(sizes), [])          # repeat
    w.close()
    lsns = [l for l, _, _, _ in read_records(wal_path(d))]
    assert lsns == list(range(1, len(sizes) + 1))


# ----------------------------------------------------------------- writer --

def test_writer_refuses_non_monotonic_and_scan_ignores_heartbeats(tmp_path):
    w = WalWriter(str(tmp_path), sync_every=1)
    rng = np.random.default_rng(2)
    w.append(1, [_random_delta(rng)])
    w.heartbeat()
    w.append(2, [_random_delta(rng)])
    w.heartbeat()
    w.close()
    last, end, size = scan_wal(wal_path(str(tmp_path)))
    assert last == 2
    assert end == size                    # heartbeats are valid records
    r = WalReader(str(tmp_path))
    recs = r.poll()
    assert [l for l, _, _ in recs] == [1, 0, 2, 0]
    assert r.poll() == []                 # tail consumed, nothing new


def test_torn_tail_is_clean_stop_and_midlog_damage_raises(tmp_path):
    d = str(tmp_path)
    w = WalWriter(d, sync_every=1)
    rng = np.random.default_rng(3)
    for i in range(1, 5):
        w.append(i, [_random_delta(rng)])
    w.close()
    path = wal_path(d)
    good = os.path.getsize(path)
    # torn tail: a partial record is a clean stop at lsn 4
    with open(path, "ab") as f:
        f.write(b"\x07\x00\x00\x00garbage")
    lsns = [l for l, _, _, _ in read_records(path)]
    assert lsns == [1, 2, 3, 4]
    last, end, size = scan_wal(path)
    assert (last, end) == (4, good) and size > good
    # a fresh writer refuses the damaged log unless asked to repair
    with pytest.raises(WalCorruptError):
        WalWriter(d, sync_every=1)
    w2 = WalWriter(d, sync_every=1, repair=True)
    assert w2.last_lsn == 4
    assert os.path.getsize(path) == good
    w2.append(5, [_random_delta(rng)])
    w2.close()
    # mid-log damage (NOT at the tail) must raise, never skip silently
    with open(path, "r+b") as f:
        f.seek(good - 3)
        b = f.read(1)
        f.seek(good - 3)
        f.write(bytes([b[0] ^ 0x10]))
    with pytest.raises(WalCorruptError):
        list(read_records(path))


# ----------------------------------------------------------------- replay --

@pytest.mark.parametrize("shape", ["star", "chain", "snowflake"])
def test_prefix_replay_bit_equals_direct_apply_and_oracle(shape):
    """THE durability property: replaying any prefix of the log into a
    fresh scorer bit-equals a scorer that applied the same prefix
    directly; the full replay also bit-equals the recompute oracle."""
    sch = _small(shape)
    trees = _fit(sch)
    root = sch.tables[0].name

    ms = MaintainedScorer(compile_ensemble(sch, trees))
    wdir = None
    import tempfile
    wdir = tempfile.mkdtemp()
    w = WalWriter(wdir, sync_every=1).attach(ms.state)
    refs = []                            # (tot, cnt) after each batch
    for batch in delta_stream(sch, ms.live_rows, seed=17, n_batches=5,
                              ops_per_batch=5):
        ms.apply(batch)
        refs.append(tuple(np.asarray(a) for a in ms.score_grouped(root)))
    w.close()
    n = len(refs)

    records = [(l, ds) for l, ds, _, _ in read_records(wal_path(wdir))]
    assert [l for l, _ in records] == list(range(1, n + 1))

    for k in sorted({1, (n + 1) // 2, n}):
        ms2 = MaintainedScorer(compile_ensemble(sch, trees))
        for _, ds in records[:k]:
            ms2.apply(ds)
        assert ms2.data_version == k
        tot, cnt = (np.asarray(a) for a in ms2.score_grouped(root))
        assert _arrays_equal(tot, refs[k - 1][0])
        assert _arrays_equal(cnt, refs[k - 1][1])
        if k == n:
            ot, oc = (np.asarray(a) for a in ms2.recompute_oracle(root))
            assert _arrays_equal(tot, ot)
            assert _arrays_equal(cnt, oc)
    import shutil
    shutil.rmtree(wdir)


def test_follower_tails_and_reports_lag(tmp_path):
    """A follower applies records in LSN order as they land, skips
    heartbeats, and reports zero lag once drained."""
    d = str(tmp_path)
    sch = _small("star")
    trees = _fit(sch)
    ms = MaintainedScorer(compile_ensemble(sch, trees))
    w = WalWriter(d, sync_every=1).attach(ms.state)

    replica = MaintainedScorer(compile_ensemble(sch, trees))
    fol = WalFollower(d, replica.apply, poll_interval_s=0.001)

    batches = list(delta_stream(sch, ms.live_rows, seed=29, n_batches=4,
                                ops_per_batch=4))
    ms.apply(batches[0])
    w.heartbeat()
    assert fol.step() == 1
    assert fol.applied_lsn == 1
    assert fol.replication_lag_s() == 0.0
    assert fol.writer_idle_s() >= 0.0
    for b in batches[1:]:
        ms.apply(b)
    w.close()
    fol.step()
    assert fol.applied_lsn == ms.data_version == len(batches)
    root = sch.tables[0].name
    a = tuple(np.asarray(x) for x in ms.score_grouped(root))
    b = tuple(np.asarray(x) for x in replica.score_grouped(root))
    assert _arrays_equal(a[0], b[0]) and _arrays_equal(a[1], b[1])
