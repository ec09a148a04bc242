"""Property suite: DynamicTable / DynamicEdge / DynamicState invariants
under random insert/delete/update streams — stable slot ids, append-only
key dictionaries, capacity growth, live-row masks.

Hypothesis-driven when available (requirements-dev.txt); the seeded
deterministic sweeps below exercise the same model-based checker so
tier-1 keeps real coverage when hypothesis is absent
(tests/_hypothesis_compat.py makes the @given tests skip cleanly)."""
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:                              # pragma: no cover
    from _hypothesis_compat import given, settings, st

from repro.core import Table
from repro.incremental import (
    DynamicEdge, DynamicState, DynamicTable, TableDelta,
)
from repro.relational.generators import delta_stream, star_schema

N_KEYS = 5          # base join-key domain; inserts may mint keys beyond it


def _base_table(n=6):
    return Table(
        name="t",
        columns={
            "k": (np.arange(n) % N_KEYS).astype(np.int64),
            "x": np.arange(n, dtype=np.float32),
        },
        feature_columns=("x",),
    )


def _check_invariants(dt, edge, shadow, prev_keys):
    """One full audit of the dynamic pair against the shadow model
    (slot → expected row values for every live row)."""
    # live-row mask ≡ shadow domain; capacity only ever covers it
    assert dt.n_live == len(shadow)
    np.testing.assert_array_equal(
        dt.live_slots(), np.asarray(sorted(shadow), np.int64)
    )
    assert dt.capacity >= dt.n_live
    assert len(dt.live) == dt.capacity
    for c in dt.columns:
        assert len(dt.columns[c]) == dt.capacity
    # stable slot ids: every surviving row reads back its exact values
    for s, row in shadow.items():
        for c, v in row.items():
            assert dt.columns[c][s] == v, (s, c)
    # append-only key dictionary: the previous mapping survives verbatim
    for key, kid in prev_keys.items():
        assert edge.key_to_id[key] == kid
    ids = edge.ids["t"]
    assert len(ids) == dt.capacity
    assert edge.n_keys == max(len(edge.key_to_id), 1)
    # every live slot carries the id of its key tuple
    for s, row in shadow.items():
        assert ids[s] == edge.key_to_id[(row["k"],)]
    # effective(): live rows in slot order, values verbatim
    eff = dt.effective()
    slots = sorted(shadow)
    assert eff.n_rows == len(slots)
    for c in eff.columns:
        np.testing.assert_array_equal(
            eff.columns[c],
            np.asarray([shadow[s][c] for s in slots], eff.columns[c].dtype),
        )


def _apply_ops(ops):
    """Drive a DynamicTable + incident DynamicEdge through an op stream,
    auditing the invariants after every delta."""
    t = _base_table()
    other = Table(name="o", columns={"k": np.arange(N_KEYS, dtype=np.int64)})
    dt = DynamicTable(t, slack=0.34)
    do = DynamicTable(other, slack=0.34)
    edge = DynamicEdge(dt, do, ("k",))
    shadow = {
        s: {c: dt.columns[c][s] for c in dt.columns} for s in range(t.n_rows)
    }
    next_x = float(t.n_rows)
    prev_keys = dict(edge.key_to_id)
    for kind, arg in ops:
        live = sorted(shadow)
        if kind == "insert":
            k = 1 + arg % 3
            keys = np.asarray(
                [(arg + i) % (N_KEYS + 2) for i in range(k)], np.int64
            )
            xs = np.asarray([next_x + i for i in range(k)], np.float32)
            next_x += k
            changed, _grew = dt.apply(
                TableDelta("t", inserts={"k": keys, "x": xs})
            )
            ins = changed[-k:]
            edge.assign(dt, ins)
            for s, kk, xx in zip(ins, keys, xs):
                assert int(s) not in shadow      # inserts fill dead slots only
                shadow[int(s)] = {"k": kk, "x": xx}
        elif kind == "delete":
            if len(live) <= 1:
                continue
            s = live[arg % len(live)]
            dt.apply(TableDelta("t", deletes=np.asarray([s])))
            del shadow[s]
        else:                                    # update of a non-key column
            s = live[arg % len(live)]
            xs = np.asarray([next_x], np.float32)
            next_x += 1
            dt.apply(TableDelta("t", updates=(np.asarray([s]), {"x": xs})))
            shadow[s]["x"] = xs[0]
        _check_invariants(dt, edge, shadow, prev_keys)
        prev_keys = dict(edge.key_to_id)


@settings(max_examples=50, deadline=None)
@given(st.lists(
    st.tuples(st.sampled_from(["insert", "delete", "update"]),
              st.integers(0, 10 ** 6)),
    max_size=40,
))
def test_dynamic_store_invariants_hypothesis(ops):
    _apply_ops(ops)


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_dynamic_store_invariants_seeded(seed):
    """Deterministic sweep over the same checker (runs without
    hypothesis; biased toward inserts so capacity growth triggers)."""
    rng = np.random.default_rng(seed)
    kinds = ["insert", "insert", "delete", "update"]
    ops = [(kinds[int(rng.integers(len(kinds)))], int(rng.integers(10 ** 6)))
           for _ in range(60)]
    _apply_ops(ops)


def test_insert_burst_forces_capacity_growth():
    """Growth path pinned explicitly: a burst larger than the free-slot
    pool doubles capacity while every pre-existing live row keeps its
    slot, values, and key id."""
    ops = [("insert", 2)] * 12                   # 3 rows per op, slack 0.34
    _apply_ops(ops)


def test_dynamic_state_version_semantics():
    """DynamicState: data_version bumps per batch; jt_version bumps only
    on structural change (inserts/growth), never on value updates."""
    sch = star_schema(seed=21, n_fact=40, n_dim=6)
    state = DynamicState(sch, slack=0.25)
    jv0, dv0 = state.jt_version, state.data_version
    upd = TableDelta("dim0", updates=(
        np.asarray([0, 1]),
        {c: np.zeros(2, np.float32) for c in sch.table("dim0").feature_columns},
    ))
    state.apply([upd])
    assert state.data_version == dv0 + 1
    assert state.jt_version == jv0               # pure update: not structural
    fact = sch.table("fact")
    row = {c: np.zeros(1, np.asarray(fact.col(c)).dtype) for c in fact.columns}
    changes = state.apply([TableDelta("fact", inserts=row)])
    assert state.jt_version == jv0 + 1           # insert assigns key ids
    assert changes[0].n_inserted == 1
    # the maintained join tree reflects the new slot's key assignment
    jt = state.jt("fact")
    cap = state.capacity("fact")
    for e in jt.edges:
        ids = e.parent_ids if e.parent == jt.root else e.child_ids
        if len(ids) == cap:
            break
    else:
        pytest.fail("no maintained id array sized to the fact capacity")


def test_dynamic_state_random_stream_effective_schema_consistent():
    """Model check at the state level: after an arbitrary churn stream,
    effective_schema() row counts and live sets agree with the stores."""
    sch = star_schema(seed=22, n_fact=50, n_dim=8)
    state = DynamicState(sch, slack=0.1)
    for batch in delta_stream(sch, state.live_rows, seed=23,
                              n_batches=6, ops_per_batch=6):
        state.apply(batch)
    eff = state.effective_schema()
    for t in sch.tables:
        assert eff.table(t.name).n_rows == state.tables[t.name].n_live


@pytest.mark.parametrize("n_cols", [1, 2])
def test_edge_ids_follow_first_appearance(n_cols):
    """Assigning a batch of slots gives each distinct key tuple the id a
    slot-by-slot pass would: existing keys keep theirs, new keys take
    the next ids in order of their first slot, repeats share one."""
    rng = np.random.default_rng(n_cols)
    cols = {f"k{j}": rng.integers(-3, 4, 40).astype(np.int32) for j in range(n_cols)}
    names = tuple(cols)

    def table(name, keys):
        return DynamicTable(Table(name=name, columns=dict(keys, x=np.zeros(len(
            keys[names[0]]), np.float32)), feature_columns=("x",)), slack=1.0)

    a = table("a", {c: v[:25] for c, v in cols.items()})
    b = table("b", {c: v[25:] for c, v in cols.items()})
    edge = DynamicEdge(a, b, names)
    want, ids = {}, {}
    for t in (a, b):
        ids[t.name] = np.zeros(t.capacity, np.int32)
        for s in t.live_slots():
            key = tuple(t.columns[c][s] for c in names)
            ids[t.name][s] = want.setdefault(key, len(want))
    assert edge.key_to_id == want
    for t in (a, b):
        np.testing.assert_array_equal(edge.ids[t.name], ids[t.name])
    # a re-assignment of live slots mints nothing and moves no id
    assert not edge.assign(a, a.live_slots()[::-1])
    np.testing.assert_array_equal(edge.ids["a"], ids["a"])
