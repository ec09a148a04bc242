"""The few places where the benchmark hands its inputs to the program."""
from __future__ import annotations

import numpy as np


def schema_of(cfg: dict, data: dict):
    """The program's ``Schema`` over the generated tables, checked to
    hold the features in the configuration's order (the order the
    reference and the models use)."""
    from repro.core.schema import Schema, Table

    tables = []
    for t in cfg["tables"]:
        tab = Table(t["name"], dict(data[t["name"]]),
                    feature_columns=tuple(t["features"]) or tuple(t["keys"]))
        # a table with no numeric column carries no feature (Table's own
        # default would make every column one)
        tab.feature_columns = tuple(t["features"])
        tables.append(tab)
    schema = Schema(tables, label=tuple(cfg["label"]))
    want = [(t["name"], c) for t in cfg["tables"] for c in t["features"]]
    if schema.features != want:
        raise RuntimeError(f"schema feature order {schema.features} != {want}")
    return schema


def tree_arrays(t: dict):
    import jax.numpy as jnp
    from repro.core.tree import TreeArrays

    return TreeArrays(feat=jnp.asarray(t["feat"], jnp.int32),
                      thr=jnp.asarray(t["thr"], jnp.float32),
                      leaf=jnp.asarray(t["leaf"], jnp.float32))


def tree_dict(t) -> dict:
    return {"feat": np.asarray(t.feat), "thr": np.asarray(t.thr),
            "leaf": np.asarray(t.leaf)}
