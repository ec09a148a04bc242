"""Plain reference for boosted regression trees over a materialized join.

Trees are heap-ordered arrays as the program stores them: ``feat`` and
``thr`` for the 2^depth - 1 internal nodes (feature -1 = no split),
``leaf`` for the 2^depth leaves.  A row goes right at a node when its
feature value is at least the threshold; both are float32 data, so the
comparison is exact in any precision.  Everything else is float64,
unless ``q`` rounds it: the control passes a rounding to bfloat16.

A round's tree is judged against the greedy optimum (paper Alg. 1/2):
at each node, the split that maximizes S_L^2/n_L + S_R^2/n_R over every
feature and every boundary between distinct values, on the residuals
of the previous trees; each leaf holds lr times its rows' mean residual.
"""
from __future__ import annotations

import numpy as np


def _exact(x):
    return x


def predict(trees, X: np.ndarray, q=_exact) -> np.ndarray:
    out = np.zeros(len(X), np.float64)
    for t in trees:
        out = q(out + q(leaf_values(t)[leaf_index(t, X)]))
    return out


def rounded(trees, q) -> list[dict]:
    """The trees with thresholds and leaf values rounded by ``q``."""
    return [{"feat": t["feat"], "thr": q(t["thr"]).astype(np.float32),
             "leaf": q(t["leaf"])} for t in trees]


def leaf_values(t) -> np.ndarray:
    return np.asarray(t["leaf"], np.float64)


def node_index(t, X: np.ndarray, level: int) -> np.ndarray:
    """Within-level node index of every row at ``level``."""
    feat = np.asarray(t["feat"])
    thr = np.asarray(t["thr"], np.float32)
    idx = np.zeros(len(X), np.int64)
    for lv in range(level):
        k = 2 ** lv - 1 + idx
        f = feat[k]
        v = X[np.arange(len(X)), np.maximum(f, 0)]
        idx = 2 * idx + ((v >= thr[k]) & (f >= 0))
    return idx


def leaf_index(t, X: np.ndarray) -> np.ndarray:
    depth = len(np.asarray(t["leaf"])).bit_length() - 1
    return node_index(t, X, depth)


def best_split(Xn: np.ndarray, r: np.ndarray, orders, mask, q=_exact):
    """(gain, feature, threshold) of the best split of the rows ``mask``.

    ``orders[f]`` is the argsort of column f over all rows; gains are
    S_L^2/n_L + S_R^2/n_R - S^2/n.  No valid boundary gives gain 0."""
    best = (0.0, -1, np.inf)
    m = int(mask.sum())
    if m < 2:
        return best
    tot = q(np.sum(r[mask]))
    base = q(tot * tot / m)
    for f, order in enumerate(orders):
        o = order[mask[order]]
        xs, rs = Xn[o, f], r[o]
        cs = q(np.cumsum(rs))[:-1]
        nl = np.arange(1, m, dtype=np.float64)
        valid = xs[1:] > xs[:-1]
        if not valid.any():
            continue
        gain = q(q(cs * cs / nl) + q((tot - cs) ** 2 / (m - nl))) - base
        gain = np.where(valid, gain, -np.inf)
        i = int(np.argmax(gain))
        if gain[i] > best[0]:
            best = (float(gain[i]), f, xs[i + 1])
    return best


def split_gain(X: np.ndarray, r: np.ndarray, mask, f: int, thr) -> float:
    """Gain of splitting the rows ``mask`` at feature ``f`` >= ``thr``."""
    m = int(mask.sum())
    if f < 0 or m < 2:
        return 0.0
    right = mask & (X[:, f] >= np.float32(thr))
    nr = int(right.sum())
    if nr == 0 or nr == m:
        return 0.0
    tot, sr = np.sum(r[mask]), np.sum(r[right])
    sl = tot - sr
    return float(sl * sl / (m - nr) + sr * sr / nr - tot * tot / m)


def grow(X: np.ndarray, r: np.ndarray, depth: int, lr: float = 1.0,
         min_gain: float = 1e-7, q=_exact):
    """A greedy tree on residuals ``r`` and its per-level node SSRs:
    what the reference computes in the program's place (the control,
    with ``q`` rounding to a lower precision)."""
    Xq, rq = q(X.astype(np.float64)), q(r)
    orders = [np.argsort(Xq[:, f], kind="stable") for f in range(X.shape[1])]
    feat = np.full(2 ** depth - 1, -1, np.int32)
    thr = np.full(2 ** depth - 1, np.inf, np.float32)
    node = np.zeros(len(X), np.int64)
    means = np.asarray([q(np.mean(rq))])
    ssr = []
    for level in range(depth):
        K = 2 ** level
        ssr.append(np.asarray([q(np.sum(rq[node == k] ** 2)) for k in range(K)]))
        new_means = np.zeros(2 * K)
        new_node = np.zeros_like(node)
        for k in range(K):
            mask = node == k
            gain, f, t = best_split(Xq, rq, orders, mask, q)
            ok = gain > min_gain
            if ok:
                feat[K - 1 + k], thr[K - 1 + k] = f, t
                right = mask & (Xq[:, f] >= t)
                left = mask & ~right
                new_means[2 * k] = q(np.mean(rq[left]))
                new_means[2 * k + 1] = q(np.mean(rq[right]))
            else:
                new_means[2 * k] = new_means[2 * k + 1] = means[k]
            new_node[mask] = 2 * k + (ok & (Xq[mask, max(f, 0)] >= t))
        node, means = new_node, new_means
    return {"feat": feat, "thr": thr, "leaf": q(lr * means)}, ssr


def judge(X: np.ndarray, r: np.ndarray, t, ssr=None, lr: float = 1.0) -> dict:
    """How far tree ``t`` (grown on residuals ``r``) lies from the greedy
    optimum, in float64:

    - ``gain_gap``: per internal node with rows, the best gain less the
      gain of ``t``'s split, over the larger of the best gain and the
      median best gain of the tree's nodes;
    - ``leaf_rel``: per leaf with rows, |leaf - lr * mean residual| over
      the larger of |lr * mean| and the median of those;
    - ``ssr_rel`` (when per-level node SSRs are given): per node,
      |SSR - sum of r^2| over the larger of that sum and the level's
      median.
    """
    depth = len(np.asarray(t["leaf"])).bit_length() - 1
    orders = [np.argsort(X[:, f], kind="stable") for f in range(X.shape[1])]
    feat, thr = np.asarray(t["feat"]), np.asarray(t["thr"], np.float32)
    best, got, want_ssr, got_ssr = [], [], [], []
    for level in range(depth):
        idx = node_index(t, X, level)
        lvl_want = []
        for k in range(2 ** level):
            mask = idx == k
            lvl_want.append(float(np.sum(r[mask] ** 2)))
            if mask.sum() < 2:
                continue
            b = best_split(X, r, orders, mask)[0]
            best.append(b)
            got.append(split_gain(X, r, mask, int(feat[2 ** level - 1 + k]),
                                  thr[2 ** level - 1 + k]))
        want_ssr.append(np.asarray(lvl_want))
        if ssr is not None:
            got_ssr.append(np.asarray(ssr[level], np.float64))
    best, got = np.asarray(best), np.asarray(got)
    denom = np.maximum(best, np.median(best[best > 0]) if np.any(best > 0) else 1.0)
    gap = np.maximum(best - got, 0.0) / denom
    # one and the same split summed in two orders differs by float64
    # rounding alone, far below a billionth of the gain
    gap[gap < 1e-9] = 0.0
    gain_gap = float(np.max(gap)) if len(best) else 0.0

    li = leaf_index(t, X)
    leaves = np.unique(li)
    want = np.asarray([lr * np.mean(r[li == a]) for a in leaves])
    have = leaf_values(t)[leaves]
    scale = np.maximum(np.abs(want), np.median(np.abs(want)))
    out = {"gain_gap": gain_gap,
           "leaf_rel": float(np.max(np.abs(have - want) / scale))}
    if ssr is not None:
        rel = []
        for w, g in zip(want_ssr, got_ssr):
            rel.append(np.max(np.abs(g - w) / np.maximum(w, np.median(w))))
        out["ssr_rel"] = float(np.max(rel))
    return out
