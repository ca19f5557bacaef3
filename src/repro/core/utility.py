"""Utility-function estimation (paper §3.2, eq. 12).

The GS (i) trains a model on a source dataset and stores the checkpoint
trajectory {w^0..w^Imax}; (ii) samples (staleness vector s, training status
T) pairs; (iii) measures the loss drop Δf of applying the staleness-vector's
local updates to w^{i_start}; (iv) fits a regression model û(φ(s), T) ≈ Δf.

Featurization φ: staleness vectors live in {-1,0,..,s_max}^K with K varying
across constellations, so we use the *histogram* of staleness values (counts
of gradients at each staleness 0..s_max) + total count + T. This is the same
feature the schedule simulator (repro.core.staleness) emits, so the search
can score candidates without materializing per-satellite vectors.

Two regressors: a from-scratch random forest (paper-faithful: "a standard
random forest regression") and a JAX MLP (beyond-paper alternative).
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Callable, List, Optional

import jax
import jax.numpy as jnp
import numpy as np


def featurize(hist: np.ndarray, status: float) -> np.ndarray:
    """hist: (..., s_max+1) counts; status: scalar training status T.

    Features: raw histogram + derived physical quantities the utility
    actually depends on — total count (direction variance ~ 1/count under
    eq. 4 normalization), staleness-compensated mass sum_s hist_s * c(s),
    and mean staleness — plus T."""
    hist = np.asarray(hist, np.float32)
    total = hist.sum(axis=-1, keepdims=True)
    s_vals = np.arange(hist.shape[-1], dtype=np.float32)
    c = (s_vals + 1.0) ** -0.5
    fresh_mass = (hist * c).sum(axis=-1, keepdims=True)
    mean_stale = (hist * s_vals).sum(axis=-1, keepdims=True) \
        / np.maximum(total, 1.0)
    stat = np.broadcast_to(np.float32(status), total.shape)
    return np.concatenate([hist, total, fresh_mass, mean_stale, stat],
                          axis=-1)


@functools.partial(jax.jit, static_argnames=("s_max",))
def _featurize_jnp(hist, status, *, s_max: int):
    hist = hist.astype(jnp.float32)
    total = hist.sum(axis=-1, keepdims=True)
    s_vals = jnp.arange(s_max + 1, dtype=jnp.float32)
    # c(s) table precomputed on host so both featurize paths share the
    # exact same float32 constants
    c = jnp.asarray((np.arange(s_max + 1, dtype=np.float32) + 1.0) ** -0.5)
    fresh_mass = (hist * c).sum(axis=-1, keepdims=True)
    mean_stale = (hist * s_vals).sum(axis=-1, keepdims=True) \
        / jnp.maximum(total, 1.0)
    stat = jnp.broadcast_to(jnp.float32(status), total.shape)
    return jnp.concatenate([hist, total, fresh_mass, mean_stale, stat],
                           axis=-1)


def featurize_jnp(hist, status):
    """Device-resident `featurize`: same features, jnp end-to-end (accepts
    and returns jnp arrays; XLA reduction order may differ from the host
    path by ~1 ulp)."""
    return _featurize_jnp(hist, jnp.float32(status),
                          s_max=hist.shape[-1] - 1)


def n_features(s_max: int) -> int:
    """Width of `featurize`'s output: the raw histogram (s_max+1) plus
    total count, staleness-compensated fresh mass, mean staleness, and the
    training status T. Depends only on `s_max`, never on K — which is what
    makes a fitted regressor transferable across constellations."""
    return s_max + 5


def transfer_ready(regressor, *, s_max: int = 8) -> bool:
    """Forest-transfer predicate: True when `regressor` can serve eq.-13
    schedule searches on *any* constellation at this `s_max` without
    refitting. The featurization is K-agnostic by construction (histogram
    counts scale with K, the feature semantics don't — paper §3.2), so the
    hard requirements are a matching feature width (when the regressor
    records one at fit time) and a device prediction path (the search and
    the replan service stay on device end-to-end)."""
    nf = getattr(regressor, "n_features_", None)
    if nf is not None and int(nf) != n_features(s_max):
        return False
    return callable(getattr(regressor, "predict_device", None))


def transfer_report(regressor, feats) -> dict:
    """Cross-constellation evaluation: how a feature batch from a *other*
    constellation than the fit (e.g. flock191-fitted û asked about
    starlink400 histograms) sits relative to the regressor's training
    envelope, plus a prediction summary.

    Tree ensembles extrapolate as constants beyond their training
    envelope — out-of-envelope counts from a larger K saturate the
    fresh-mass/total splits rather than exploding — so `in_envelope` below
    1.0 flags *reduced resolution*, not invalid predictions. Returns:
      rows, finite (inputs all finite), in_envelope (fraction of feature
      values inside the per-feature fit range; only when the regressor
      recorded one), out_features (feature indices with any value outside
      the envelope), pred_min/pred_max/pred_finite.
    """
    X = np.asarray(feats, np.float32)
    if X.ndim == 1:
        X = X[None, :]
    out = {"rows": int(X.shape[0]),
           "finite": bool(np.isfinite(X).all())}
    lo = getattr(regressor, "feature_low_", None)
    hi = getattr(regressor, "feature_high_", None)
    if lo is not None and hi is not None:
        inside = (X >= lo) & (X <= hi)
        out["in_envelope"] = float(inside.mean())
        out["out_features"] = [int(j) for j in
                               np.flatnonzero(~inside.all(axis=0))]
    preds = np.asarray(regressor.predict(X))
    out["pred_min"] = float(preds.min())
    out["pred_max"] = float(preds.max())
    out["pred_finite"] = bool(np.isfinite(preds).all())
    return out


def _record_envelope(regressor, X):
    """Remember the fit's feature geometry (width + per-feature range) so
    `transfer_ready` / `transfer_report` can reason about serving other
    constellations. Pure metadata — predictions are untouched."""
    regressor.n_features_ = int(X.shape[1])
    regressor.feature_low_ = X.min(axis=0)
    regressor.feature_high_ = X.max(axis=0)


# ---------------------------------------------------------------------------
# Random forest (numpy CART ensemble)


@dataclass
class _Node:
    feature: int = -1
    thresh: float = 0.0
    left: int = -1
    right: int = -1
    value: float = 0.0


@dataclass(frozen=True)
class ForestArrays:
    """Structure-of-arrays view of a fitted forest: (n_trees, max_nodes)
    per-node fields in the fit's depth-first order, leaf-padded so every
    tree shares one node axis. `feature < 0` marks a leaf; leaf left/right
    self-loop to node 0 so the level-wise host traversal is branch-free.
    The device path re-lays it as complete heaps (`forest_to_heap`)."""
    feature: np.ndarray    # (T, M) int32, -1 at leaves / padding
    thresh: np.ndarray     # (T, M) f32
    left: np.ndarray       # (T, M) int32
    right: np.ndarray      # (T, M) int32
    value: np.ndarray      # (T, M) f32
    depth: int             # max root-to-leaf edge count


def forest_to_arrays(trees: List[List[_Node]], max_depth: int
                     ) -> ForestArrays:
    T = len(trees)
    M = max(len(t) for t in trees)
    feature = np.full((T, M), -1, np.int32)
    thresh = np.zeros((T, M), np.float32)
    left = np.zeros((T, M), np.int32)
    right = np.zeros((T, M), np.int32)
    value = np.zeros((T, M), np.float32)
    for ti, nodes in enumerate(trees):
        for ni, n in enumerate(nodes):
            feature[ti, ni] = n.feature
            thresh[ti, ni] = n.thresh
            left[ti, ni] = max(n.left, 0)
            right[ti, ni] = max(n.right, 0)
            value[ti, ni] = n.value
    return ForestArrays(feature, thresh, left, right, value, max_depth)


def forest_to_heap(fa: ForestArrays):
    """Re-lay every tree as a complete heap of `fa.depth` levels: inner
    node h has children 2h+1 and 2h+2. Returns feature (T, 2^D - 1) int32,
    thresh (T, 2^D - 1) f32 and leaf values (T, 2^D) f32. A leaf met above
    the last level fills every heap leaf under it with its value; the
    padded inner nodes under it keep feature 0 and threshold +inf, and
    since both their children carry the same value the direction taken
    there cannot change the result. A node reached at depth D serves its
    own value, as the level-wise traversal that stops after D levels does.
    Takes depth-first (unbalanced) and heap-ordered forests alike."""
    T, D = fa.feature.shape[0], fa.depth
    feature = np.zeros((T, 2 ** D - 1), np.int32)
    thresh = np.full((T, 2 ** D - 1), np.inf, np.float32)
    leaf = np.zeros((T, 2 ** D), np.float32)
    for t in range(T):
        stack = [(0, 0, 0)]                  # (node, heap position, level)
        while stack:
            n, h, d = stack.pop()
            if d < D and fa.feature[t, n] >= 0:
                feature[t, h] = fa.feature[t, n]
                thresh[t, h] = fa.thresh[t, n]
                stack += [(fa.left[t, n], 2 * h + 1, d + 1),
                          (fa.right[t, n], 2 * h + 2, d + 1)]
            else:
                width = 2 ** (D - d)         # heap leaves under h
                first = (h - (2 ** d - 1)) * width
                leaf[t, first:first + width] = fa.value[t, n]
    return feature, thresh, leaf


def forest_predict_np(fa: ForestArrays, X: np.ndarray) -> np.ndarray:
    """Vectorized level-wise traversal: every (tree, row) pair walks one
    level per iteration; rows already at a leaf stay put. Bit-matches the
    per-row node walk (same leaf values, same f32 mean over trees)."""
    X = np.asarray(X, np.float32)
    T, N = fa.feature.shape[0], X.shape[0]
    rows = np.arange(T)[:, None]
    cols = np.arange(N)[None, :]
    idx = np.zeros((T, N), np.int32)
    for _ in range(fa.depth):
        f = fa.feature[rows, idx]
        leaf = f < 0
        xv = X[cols, np.clip(f, 0, X.shape[1] - 1)]
        go_left = xv <= fa.thresh[rows, idx]
        nxt = np.where(go_left, fa.left[rows, idx], fa.right[rows, idx])
        idx = np.where(leaf, idx, nxt)
    return fa.value[rows, idx].mean(axis=0)


# The select walk costs O(2^depth) per (tree, row); deeper forests keep
# the gather traversal.
DENSE_MAX_DEPTH = 8


def _select(pos, *tables):
    """table[:, pos] of each table, for pos (T, N) in [0, table.shape[1]),
    by one chain of exact `jnp.where` selects that share their compares,
    with no gather."""
    outs = [jnp.broadcast_to(tb[:, :1], pos.shape) for tb in tables]
    for j in range(1, tables[0].shape[1]):
        hit = pos == j
        outs = [jnp.where(hit, tb[:, j:j + 1], o)
                for tb, o in zip(tables, outs)]
    return outs


@jax.jit
def _forest_predict_device(feature, thresh, leaf, X):
    """Gather-free walk over the heap layout (`forest_to_heap`): each
    (tree, row) pair holds its node's position within the current level,
    (T, N). The node's feature and threshold are picked by selects over
    the level's 2^d nodes, the row's feature value by selects over the F
    columns (clipped to the last, as the host walk clips), and a row steps
    to 2p + (not x <= thresh), in float32. The leaf value is selected the
    same way and averaged over trees. Every select is exact, so the result
    is bit-identical to the gather traversal."""
    T, depth = leaf.shape[0], leaf.shape[1].bit_length() - 1
    N, F = X.shape
    Xt = X.T
    pos = jnp.zeros((T, N), jnp.int32)
    for d in range(depth):
        level = slice(2 ** d - 1, 2 ** (d + 1) - 1)
        f, t = _select(pos, feature[:, level], thresh[:, level])
        xv = jnp.broadcast_to(Xt[F - 1], (T, N))
        for c in range(F - 1):
            xv = jnp.where(f == c, Xt[c], xv)
        pos = 2 * pos + jnp.logical_not(xv <= t).astype(jnp.int32)
    leaf_value, = _select(pos, leaf)
    return leaf_value.mean(axis=0)


@functools.partial(jax.jit, static_argnames=("depth",))
def _forest_predict_gather(feature, thresh, left, right, value, offsets,
                           X, *, depth: int):
    """Level-wise traversal over the flattened forest, for forests deeper
    than `DENSE_MAX_DEPTH`. All node fields are 1-D (total_nodes,) arrays
    and `offsets` (T, 1) holds each tree's root index: 1-D `jnp.take`
    gathers lower much faster on CPU than the 2-D take_along_axis
    equivalent. left/right store tree-local child indices, hence the
    `offsets +` rebase each level."""
    T = offsets.shape[0]
    N, F = X.shape
    Xf = X.reshape(-1)
    cols = jnp.arange(N)[None, :]

    def body(_, idx):
        f = jnp.take(feature, idx)
        leaf = f < 0
        xv = jnp.take(Xf, cols * F + jnp.clip(f, 0, F - 1))
        go_left = xv <= jnp.take(thresh, idx)
        nxt = offsets + jnp.where(go_left, jnp.take(left, idx),
                                  jnp.take(right, idx))
        return jnp.where(leaf, idx, nxt)

    idx = jax.lax.fori_loop(0, depth, body,
                            jnp.broadcast_to(offsets, (T, N)))
    return jnp.take(value, idx).mean(axis=0)


def forest_dense(regressor) -> int:
    """1 when `regressor.predict_device` runs the gather-free forest walk,
    else 0: the `forest_dense` count of the search's spans."""
    return int(getattr(regressor, "dense", False))


def device_predictor(regressor):
    """(fn, arrays) with fn(*arrays, X) == regressor.predict_device(X), for
    a caller that runs the prediction inside its own jitted program: the
    regressor's own `device_predictor()` where it has one, else its bound
    `predict_device` with no operands (a hashable static argument, whose
    weights the caller's program then holds as constants)."""
    own = getattr(regressor, "device_predictor", None)
    return own() if own is not None else (regressor.predict_device, ())


class RandomForestRegressor:
    def __init__(self, n_trees: int = 40, max_depth: int = 6,
                 min_leaf: int = 4, feature_frac: float = 0.8,
                 seed: int = 0):
        self.n_trees = n_trees
        self.max_depth = max_depth
        self.min_leaf = min_leaf
        self.feature_frac = feature_frac
        self.seed = seed
        self.trees: List[List[_Node]] = []
        self._arrays: Optional[ForestArrays] = None
        self._device_arrays = None

    def _build(self, X, y, rng) -> List[_Node]:
        nodes: List[_Node] = []

        def grow(idx, depth) -> int:
            node = _Node(value=float(y[idx].mean()))
            nodes.append(node)
            me = len(nodes) - 1
            if depth >= self.max_depth or len(idx) < 2 * self.min_leaf \
                    or np.ptp(y[idx]) < 1e-12:
                return me
            nf = max(1, int(X.shape[1] * self.feature_frac))
            feats = rng.choice(X.shape[1], nf, replace=False)
            best = (None, None, np.inf)
            for f in feats:
                xs = X[idx, f]
                order = np.argsort(xs)
                xs_s, ys_s = xs[order], y[idx][order]
                csum = np.cumsum(ys_s)
                csq = np.cumsum(ys_s ** 2)
                n = len(ys_s)
                for cut in range(self.min_leaf, n - self.min_leaf):
                    if xs_s[cut] == xs_s[cut - 1]:
                        continue
                    ln, rn = cut, n - cut
                    lsum, lsq = csum[cut - 1], csq[cut - 1]
                    rsum, rsq = csum[-1] - lsum, csq[-1] - lsq
                    sse = (lsq - lsum ** 2 / ln) + (rsq - rsum ** 2 / rn)
                    if sse < best[2]:
                        best = (f, (xs_s[cut] + xs_s[cut - 1]) / 2, sse)
            if best[0] is None:
                return me
            f, t, _ = best
            mask = X[idx, f] <= t
            node.feature, node.thresh = int(f), float(t)
            node.left = grow(idx[mask], depth + 1)
            node.right = grow(idx[~mask], depth + 1)
            return me

        grow(np.arange(len(y)), 0)
        return nodes

    def fit(self, X, y):
        X = np.asarray(X, np.float32)
        y = np.asarray(y, np.float32)
        rng = np.random.default_rng(self.seed)
        self.trees = []
        for _ in range(self.n_trees):
            boot = rng.integers(0, len(y), len(y))
            self.trees.append(self._build(X[boot], y[boot], rng))
        self._arrays = None
        self._device_arrays = None
        _record_envelope(self, X)
        return self

    def arrays(self) -> ForestArrays:
        """Structure-of-arrays view, built once per fit."""
        if self._arrays is None:
            self._arrays = forest_to_arrays(self.trees, self.max_depth)
        return self._arrays

    def _predict_tree(self, nodes: List[_Node], X) -> np.ndarray:
        out = np.empty(len(X), np.float32)
        for i, x in enumerate(X):
            n = 0
            while nodes[n].feature >= 0:
                n = nodes[n].left if x[nodes[n].feature] <= nodes[n].thresh \
                    else nodes[n].right
            out[i] = nodes[n].value
        return out

    def predict_reference(self, X) -> np.ndarray:
        """Per-row, per-tree node walk — the O(rows * trees) pure-Python
        oracle the vectorized paths are tested against."""
        X = np.asarray(X, np.float32)
        return np.mean([self._predict_tree(t, X) for t in self.trees],
                       axis=0)

    def predict(self, X) -> np.ndarray:
        X = np.asarray(X, np.float32)
        return forest_predict_np(self.arrays(), X)

    @property
    def dense(self) -> bool:
        """Whether `predict_device` runs the gather-free heap walk (depth
        at most `DENSE_MAX_DEPTH`) rather than the gather traversal."""
        return self.max_depth <= DENSE_MAX_DEPTH

    def predict_device(self, X):
        """jit-compatible prediction on a jnp feature batch; stays on
        device (the schedule search feeds simulator histograms straight in
        with no host round-trip). Forests of depth up to `DENSE_MAX_DEPTH`
        are re-laid once per fit as complete heaps and walked with selects
        (`_forest_predict_device`); deeper ones keep the gather traversal.
        The two give the same bits; they pick the same leaves as
        `predict`, whose numpy mean over trees may round differently."""
        fn, arrays = self.device_predictor()
        return fn(*arrays, jnp.asarray(X))

    def device_predictor(self):
        """`predict_device` as (fn, arrays), fn(*arrays, X): the walk and
        the forest's device arrays, built once per fit. A program that
        calls fn takes the arrays as operands, so the trees are not baked
        into it and one compiled program serves every forest of a shape."""
        if self._device_arrays is None:
            fa = self.arrays()
            if self.dense:
                self._device_arrays = (_forest_predict_device, tuple(
                    jnp.asarray(a) for a in forest_to_heap(fa)))
            else:
                T, M = fa.feature.shape
                offsets = (np.arange(T, dtype=np.int32) * M)[:, None]
                self._device_arrays = (functools.partial(
                    _forest_predict_gather, depth=fa.depth), tuple(
                    jnp.asarray(a.reshape(-1))
                    for a in (fa.feature, fa.thresh, fa.left, fa.right,
                              fa.value)) + (jnp.asarray(offsets),))
        return self._device_arrays


# ---------------------------------------------------------------------------
# JAX MLP regressor (beyond-paper alternative)


class MLPRegressor:
    def __init__(self, hidden: int = 64, steps: int = 800, lr: float = 1e-2,
                 seed: int = 0):
        self.hidden = hidden
        self.steps = steps
        self.lr = lr
        self.seed = seed
        self.params = None
        self.mu = self.sd = self.ymu = self.ysd = None

    def _apply(self, p, x):
        h = jnp.tanh(x @ p["w1"] + p["b1"])
        h = jnp.tanh(h @ p["w2"] + p["b2"])
        return (h @ p["w3"] + p["b3"])[..., 0]

    def fit(self, X, y):
        X = np.asarray(X, np.float32)
        y = np.asarray(y, np.float32)
        self.mu, self.sd = X.mean(0), X.std(0) + 1e-6
        self.ymu, self.ysd = y.mean(), y.std() + 1e-9
        _record_envelope(self, X)
        Xn = (X - self.mu) / self.sd
        yn = (y - self.ymu) / self.ysd
        k = jax.random.PRNGKey(self.seed)
        ks = jax.random.split(k, 3)
        F, H = X.shape[1], self.hidden
        p = {"w1": jax.random.normal(ks[0], (F, H)) / np.sqrt(F),
             "b1": jnp.zeros(H),
             "w2": jax.random.normal(ks[1], (H, H)) / np.sqrt(H),
             "b2": jnp.zeros(H),
             "w3": jax.random.normal(ks[2], (H, 1)) / np.sqrt(H),
             "b3": jnp.zeros(1)}

        def loss(p):
            return jnp.mean((self._apply(p, Xn) - yn) ** 2)

        @jax.jit
        def train(p):
            def body(carry, _):
                p, m = carry
                g = jax.grad(loss)(p)
                m = jax.tree.map(lambda m_, g_: 0.9 * m_ + g_, m, g)
                p = jax.tree.map(lambda p_, m_: p_ - self.lr * m_, p, m)
                return (p, m), None
            m0 = jax.tree.map(jnp.zeros_like, p)
            (p, _), _ = jax.lax.scan(body, (p, m0), None, length=self.steps)
            return p

        self.params = train(p)
        return self

    def predict(self, X) -> np.ndarray:
        Xn = (np.asarray(X, np.float32) - self.mu) / self.sd
        return np.asarray(self._apply(self.params, Xn)) * self.ysd + self.ymu

    def predict_device(self, X):
        """jit-compatible prediction on a jnp feature batch (see
        RandomForestRegressor.predict_device)."""
        Xn = (X.astype(jnp.float32) - self.mu) / self.sd
        return self._apply(self.params, Xn) * self.ysd + self.ymu


# ---------------------------------------------------------------------------
# Sample generation (eq. 12)


def _pad_rows(tree, bucket: int):
    """Pad a stacked pytree's leading axis to `bucket` rows by repeating
    row 0 (rows are independent under vmap/segment_sum, so padded rows are
    inert when their weights are zero)."""
    return jax.tree.map(
        lambda b: jnp.concatenate(
            [b, jnp.broadcast_to(b[:1], (bucket - b.shape[0],)
                                 + b.shape[1:])], axis=0), tree)


@functools.partial(jax.jit, static_argnums=1)
def _tile(tree, n: int):
    """`tree` stacked n times on a new leading axis (one base per row)."""
    return jax.tree.map(lambda x: jnp.broadcast_to(x, (n,) + x.shape), tree)


@functools.partial(jax.jit, static_argnames=("n_seg",))
def _segment_accumulate(totals, upd, seg, w, *, n_seg):
    """totals[n] += sum over rows with seg == n of w_row * upd_row, per
    leaf. One jitted scatter-reduce per update group."""
    def add(t, u):
        wb = w.reshape((-1,) + (1,) * (u.ndim - 1))
        return t + jax.ops.segment_sum(u * wb, seg, num_segments=n_seg)
    return jax.tree.map(add, totals, upd)


def generate_utility_samples(
        key,
        checkpoints: List,                    # {w^0..w^Imax} pytrees
        client_update_fn: Callable,           # (params, client_idx, rng)->upd
        eval_loss_fn: Callable,               # params -> float
        *,
        num_clients: int,
        n_samples: int = 200,
        s_max: int = 8,
        clients_per_sample: int = 48,
        participate_p=None,
        seed: int = 0,
        batch_fn: Optional[Callable] = None,
        batched_update_fn: Optional[Callable] = None,
        batched_loss_fn: Optional[Callable] = None,
        eval_chunk: int = 64):
    """Returns (features (N,F), targets ΔF (N,)). Each sample: draw i_start
    and a staleness vector over a client subset, apply eq. 12 against the
    checkpoint trajectory and record the loss drop.

    The participation fraction is drawn per sample from U(0.1, 1.0) so the
    regressor sees the full range of aggregation sizes the scheduler will
    encounter (a 2-gradient aggregation moves the model as far as a
    90-gradient one under eq. 4's normalization, but with a far noisier
    direction — the count-utility curve is exactly what û must learn).
    Updates are normalized by the participating count, matching eq. 4.

    When the batched machinery is supplied — ``batch_fn(ci, rng_int)``
    returning the client's training batch (or None for an empty shard),
    ``batched_update_fn(stacked_bases, stacked_batches)`` (e.g.
    `repro.fl.client.make_batched_client_update`), and
    ``batched_loss_fn(stacked_params) -> (M,) losses`` — generation is
    vectorized on the engine's machinery: sampled client updates are
    grouped by base checkpoint and trained in vmapped jitted calls, and
    the perturbed checkpoints are evaluated in vmapped loss calls instead
    of one host round-trip per sample. The rng draw sequence is shared
    with the loop path, so the integer staleness histograms (and thus the
    features) are identical; targets agree to float tolerance (vmapped
    per-client updates are bit-identical — only the update-sum and loss
    reduction orders differ)."""
    rng = np.random.default_rng(seed)
    Imax = len(checkpoints) - 1
    vectorized = (batch_fn is not None and batched_update_fn is not None
                  and batched_loss_fn is not None)

    # --- draws (one rng stream, identical for both execution paths)
    plans = []   # per sample: (i_start, hist, n_part, any participant)
    items = []   # flattened work list: (sample, base ckpt idx, ci, rng_int)
    for n in range(n_samples):
        i_start = int(rng.integers(min(s_max, Imax - 1) if Imax > s_max
                                   else 0, Imax))
        clients = rng.choice(num_clients, min(clients_per_sample,
                                              num_clients), replace=False)
        s_vec = np.full(len(clients), -1, np.int64)
        p_this = (rng.uniform(0.1, 1.0) if participate_p is None
                  else participate_p)
        part = rng.random(len(clients)) < p_this
        s_vec[part] = rng.integers(0, min(s_max, i_start) + 1,
                                   part.sum())
        n_part = max(int(part.sum()), 1)
        items += [(n, i_start - int(s), int(ci),
                   int(rng.integers(0, 2 ** 31)))
                  for ci, s in zip(clients, s_vec) if s >= 0]
        hist = np.bincount(s_vec[s_vec >= 0], minlength=s_max + 1
                           )[:s_max + 1]
        plans.append((i_start, hist, n_part, bool(part.sum())))

    if not vectorized:
        return _samples_loop(checkpoints, client_update_fn, eval_loss_fn,
                             plans, items)

    # --- vectorized path: train grouped by base checkpoint ...
    totals = jax.tree.map(
        lambda l: jnp.zeros((n_samples,) + np.shape(l),
                            jnp.asarray(l).dtype), checkpoints[0])
    seg_all = np.asarray([it[0] for it in items], np.int32)
    w_all = np.asarray([1.0 / plans[it[0]][2] for it in items], np.float32)
    by_base = {}
    for idx, it in enumerate(items):
        by_base.setdefault(it[1], []).append(idx)
    for base_i, idxs in by_base.items():
        by_shape = {}   # batch-shape signature -> rows (into items)
        for idx in idxs:
            b = batch_fn(items[idx][2], items[idx][3])
            if b is None:        # empty shard: exact-zero update, skip
                continue
            sig = tuple(tuple(np.shape(leaf))
                        for leaf in jax.tree.leaves(b))
            by_shape.setdefault(sig, []).append((idx, b))
        if not by_shape:
            continue
        base = jax.tree.map(jnp.asarray, checkpoints[base_i])
        for mem in by_shape.values():
            m = len(mem)
            bucket = 1 << (m - 1).bit_length()
            # pad with repeats of the first batch BEFORE stacking, so the
            # stacked shapes (and every jit signature downstream) only come
            # in power-of-two buckets — padded rows carry zero weight
            blist = [b for _, b in mem] + [mem[0][1]] * (bucket - m)
            batches = jax.tree.map(lambda *bs: jnp.stack(bs), *blist)
            upd = batched_update_fn(_tile(base, bucket), batches)
            rows = [idx for idx, _ in mem]
            seg = np.zeros(bucket, np.int32)
            w = np.zeros(bucket, np.float32)
            seg[:m], w[:m] = seg_all[rows], w_all[rows]
            totals = _segment_accumulate(totals, upd, jnp.asarray(seg),
                                         jnp.asarray(w), n_seg=n_samples)

    # --- ... and evaluate every base/perturbed checkpoint in vmapped calls
    i_starts = np.asarray([p[0] for p in plans])
    distinct = sorted(set(int(i) for i in i_starts))
    base_stack = jax.tree.map(lambda *ls: jnp.stack(ls),
                              *[checkpoints[i] for i in distinct])
    T_by = dict(zip(distinct,
                    np.asarray(batched_loss_fn(base_stack), np.float64)))
    lookup = jnp.asarray([distinct.index(int(i)) for i in i_starts],
                         jnp.int32)
    new_loss = np.empty(n_samples, np.float64)
    for c0 in range(0, n_samples, eval_chunk):
        # materialize base + total only per chunk, so eval_chunk really
        # bounds peak device memory on top of the `totals` accumulator
        lk = lookup[c0:c0 + eval_chunk]
        sl = jax.tree.map(
            lambda b, t: jnp.take(b, lk, axis=0) + t[c0:c0 + eval_chunk],
            base_stack, totals)
        m = min(eval_chunk, n_samples - c0)
        if m < eval_chunk:
            sl = _pad_rows(sl, eval_chunk)
        new_loss[c0:c0 + m] = np.asarray(batched_loss_fn(sl))[:m]

    feats, targets = [], []
    for n, (i_start, hist, _, any_part) in enumerate(plans):
        T = float(T_by[i_start])
        d_f = T - float(new_loss[n]) if any_part else 0.0
        feats.append(featurize(hist, T))
        targets.append(d_f)
    return np.stack(feats), np.asarray(targets, np.float32)


def _samples_loop(checkpoints, client_update_fn, eval_loss_fn, plans,
                  items):
    """The seed per-sample/per-client loop (kept as the reference path and
    for callers without batched machinery): one client-update dispatch and
    one host loss evaluation per sample."""
    losses = {}

    def loss_at(i):
        if i not in losses:
            losses[i] = float(eval_loss_fn(checkpoints[i]))
        return losses[i]

    per_sample = [[] for _ in plans]
    for it in items:
        per_sample[it[0]].append(it)
    feats, targets = [], []
    for n, (i_start, hist, n_part, _) in enumerate(plans):
        total_update = None
        for _, base_i, ci, rng_int in per_sample[n]:
            upd = client_update_fn(checkpoints[base_i], ci, rng_int)
            upd = jax.tree.map(lambda x: x / n_part, upd)
            total_update = upd if total_update is None else jax.tree.map(
                lambda a, b: a + b, total_update, upd)
        T = loss_at(i_start)
        if total_update is None:
            d_f = 0.0
        else:
            new = jax.tree.map(lambda w, u: w + u, checkpoints[i_start],
                               total_update)
            d_f = T - float(eval_loss_fn(new))
        feats.append(featurize(hist, T))
        targets.append(d_f)
    return np.stack(feats), np.asarray(targets, np.float32)
