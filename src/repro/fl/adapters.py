"""Model adapters: bind a model family to the FL engine (init/loss/eval +
deterministic client batches). FedSpace schedules pytree updates, so any
adapter — MLP, the paper's DenseNet, or a zoo transformer — plugs in.
"""
from __future__ import annotations

import functools
from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig, StageSpec
from repro.data.fmow import NUM_CLASSES, SyntheticFmow
from repro.data.pipeline import ClientDataset, row_bucket
from repro.fl.registry import register_adapter
from repro.kernels.flash_attention.ops import flash_attention_bshd
from repro.kernels.rmsnorm.ops import rmsnorm as rmsnorm_op
from repro.models import attention as A
from repro.models import densenet as DN
from repro.models import layers as L
from repro.models import transformer as TF


def _xent(logits, labels):
    logits = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.mean(lse - ll)


@register_adapter("mlp")
class MlpFmowAdapter:
    """Fast path: 62-class classification over feature vectors."""

    name = "mlp"

    def __init__(self, data: SyntheticFmow, clients: List[ClientDataset],
                 hidden: int = 64):
        self.data = data
        self.clients = clients
        self.hidden = hidden
        self._X_train = data.features(np.arange(data.spec.num_train),
                                      "train")
        self._y_train = data.train_labels
        self._X_val = data.features(np.arange(data.spec.num_val), "val")
        self._y_val = data.val_labels

    def init(self, key):
        ks = jax.random.split(key, 2)
        F, H = self._X_train.shape[1], self.hidden
        return {
            "w1": jax.random.normal(ks[0], (F, H)) * F ** -0.5,
            "b1": jnp.zeros(H),
            "w2": jax.random.normal(ks[1], (H, NUM_CLASSES)) * H ** -0.5,
            "b2": jnp.zeros(NUM_CLASSES),
        }

    def apply(self, params, X):
        h = jnp.tanh(X @ params["w1"] + params["b1"])
        return h @ params["w2"] + params["b2"]

    def loss(self, params, batch):
        X, y = batch
        return _xent(self.apply(params, X), y)

    def client_batch(self, client_idx: int, round_rng: int, batch_size: int,
                     num_batches: int):
        idx = self.clients[client_idx].batches(round_rng, batch_size,
                                               num_batches)
        if idx.shape[1] == 0:
            return None
        return (jnp.asarray(self._X_train[idx]),
                jnp.asarray(self._y_train[idx]))

    def _client_batch_indices(self, client_ids, round_rng: int,
                              batch_size: int, num_batches: int):
        """Index batches for a client set, restricted to the modal batch
        width so they stack. Returns (idx (B, num_batches, b), rows), rows
        being the positions of `client_ids` included and B =
        `row_bucket(len(client_ids))`; idx rows past len(rows) repeat row
        0. Clients with empty shards or off-modal widths are left out."""
        idxs = [self.clients[i].batches(round_rng, batch_size, num_batches)
                for i in client_ids]
        widths = [ix.shape[1] for ix in idxs]
        counts = {}
        for w in widths:
            if w > 0:
                counts[w] = counts.get(w, 0) + 1
        if not counts:
            return None, []
        modal = max(counts, key=lambda w: (counts[w], w))
        rows = [r for r, w in enumerate(widths) if w == modal]
        pad = row_bucket(len(client_ids)) - len(rows)
        return np.stack([idxs[r] for r in rows + rows[:1] * pad]), rows

    def client_batch_many(self, client_ids, round_rng: int, batch_size: int,
                          num_batches: int):
        """Batched `client_batch`: one host gather + one device transfer
        for the whole client set (bit-identical batches to the per-client
        calls). Returns (stacked batch, rows): stacked row j is client
        `client_ids[rows[j]]`'s batch, and rows past len(rows), up to
        `row_bucket(len(client_ids))`, repeat row 0."""
        idx, rows = self._client_batch_indices(client_ids, round_rng,
                                               batch_size, num_batches)
        if not rows:
            return None, []
        return (jnp.asarray(self._X_train[idx]),
                jnp.asarray(self._y_train[idx])), rows

    def eval_batch(self, max_n: int = 2048):
        return jnp.asarray(self._X_val[:max_n]), \
            jnp.asarray(self._y_val[:max_n])

    def accuracy(self, params, max_n: int = 2048) -> float:
        X, y = self.eval_batch(max_n)
        pred = jnp.argmax(self.apply(params, X), axis=-1)
        return float(jnp.mean((pred == y).astype(jnp.float32)))

    def val_loss(self, params, max_n: int = 2048) -> float:
        X, y = self.eval_batch(max_n)
        return float(self.loss(params, (X, y)))


@register_adapter("densenet")
class DenseNetFmowAdapter(MlpFmowAdapter):
    """The paper's model family: DenseNet-style CNN over images, optional
    frozen prefix (transfer learning, §4.1)."""

    name = "densenet"

    def __init__(self, data: SyntheticFmow, clients: List[ClientDataset],
                 growth: int = 8, blocks=(2, 2, 2), stem: int = 16,
                 frozen_blocks: int = 0, val_n: int = 1024):
        self.data = data
        self.clients = clients
        self.growth, self.blocks, self.stem = growth, blocks, stem
        self.frozen_blocks = frozen_blocks
        self._y_train = data.train_labels
        self._val_X = jnp.asarray(
            data.images(np.arange(min(val_n, data.spec.num_val)), "val"))
        self._val_y = jnp.asarray(
            data.val_labels[:min(val_n, data.spec.num_val)])

    def init(self, key):
        return DN.densenet_init(key, num_classes=NUM_CLASSES,
                                growth=self.growth, blocks=self.blocks,
                                stem=self.stem)

    def trainable_mask(self, params):
        return DN.frozen_mask(params, self.frozen_blocks)

    def apply(self, params, X):
        return DN.densenet_apply(params, X)

    def loss(self, params, batch):
        X, y = batch
        return _xent(self.apply(params, X), y)

    def client_batch(self, client_idx, round_rng, batch_size, num_batches):
        idx = self.clients[client_idx].batches(round_rng, batch_size,
                                               num_batches)
        if idx.shape[1] == 0:
            return None
        imgs = np.stack([self.data.images(row, "train") for row in idx])
        return jnp.asarray(imgs), jnp.asarray(self._y_train[idx])

    def client_batch_many(self, client_ids, round_rng, batch_size,
                          num_batches):
        idx, rows = self._client_batch_indices(client_ids, round_rng,
                                               batch_size, num_batches)
        if not rows:
            return None, []
        s = self.data.spec.image_size
        imgs = self.data.images(idx.reshape(-1), "train").reshape(
            idx.shape + (s, s, 3))
        return (jnp.asarray(imgs), jnp.asarray(self._y_train[idx])), rows

    def eval_batch(self, max_n: int = 1024):
        # same slice as val_loss's default, so the utility sampler's
        # vmapped loss sees the exact batch the loop path evaluates
        return self._val_X[:max_n], self._val_y[:max_n]

    def accuracy(self, params, max_n: int = 1024) -> float:
        pred = jnp.argmax(self.apply(params, self._val_X[:max_n]), axis=-1)
        return float(jnp.mean((pred == self._val_y[:max_n]).astype(
            jnp.float32)))

    def val_loss(self, params, max_n: int = 1024) -> float:
        return float(self.loss(params,
                               (self._val_X[:max_n], self._val_y[:max_n])))


@register_adapter("transformer")
class TransformerFmowAdapter(MlpFmowAdapter):
    """Real payload on the wire: a small decoder stack
    (`repro.models.transformer` blocks — GQA attention with RoPE, swiglu
    FFN) classifying each fMoW feature vector as a token sequence, with
    the forward routed through the in-repo kernel dispatch
    (`kernels/flash_attention`, `kernels/rmsnorm`: compiled Pallas
    kernels on TPU, bit-identical jnp oracles everywhere else). Parameter
    pytrees are ~2 orders of magnitude heavier than the MLP's, so uplink
    compression and the link-budget byte accounting act on something
    real. Data plumbing (client batches, eval slices) is inherited from
    `MlpFmowAdapter` unchanged — the adapter contract is the same."""

    name = "transformer"

    def __init__(self, data: SyntheticFmow, clients: List[ClientDataset],
                 d_model: int = 32, num_layers: int = 2, num_heads: int = 4,
                 num_kv_heads: int = 2, d_ff: int = 64, seq_len: int = 8):
        super().__init__(data, clients)
        F = self._X_train.shape[1]
        # the feature vector is read as a sequence of S tokens of width
        # F/S; S is the largest value <= seq_len that divides F
        S = min(seq_len, F)
        while F % S:
            S -= 1
        self.seq_len = S
        self.cfg = ModelConfig(
            name="fl-transformer", arch_type="dense",
            num_layers=num_layers, d_model=d_model, num_heads=num_heads,
            num_kv_heads=num_kv_heads, d_ff=d_ff, vocab_size=NUM_CLASSES,
            stages=(StageSpec(("global",), num_layers),),
            param_dtype="float32")
        self.cfg.validate()

    def init(self, key):
        cfg = self.cfg
        ks = jax.random.split(key, 3)
        F, S = self._X_train.shape[1], self.seq_len
        return {
            "w_in": L.dense_init(ks[0], F // S, cfg.d_model, jnp.float32),
            "stage": TF.stage_init(ks[1], cfg, cfg.stages[0]),
            "final_norm": L.rmsnorm_init(cfg.d_model, jnp.float32),
            "head_w": L.dense_init(ks[2], cfg.d_model, NUM_CLASSES,
                                   jnp.float32),
            "head_b": jnp.zeros(NUM_CLASSES),
        }

    def apply(self, params, X):
        cfg = self.cfg
        B, S = X.shape[0], self.seq_len
        x = X.reshape(B, S, -1) @ params["w_in"]
        positions = jnp.broadcast_to(jnp.arange(S), (B, S))

        def block(h, rep):
            # pre-norm attention + residual, with the normalization and
            # the attention itself on the kernel dispatch path
            a = rep["pos0"]["attn"]
            hn = rmsnorm_op(h, a["norm"]["scale"], cfg.norm_eps)
            q, k, v = A._project_qkv(a, hn, cfg, positions)
            o = flash_attention_bshd(q, k, v, causal=True, bq=S, bk=S)
            h = h + o.reshape(B, S, -1) @ a["wo"]
            f = rep["pos0"]["ffn"]
            hn = rmsnorm_op(h, f["norm"]["scale"], cfg.norm_eps)
            h = h + L.mlp_apply(f["mlp"], hn, cfg.mlp_act)
            return h, None

        x, _ = jax.lax.scan(block, x, params["stage"])
        x = rmsnorm_op(x, params["final_norm"]["scale"], cfg.norm_eps)
        return x[:, -1, :] @ params["head_w"] + params["head_b"]
