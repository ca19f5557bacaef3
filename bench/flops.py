"""Operations and bytes from shapes, for the payload and the kernels.

A multiply-add counts as 2 operations. Only the work the algorithm needs
is counted: causal attention scores and values over the S(S+1)/2 pairs
at or below the diagonal, the head on the last position alone, the
backward pass at twice the forward. Bytes are those a kernel must move
through HBM at least once: its inputs read and its outputs written, in
float32.
"""
from __future__ import annotations

from dataclasses import dataclass

NUM_CLASSES = 62
F32 = 4


@dataclass(frozen=True)
class Payload:
    """The transformer payload's shapes: a feature vector of `features`
    values read as `seq` tokens of `features // seq`."""
    features: int
    seq: int
    d_model: int
    layers: int
    heads: int
    kv_heads: int
    d_ff: int
    classes: int = NUM_CLASSES

    @property
    def head_dim(self) -> int:
        return self.d_model // self.heads

    @classmethod
    def from_config(cls, cfg: dict) -> "Payload":
        p = cfg["payload"]["params"]
        return cls(features=cfg["dataset"]["feature_dim"], seq=p["seq_len"],
                   d_model=p["d_model"], layers=p["num_layers"],
                   heads=p["num_heads"], kv_heads=p["num_kv_heads"],
                   d_ff=p["d_ff"])

    def attention_pairs(self) -> int:
        return self.seq * (self.seq + 1) // 2

    def forward_flops(self) -> int:
        """One sample's forward pass."""
        S, d, hd = self.seq, self.d_model, self.head_dim
        H, K, f = self.heads, self.kv_heads, self.d_ff
        embed = 2 * S * (self.features // S) * d
        proj = 2 * S * d * (H + 2 * K) * hd + 2 * S * H * hd * d
        attn = 2 * 2 * self.attention_pairs() * H * hd
        ffn = 3 * 2 * S * d * f
        head = 2 * d * self.classes
        return embed + self.layers * (proj + attn + ffn) + head

    def train_flops(self, samples: int) -> int:
        """Forward and backward over `samples` samples."""
        return 3 * self.forward_flops() * samples

    def flash_forward(self, batch: int) -> tuple:
        """(flops, bytes) of one flash-attention forward call on `batch`
        sequences: q and the output at H heads, k and v at K heads."""
        S, hd, H, K = self.seq, self.head_dim, self.heads, self.kv_heads
        flops = 2 * 2 * self.attention_pairs() * H * hd * batch
        nbytes = F32 * batch * S * hd * (2 * H + 2 * K)
        return flops, nbytes


def agg_call(m: int, n: int) -> tuple:
    """(flops, bytes) of one aggregation-kernel call: an (m, n) update
    stack weighted and summed onto n parameters. It reads the stack, the
    parameters and the m weights and writes n outputs."""
    return 2 * m * n, F32 * (m * n + 2 * n + m)


def agg_event(m: int, leaf_sizes) -> tuple:
    """(flops, bytes) of one aggregation over a tree of leaves: one kernel
    call per leaf."""
    fl = by = 0
    for n in leaf_sizes:
        a, b = agg_call(m, n)
        fl, by = fl + a, by + b
    return fl, by


def roofline_share(flops: float, nbytes: float, seconds: float,
                   peak_flops: float, peak_bw: float) -> tuple:
    """(share in %, bound): the least time the chip could take, the larger
    of flops over peak and bytes over bandwidth, over the time taken."""
    t_compute, t_memory = flops / peak_flops, nbytes / peak_bw
    bound = "compute" if t_compute >= t_memory else "memory"
    return 100.0 * max(t_compute, t_memory) / seconds, bound
