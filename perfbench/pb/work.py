"""Work the algorithm needs, counted from shapes and real lengths.

Every count here depends on the model's published shapes and on the
lengths the lanes really held: never on a kernel's tile, split degree or
the width of the page table.  Any implementation of the same work is read
against the same yardstick, so a share of a roofline or of a peak computed
from these counts cannot pass 100% unless the timing leaves out work.
"""
from __future__ import annotations

import json
import os
from dataclasses import dataclass

PEAKS_FILE = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "peaks.json")


@dataclass(frozen=True)
class Shapes:
    """The sizes of a dense decoder that the counts need."""

    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    kv_bytes: int = 2          # bytes per cached key or value element
    act_bytes: int = 2         # bytes per activation element

    @classmethod
    def from_config(cls, c: dict) -> "Shapes":
        heads = c["num_attention_heads"]
        return cls(layers=c["num_hidden_layers"], d_model=c["hidden_size"],
                   heads=heads, kv_heads=c["num_key_value_heads"],
                   head_dim=c.get("head_dim",
                                  c["hidden_size"] // heads),
                   d_ff=c["intermediate_size"], vocab=c["vocab_size"])

    @property
    def layer_matmul_params(self) -> int:
        d, hd = self.d_model, self.head_dim
        attn = d * self.heads * hd * 2 + d * self.kv_heads * hd * 2
        return attn + 3 * d * self.d_ff


class PeakMissing(KeyError):
    """The device kind has no row in the peaks table."""


def peaks(device_kind: str, path: str = PEAKS_FILE) -> dict:
    """The published peaks of one chip of ``device_kind``.  A kind that is
    not in the table is an error, never a default."""
    with open(path) as f:
        table = json.load(f)
    if device_kind not in table:
        raise PeakMissing(f"no peaks for device kind {device_kind!r} in "
                          f"{path}; known: {sorted(table)}")
    return table[device_kind]


# -- attention kernels ---------------------------------------------------------

def decode_attn(s: Shapes, kv_lens) -> tuple[float, float]:
    """FLOPs and bytes of paged decode attention over all layers for lanes
    whose contexts (the new token included) are ``kv_lens``: per lane,
    ``q k^T`` and ``p v`` over its real context, the keys and values of that
    context read once, the query read and the output written."""
    flops = bytes_ = 0.0
    for n in kv_lens:
        flops += 4.0 * s.heads * s.head_dim * n
        bytes_ += (2.0 * s.kv_heads * n * s.head_dim * s.kv_bytes
                   + 2.0 * s.heads * s.head_dim * s.act_bytes)
    return flops * s.layers, bytes_ * s.layers


def prefill_attn(s: Shapes, chunks) -> tuple[float, float]:
    """FLOPs and bytes of paged chunked-prefill attention over all layers.
    ``chunks`` holds ``(start, end)``: the chunk's valid tokens sit at
    positions ``start .. end - 1`` and attend causally to everything before
    them, so token ``i`` reads ``i + 1`` keys."""
    flops = bytes_ = 0.0
    for start, end in chunks:
        n = end - start
        keys = n * start + n * (n + 1) / 2.0
        flops += 4.0 * s.heads * s.head_dim * keys
        bytes_ += (2.0 * s.kv_heads * end * s.head_dim * s.kv_bytes
                   + 2.0 * s.heads * n * s.head_dim * s.act_bytes)
    return flops * s.layers, bytes_ * s.layers


def roofline_s(flops: float, bytes_: float, peak: dict) -> float:
    """The least time one chip needs for the work: the larger of its
    operations over peak FLOP/s and its bytes over peak bandwidth."""
    return max(flops / peak["bf16_flops"], bytes_ / peak["hbm_bytes_per_s"])


# -- whole model ---------------------------------------------------------------

def token_flops(s: Shapes, context: int, logits: bool) -> float:
    """Model FLOPs of one token at position ``context - 1``: every matmul
    of every layer, attention over its ``context`` keys, and the output
    head where the token's logits are needed."""
    f = 2.0 * s.layer_matmul_params * s.layers
    f += 4.0 * s.heads * s.head_dim * context * s.layers
    if logits:
        f += 2.0 * s.d_model * s.vocab
    return f


def decode_flops(s: Shapes, kv_lens) -> float:
    """Model FLOPs of one decode step for lanes with contexts ``kv_lens``."""
    return sum(token_flops(s, n, True) for n in kv_lens)


def prefill_flops(s: Shapes, start: int, end: int, final: bool) -> float:
    """Model FLOPs of one prefill chunk of positions ``start .. end - 1``;
    the output head is needed for the prompt's last token only."""
    n = end - start
    f = 2.0 * s.layer_matmul_params * s.layers * n
    f += 4.0 * s.heads * s.head_dim * s.layers * (n * start + n * (n + 1) / 2.0)
    if final:
        f += 2.0 * s.d_model * s.vocab
    return f
