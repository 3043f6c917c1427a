"""Operations and bytes the algorithms *require*, from shapes alone, and
the chip's published peaks.

These are the yardstick's own counts, not the compiler's: recomputation,
padding and whatever else a compiled program does beyond the mathematics
is not counted, so a share of peak computed from them can only be
flattered by doing less work than the algorithm needs.

Conventions: one multiply-accumulate is two operations; a backward pass
costs twice its forward pass (one product for the input's gradient, one
for the weight's), so forward plus backward is three forwards; only
matrix products and convolutions are counted (normalisation, activation
functions, softmax and the optimizer are not).
"""

from __future__ import annotations

import json
import os

PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "peaks.json")


def peaks(device_kind: str, table_file: str = PEAKS_FILE) -> dict:
    """The published peaks of ``device_kind``; an unknown kind is an
    error, never a default."""
    with open(table_file) as f:
        table = json.load(f)
    if device_kind not in table or device_kind.startswith("_"):
        raise KeyError(
            f"device kind {device_kind!r} has no row in {table_file} "
            f"(rows: {[k for k in table if not k.startswith('_')]})")
    return table[device_kind]


# -- ResNet (He et al. 2015, Table 1; torchvision's v1.5 strides) ----------

_RESNET_STAGES = {50: (3, 4, 6, 3), 101: (3, 4, 23, 3), 152: (3, 8, 36, 3)}


def resnet_forward_macs(depth: int = 50, image_size: int = 224,
                        num_classes: int = 1000, channels: int = 3) -> int:
    """Multiply-accumulates of one image's forward pass through a
    bottleneck ResNet: every convolution and the classifier."""
    def conv(h_out, k, c_in, c_out):
        return h_out * h_out * k * k * c_in * c_out

    h = image_size // 2                       # 7x7/2 stem
    macs = conv(h, 7, channels, 64)
    h //= 2                                   # 3x3/2 max pool
    c_in = 64
    for stage, blocks in enumerate(_RESNET_STAGES[depth]):
        width = 64 * 2 ** stage
        for block in range(blocks):
            stride = 2 if (stage > 0 and block == 0) else 1
            h_out = h // stride
            macs += conv(h, 1, c_in, width)              # 1x1 reduce
            macs += conv(h_out, 3, width, width)         # 3x3 (strided)
            macs += conv(h_out, 1, width, 4 * width)     # 1x1 expand
            if block == 0:                               # projection
                macs += conv(h_out, 1, c_in, 4 * width)
            c_in, h = 4 * width, h_out
    return macs + c_in * num_classes


def resnet_train_flops(images: int, **shape) -> float:
    """Operations one training step over ``images`` images requires."""
    return 3.0 * 2.0 * resnet_forward_macs(**shape) * images


# -- decoder-only transformer (models/transformer.py's dense block) --------

def _causal_pairs(t: int) -> float:
    return t * (t + 1) / 2.0


def lm_forward_flops_per_sequence(*, n_layers: int, d_model: int, d_ff: int,
                                  vocab: int, seq_len: int,
                                  causal: bool = True) -> float:
    """Operations of one sequence's forward pass: q, k, v, o projections,
    the two MLP products, attention's two products over the (causal)
    pairs, and the output head.  The embedding is a lookup."""
    t = seq_len
    pairs = _causal_pairs(t) if causal else float(t * t)
    per_layer = (2.0 * t * (4 * d_model * d_model + 2 * d_model * d_ff)
                 + 2.0 * 2.0 * pairs * d_model)
    return n_layers * per_layer + 2.0 * t * d_model * vocab


def lm_train_flops(sequences: int, **shape) -> float:
    return 3.0 * lm_forward_flops_per_sequence(**shape) * sequences


# -- flash attention, forward and backward ---------------------------------

def flash_flops(*, batch: int, heads: int, seq_len: int, head_dim: int,
                causal: bool = True) -> dict:
    """Required operations of attention's forward (QK^T, PV) and backward
    (the scores once more, dV, dP, dQ, dK: five products), over the pairs
    a causal mask keeps.  The kernels' own split of the backward into two
    programs recomputes more than this; that is theirs to pay."""
    pairs = _causal_pairs(seq_len) if causal else float(seq_len) ** 2
    one_product = 2.0 * batch * heads * pairs * head_dim
    return {"forward": 2 * one_product, "backward": 5 * one_product}


def flash_bytes(*, batch: int, heads: int, seq_len: int, head_dim: int,
                itemsize: int = 2) -> dict:
    """Least HBM traffic: forward reads Q, K, V and writes O; backward
    reads Q, K, V, O, dO and writes dQ, dK, dV (the [B,H,T] softmax
    statistics are under one percent and left out)."""
    tensor = batch * heads * seq_len * head_dim * itemsize
    return {"forward": 4.0 * tensor, "backward": 8.0 * tensor}


def roofline_seconds(flops: float, nbytes: float, peak: dict) -> dict:
    """Least time for ``flops`` and ``nbytes`` on a chip with ``peak``,
    and which of the two bounds it."""
    compute = flops / peak["bf16_flops_per_s"]
    memory = nbytes / peak["hbm_bytes_per_s"]
    return {"seconds": max(compute, memory),
            "bound": "compute" if compute >= memory else "memory"}
