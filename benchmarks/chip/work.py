"""Operations and bytes of the programs the window drives, from shapes.

Model FLOPs count multiply-adds as two operations, at the true token counts
(no bucket padding), with causal attention counted over the keys each query
reads.  The embedding lookup and the norms, rotary embeddings, softmax and
elementwise work are left out: under 1% of a layer's matrix work at these
widths.  Kernel counts are of the rows the kernel processes, as its HLO
instruction in the trace gives them; its bytes come from the same
instruction (``devtrace.kernel_shapes``).

The model counts are the architecture's (``arch/<model_type>.py``); the
wire kernels' counts are shared.
"""
from __future__ import annotations

import spec


def _arch(m):
    return spec.arch_module(m.arch)


def layer_params(m) -> int:
    """Weights of one layer's matrix multiplications, where every layer is
    alike."""
    return _arch(m).layer_params(m)


def edge_prefill(m, seq: int) -> int:
    """Layers [0, split) over ``seq`` tokens and the reduction."""
    return _arch(m).edge_prefill(m, seq)


def cloud_prefill(m, seq: int) -> int:
    """Restoration, layers [split, L) over ``seq`` tokens and the head at the
    last position."""
    return _arch(m).cloud_prefill(m, seq)


def edge_step(m, pos: int) -> int:
    """One streamed token on the edge at position ``pos``: layers
    [0, split) and the reduction."""
    return _arch(m).edge_step(m, pos)


def cloud_step(m, pos: int) -> int:
    """One streamed token on the cloud: restoration, layers [split, L), head."""
    return _arch(m).cloud_step(m, pos)


def decode_token(m, pos: int) -> int:
    """One token of the batched decode step: the whole model with the wire."""
    return _arch(m).decode_token(m, pos)


def reduce_quant(rows: int, d: int, d_r: int) -> int:
    """FLOPs of the reduce+quant kernel: an (rows, d) x (d, d_r) product;
    the absmax and rounding epilogue is left out."""
    return 2 * rows * d * d_r


def dequant_restore(rows: int, d_r: int, d: int) -> int:
    """FLOPs of the dequant+restore kernel: the scaling of the codes and an
    (rows, d_r) x (d_r, d) product."""
    return 2 * rows * d_r * d + rows * d_r
