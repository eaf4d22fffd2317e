"""Public jit'd entry points for the Pallas kernels.

Models call these, never pallas_call directly. The platform picks the
path: on a TPU each op runs its Pallas kernel when shapes are
block-compatible, and the pure-jnp oracle otherwise; on any other backend
every op runs the oracle (Pallas interpret mode is correct but slow). So
the same model code runs in CPU tests and in TPU production, and the
kernels' interpret-mode tests call them directly.
"""
from __future__ import annotations

import jax

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.lstm_cell import lstm_cell
from repro.kernels.mlstm_chunk import mlstm_chunk
from repro.kernels.ssm_scan import ssm_scan

__all__ = ["attention", "lstm_step", "ssm", "mlstm", "flash_attention",
           "lstm_cell", "ssm_scan", "mlstm_chunk"]


def _pallas_enabled() -> bool:
    return jax.default_backend() == "tpu"


def attention(q, k, v, *, causal=True, window=None, softcap=None,
              scale=None, block_q=128, block_k=128):
    lq, lk, d = q.shape[-2], k.shape[-2], q.shape[-1]
    blockable = (lq % min(block_q, lq) == 0 and lk % min(block_k, lk) == 0)
    if _pallas_enabled() and blockable:
        return flash_attention(q, k, v, causal=causal, window=window,
                               softcap=softcap, scale=scale,
                               block_q=block_q, block_k=block_k)
    if lq >= 1024:  # production shapes: block-wise, memory-bounded path
        return ref.attention_blockwise(q, k, v, causal=causal, window=window,
                                       softcap=softcap, scale=scale)
    return ref.attention_reference(q, k, v, causal=causal, window=window,
                                   softcap=softcap, scale=scale)


def lstm_step(x, h, c, wx, wh, b):
    """wx: (I, 4, H); wh: (H, 4, H); b: (4, H)."""
    if _pallas_enabled():
        return lstm_cell(x, h, c, wx, wh, b)
    i_dim, _, h_dim = wx.shape
    return ref.lstm_cell_reference(x, h, c, wx.reshape(i_dim, 4 * h_dim),
                                   wh.reshape(h_dim, 4 * h_dim),
                                   b.reshape(4 * h_dim))


def ssm(x, dt, a, b, c, d, *, chunk=256, block_h=8):
    l, h = x.shape[1], x.shape[2]
    t = min(chunk, l)
    blockable = l % t == 0 and h % min(block_h, h) == 0
    if _pallas_enabled() and blockable:
        return ssm_scan(x, dt, a, b, c, d, chunk=chunk, block_h=block_h)
    return ref.ssm_scan_reference(x, dt, a, b, c, d)


def mlstm(q, k, v, i_gate, f_gate, *, chunk=64, block_h=4):
    """Returns (y, (C, n, m) final state)."""
    l, h = q.shape[1], q.shape[2]
    t = min(chunk, l)
    blockable = l % t == 0 and h % min(block_h, h) == 0
    if _pallas_enabled() and blockable:
        return mlstm_chunk(q, k, v, i_gate, f_gate, chunk=chunk,
                           block_h=block_h)
    if l >= 256:   # chunkwise jnp path: O(L/chunk) saved state, trainable
        return ref.mlstm_chunk_jnp(q, k, v, i_gate, f_gate, chunk=256)
    return ref.mlstm_chunk_reference(q, k, v, i_gate, f_gate)
