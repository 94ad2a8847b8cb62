"""Jit'd public wrappers for the Pallas kernels.

The platform alone picks the mode: on a TPU the kernels compile for the
chip, on the CPU backend they run in Pallas interpret mode.  The model
layers only route here when ``attn_impl='pallas'`` or masked compute is
requested.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.decode_attention import decode_attention as _decode
from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.masked_matmul import masked_matmul as _masked_mm
from repro.kernels.ssd_scan import ssd_scan as _ssd


def _interpret() -> bool:
    return jax.default_backend() == "cpu"


def flash_attention(q, k, v, *, causal=True, window=None,
                    block_q=256, block_k=256):
    sq, skv = q.shape[1], k.shape[1]
    bq = min(block_q, sq)
    bk = min(block_k, skv)
    if sq % bq or skv % bk:
        return ref.flash_attention_ref(q, k, v, causal=causal, window=window)
    return _flash(q, k, v, causal=causal, window=window, block_q=bq,
                  block_k=bk, interpret=_interpret())


def decode_attention(q, k, v, lengths=None, *, block_k=512):
    s = k.shape[1]
    bk = min(block_k, s)
    if s % bk:
        return ref.decode_attention_ref(q, k, v, lengths)
    return _decode(q, k, v, lengths, block_k=bk, interpret=_interpret())


def ssd_scan(x, bmat, cmat, dt, a_log, d, dt_bias, *, chunk=128):
    s = x.shape[1]
    ch = min(chunk, s)
    if s % ch:
        return ref.ssd_scan_ref(x, bmat, cmat, dt, a_log, d, dt_bias)
    return _ssd(x, bmat, cmat, dt, a_log, d, dt_bias, chunk=ch,
                interpret=_interpret())


def masked_matmul(x, w, block_mask, *, block_n=128):
    return _masked_mm(x, w, block_mask, block_n=block_n,
                      interpret=_interpret())
