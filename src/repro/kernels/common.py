"""Kernel dispatch policy.

Every kernel ships three execution paths:
  - "pallas":    pl.pallas_call lowered for TPU (the TARGET).
  - "interpret": same kernel body, interpret=True — executes on CPU for
                 correctness validation (used by the kernel test suites).
  - "ref":       the pure-jnp oracle from ref.py — the default on CPU hosts
                 (fast XLA path; also what the dry-run lowers so roofline
                 terms reflect the jnp compute graph).

Select globally with REPRO_KERNEL_MODE in {auto, pallas, interpret, ref};
"auto" = pallas on TPU backends, ref elsewhere; the int8 scans follow
the same policy. The IVF member scan (ivf_scan.py) is one plain-XLA
program with no mode: a CPU host runs the program the chip runs.
"""
from __future__ import annotations

import os

import jax
import numpy as np

from .. import obs


def kernel_mode(override: str | None = None) -> str:
    mode = override or os.environ.get("REPRO_KERNEL_MODE", "auto")
    if mode == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "ref"
    if mode not in ("pallas", "interpret", "ref"):
        raise ValueError(f"bad kernel mode {mode!r}")
    return mode


def to_device(*args):
    """``(array, dtype)`` pairs as device arrays, converted under an
    ``h2d`` span whose counter ``h2d_bytes`` sums the nbytes of the host
    arrays handed to the device. An argument that is already a device
    array (or a tracer) is converted there and counts nothing."""
    import jax.numpy as jnp
    with obs.span("h2d") as sp:
        out = []
        for x, dtype in args:
            if isinstance(x, jax.Array):
                out.append(jnp.asarray(x, dtype))
                continue
            x = np.asarray(x, dtype)
            sp.add("h2d_bytes", int(x.nbytes))
            out.append(jnp.asarray(x))
        return out


def to_host(*outs) -> tuple:
    """A kernel's outputs as host arrays, under a ``device_wait`` span:
    an explicit block on the device, then the copy back. The span is the
    time the host waits on the device and on the results' return."""
    with obs.span("device_wait"):
        jax.block_until_ready(outs)
        return tuple(np.asarray(o) for o in outs)


def pad_to(x, axis: int, multiple: int, value=0):
    """Pad one axis up to a multiple (static shapes for BlockSpec grids)."""
    import jax.numpy as jnp
    n = x.shape[axis]
    rem = (-n) % multiple
    if rem == 0:
        return x, n
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, rem)
    return jnp.pad(x, widths, constant_values=value), n


def block_topk(scores, k: int, idx_base):
    """Top-k of each row of a (Q, bn) score block inside a kernel body.

    k max passes (VPU reductions), rolled into a fori_loop so the lowered
    graph stays O(1) in k. The k winners are kept in (Q, k) registers and
    returned whole, so the caller stores each output block once: a store
    at a dynamic lane offset inside the loop is refused by the TPU
    compiler. Ties go to the lowest column, as ``jnp.argmax`` would.
    Returns ((Q, k) scores, (Q, k) int32 row ids offset by ``idx_base``).
    """
    import jax.numpy as jnp
    nq, bn = scores.shape
    cols = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
    slot = jax.lax.broadcasted_iota(jnp.int32, (nq, k), 1)

    def body(t, carry):
        s, top_s, top_i = carry
        best = jnp.max(s, axis=1, keepdims=True)                   # (Q, 1)
        arg = jnp.min(jnp.where(s == best, cols, bn), axis=1,
                      keepdims=True)                               # (Q, 1)
        top_s = jnp.where(slot == t, best, top_s)
        top_i = jnp.where(slot == t, arg + idx_base, top_i)
        return jnp.where(cols == arg, -jnp.inf, s), top_s, top_i

    init = (scores, jnp.full((nq, k), -jnp.inf, jnp.float32),
            jnp.zeros((nq, k), jnp.int32))
    _, top_s, top_i = jax.lax.fori_loop(0, k, body, init)
    return top_s, top_i


def merge_blocks(s_blk, i_blk, k: int):
    """Global merge of per-block candidates: (nblocks, Q, k) -> (Q, k)."""
    import jax.numpy as jnp
    nb, nq, kb = s_blk.shape
    s_all = jnp.transpose(s_blk, (1, 0, 2)).reshape(nq, nb * kb)
    i_all = jnp.transpose(i_blk, (1, 0, 2)).reshape(nq, nb * kb)
    top_s, pos = jax.lax.top_k(s_all, k)
    return top_s, jnp.take_along_axis(i_all, pos, axis=1)


def row_block(n: int, bn: int) -> int:
    """Corpus rows per grid step: ``bn``, or fewer for a small corpus,
    always a multiple of 128 so every block is lane-aligned on the chip
    (the wrapper pads the corpus to a whole number of blocks)."""
    return min(bn, -(-max(n, 1) // 128) * 128)


def split_i64(x):
    """Split non-negative int64 (numpy, host-side) into (hi:int32,
    lo:uint32) device arrays — TPUs are 32-bit machines and JAX x64 is off;
    lexicographic compare on (hi, lo) is exact for timestamps."""
    x = np.asarray(x, np.int64)
    hi = (x >> 32).astype(np.int32)
    lo = (x & np.int64(0xFFFFFFFF)).astype(np.uint32)
    return hi, lo


def le_i64(a_hi, a_lo, b_hi, b_lo):
    """(a <= b) for split int64 pairs, elementwise (jnp)."""
    return (a_hi < b_hi) | ((a_hi == b_hi) & (a_lo <= b_lo))


def lt_i64(a_hi, a_lo, b_hi, b_lo):
    return (a_hi < b_hi) | ((a_hi == b_hi) & (a_lo < b_lo))
