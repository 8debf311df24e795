"""Pallas TPU kernels: fused dense+bias+ReLU and the whole-MLP megakernel
(the GAN's MLP hot-spot, forward AND backward).

The GANDSE G/D networks are deep ReLU MLPs (11-14 layers x 2048); on TPU
the hot loop is `y = relu(x @ w + b)` repeated per layer.  Three kernels
cover it:

- ``fused_dense`` — one layer, bias+ReLU fused into the matmul epilogue.
  Differentiable: a ``custom_vjp`` backs it with Pallas backward kernels
  (dx = g @ Wᵀ, dW = xᵀ @ g, db = Σ_M g, where g = dy·[y > 0] folds the
  ReLU mask into the same accumulate-in-VMEM tiling as the forward), so
  Algorithm 1's jitted/scanned train step runs fused end to end.
- ``fused_mlp`` — the layer-chained forward megakernel for inference-only
  paths: the hidden activations ping-pong between two VMEM scratch
  buffers across the layer grid axis instead of round-tripping through
  HBM once per layer.  Also differentiable (its VJP re-runs the layer
  chain through ``fused_dense``'s kernels).

Tiling (shared by forward and backward): grid (rows/bm, cols/bn, red/bk)
with the reduction axis innermost (sequential), accumulating into a VMEM
f32 scratch tile; on the last reduction step the epilogue (bias+ReLU, or
the output cast) runs and the tile is written once.  VMEM working set =
2 * (bm*bk + bk*bn + bm*bn + bn) floats for the double-buffered blocks
plus the bm*bn accumulator: 4.5 MiB for the default (256, 512, 512) f32
tiles, well inside the default scoped VMEM limit, and MXU-aligned.
Biases travel as
(1, N) rows with (1, bn) blocks: TPU blocks need their last two dims
divisible by (8, 128) or equal to the array's, and 1-D blocks have no
layout Mosaic and XLA agree on.
Operands whose dims do not divide the block are zero-padded up to the
block multiple (and outputs sliced back), so a prime/odd dim can never
force a whole-dim block past the VMEM budget.
"""
from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_BM = 256
DEFAULT_BK = 512
DEFAULT_BN = 512


def _pick(block: int, dim: int) -> int:
    """Block size for `dim`: the requested block, shrunk to the next power
    of two >= dim when the dim is smaller.  Never returns `dim` itself for
    an awkward (prime/odd) dim — the operand is zero-padded up to a block
    multiple instead, so the VMEM working set is bounded by the requested
    block size, not by the shape."""
    return min(block, max(8, 1 << (max(int(dim), 1) - 1).bit_length()))


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _pad2(a: jnp.ndarray, rows: int, cols: int) -> jnp.ndarray:
    pr, pc = rows - a.shape[0], cols - a.shape[1]
    return jnp.pad(a, ((0, pr), (0, pc))) if pr or pc else a


def _bias_row(b: jnp.ndarray, n: int) -> jnp.ndarray:
    """(N,) bias -> zero-padded (1, n) row.  Biases travel as 2-D rows: a
    1-D block has no legal TPU tiling (Mosaic's 1-D layout differs from
    XLA's), while a (1, bn) block is legal whenever bn is a multiple of
    128 or the whole padded width."""
    return _pad2(b.reshape(1, -1), 1, n)


# ---------------------------------------------------------------------------
# forward: y = [relu](x @ w + b)
# ---------------------------------------------------------------------------
def _fused_dense_kernel(x_ref, w_ref, b_ref, o_ref, acc_ref, *, n_k: int, relu: bool):
    k_step = pl.program_id(2)

    @pl.when(k_step == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        x_ref[...].astype(jnp.float32),
        w_ref[...].astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )

    @pl.when(k_step == n_k - 1)
    def _epilogue():
        y = acc_ref[...] + b_ref[...].astype(jnp.float32)
        if relu:
            y = jnp.maximum(y, 0.0)
        o_ref[...] = y.astype(o_ref.dtype)


def _forward(x, w, b, *, relu: bool, bm: int, bk: int, bn: int, interpret: bool):
    m, k = x.shape
    k2, n = w.shape
    assert k == k2 and b.shape == (n,)
    bm, bk, bn = _pick(bm, m), _pick(bk, k), _pick(bn, n)
    mp, kp, np_ = _round_up(m, bm), _round_up(k, bk), _round_up(n, bn)
    xp, wp, bp = _pad2(x, mp, kp), _pad2(w, kp, np_), _bias_row(b, np_)
    n_k = kp // bk

    grid = (mp // bm, np_ // bn, n_k)
    y = pl.pallas_call(
        functools.partial(_fused_dense_kernel, n_k=n_k, relu=relu),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(xp, wp, bp)
    return y[:m, :n] if (mp, np_) != (m, n) else y


# ---------------------------------------------------------------------------
# backward: dx = g @ wᵀ, dw = xᵀ @ g, db = Σ_M g  (g = dy·[y > 0])
# ---------------------------------------------------------------------------
def _dx_kernel(dy_ref, y_ref, w_ref, o_ref, acc_ref, *, n_n: int, relu: bool):
    n_step = pl.program_id(2)

    @pl.when(n_step == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    g = dy_ref[...].astype(jnp.float32)
    if relu:
        g = g * (y_ref[...].astype(jnp.float32) > 0.0)
    # (bm, bn) x (bk, bn) contracted over the shared N axis -> (bm, bk)
    acc_ref[...] += jax.lax.dot_general(
        g, w_ref[...].astype(jnp.float32),
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(n_step == n_n - 1)
    def _epilogue():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _dw_db_kernel(x_ref, dy_ref, y_ref, dw_ref, db_ref, accw_ref, accb_ref,
                  *, n_m: int, relu: bool):
    k_blk = pl.program_id(1)
    m_step = pl.program_id(2)

    @pl.when(m_step == 0)
    def _init_w():
        accw_ref[...] = jnp.zeros_like(accw_ref)

    @pl.when((m_step == 0) & (k_blk == 0))
    def _init_b():
        accb_ref[...] = jnp.zeros_like(accb_ref)

    g = dy_ref[...].astype(jnp.float32)
    if relu:
        g = g * (y_ref[...].astype(jnp.float32) > 0.0)
    # (bm, bk) x (bm, bn) contracted over the shared M axis -> (bk, bn)
    accw_ref[...] += jax.lax.dot_general(
        x_ref[...].astype(jnp.float32), g,
        dimension_numbers=(((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    # db needs one full M sweep; take the k_blk == 0 sweep (g is identical
    # across k blocks) and let the scratch carry the sum to the write below
    @pl.when(k_blk == 0)
    def _acc_b():
        accb_ref[...] += jnp.sum(g, axis=0, keepdims=True)

    @pl.when(m_step == n_m - 1)
    def _epilogue():
        dw_ref[...] = accw_ref[...].astype(dw_ref.dtype)
        db_ref[...] = accb_ref[...].astype(db_ref.dtype)


def _backward(x, w, dy, y, *, relu: bool, bm: int, bk: int, bn: int,
              interpret: bool):
    m, k = x.shape
    _, n = w.shape
    bm, bk, bn = _pick(bm, m), _pick(bk, k), _pick(bn, n)
    mp, kp, np_ = _round_up(m, bm), _round_up(k, bk), _round_up(n, bn)
    xp, wp = _pad2(x, mp, kp), _pad2(w, kp, np_)
    dyp, yp = _pad2(dy, mp, np_), _pad2(y, mp, np_)
    n_m, n_k, n_n = mp // bm, kp // bk, np_ // bn

    dx = pl.pallas_call(
        functools.partial(_dx_kernel, n_n=n_n, relu=relu),
        grid=(n_m, n_k, n_n),
        in_specs=[
            pl.BlockSpec((bm, bn), lambda i, kk, j: (i, j)),
            pl.BlockSpec((bm, bn), lambda i, kk, j: (i, j)),
            pl.BlockSpec((bk, bn), lambda i, kk, j: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bk), lambda i, kk, j: (i, kk)),
        out_shape=jax.ShapeDtypeStruct((mp, kp), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bk), jnp.float32)],
        interpret=interpret,
    )(dyp, yp, wp)

    dw, db = pl.pallas_call(
        functools.partial(_dw_db_kernel, n_m=n_m, relu=relu),
        grid=(n_n, n_k, n_m),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda j, kk, mm: (mm, kk)),
            pl.BlockSpec((bm, bn), lambda j, kk, mm: (mm, j)),
            pl.BlockSpec((bm, bn), lambda j, kk, mm: (mm, j)),
        ],
        out_specs=[
            pl.BlockSpec((bk, bn), lambda j, kk, mm: (kk, j)),
            pl.BlockSpec((1, bn), lambda j, kk, mm: (0, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((kp, np_), w.dtype),
            jax.ShapeDtypeStruct((1, np_), dy.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, bn), jnp.float32),
            pltpu.VMEM((1, bn), jnp.float32),
        ],
        interpret=interpret,
    )(xp, dyp, yp)

    return dx[:m, :k], dw[:k, :n], db[0, :n]


@functools.lru_cache(maxsize=None)
def _fused_dense_vjp(relu: bool, bm: int, bk: int, bn: int, interpret: bool):
    """custom_vjp'd (x, w, b) -> y closure over the static kernel config.

    Residuals are (x, w, y): the ReLU mask is recomputed from the saved
    output (y > 0), so the backward never re-runs the forward matmul.
    """

    @jax.custom_vjp
    def fd(x, w, b):
        return _forward(x, w, b, relu=relu, bm=bm, bk=bk, bn=bn,
                        interpret=interpret)

    def fwd(x, w, b):
        y = fd(x, w, b)
        return y, (x, w, y)

    def bwd(res, dy):
        x, w, y = res
        dx, dw, db = _backward(x, w, dy, y, relu=relu, bm=bm, bk=bk, bn=bn,
                               interpret=interpret)
        return dx, dw.astype(w.dtype), db.astype(x.dtype)

    fd.defvjp(fwd, bwd)
    return fd


@functools.partial(jax.jit, static_argnames=("relu", "bm", "bk", "bn", "interpret"))
def fused_dense(
    x: jnp.ndarray,                 # (M, K)
    w: jnp.ndarray,                 # (K, N)
    b: jnp.ndarray,                 # (N,)
    *,
    relu: bool = True,
    bm: int = DEFAULT_BM,
    bk: int = DEFAULT_BK,
    bn: int = DEFAULT_BN,
    interpret: bool = False,
) -> jnp.ndarray:
    """[relu](x @ w + b), differentiable (Pallas forward AND backward)."""
    return _fused_dense_vjp(relu, bm, bk, bn, interpret)(x, w, b)


# ---------------------------------------------------------------------------
# whole-MLP layer-chained forward megakernel
# ---------------------------------------------------------------------------
def _mlp_kernel(x_ref, w_ref, b_ref, o_ref, h0_ref, h1_ref, *, n_layers: int):
    l = pl.program_id(1)
    j = pl.program_id(2)
    bn = o_ref.shape[-1]

    parity = jax.lax.rem(l, 2)
    # activations ping-pong between the two VMEM buffers; layer 0 reads the
    # HBM input block instead (the h buffers are uninitialized then — the
    # where() discards them)
    h_prev = jnp.where(parity == 0, h0_ref[...], h1_ref[...])
    h_in = jnp.where(l == 0, x_ref[...].astype(jnp.float32), h_prev)

    y = jnp.dot(h_in, w_ref[0].astype(jnp.float32),
                preferred_element_type=jnp.float32)
    y = y + b_ref[0].astype(jnp.float32)
    y = jnp.where(l == n_layers - 1, y, jnp.maximum(y, 0.0))

    col = pl.multiple_of(j * bn, bn)

    @pl.when(parity == 0)
    def _to_h1():
        h1_ref[:, pl.ds(col, bn)] = y

    @pl.when(parity == 1)
    def _to_h0():
        h0_ref[:, pl.ds(col, bn)] = y

    @pl.when(l == n_layers - 1)
    def _out():
        o_ref[...] = y.astype(o_ref.dtype)


def _mlp_forward(x, ws, bs, *, bm: int, bn: int, interpret: bool):
    m, d_in = x.shape
    d_out = ws[-1].shape[1]
    n_layers = len(ws)
    dims = {d_in, d_out}
    for w in ws:
        dims.update(w.shape)
    h = max(dims)
    bn = _pick(bn, h)
    bm = _pick(bm, m)
    h = _round_up(h, bn)
    mp = _round_up(m, bm)

    # every layer padded onto the (h, h) square: zero rows/cols keep the
    # chain exact (relu(0·x + 0) = 0 rides along and is sliced off at the end)
    w_stack = jnp.stack([_pad2(w, h, h) for w in ws])           # (L, h, h)
    b_stack = jnp.stack([_bias_row(b, h) for b in bs])          # (L, 1, h)
    xp = _pad2(x, mp, h)

    grid = (mp // bm, n_layers, h // bn)
    y = pl.pallas_call(
        functools.partial(_mlp_kernel, n_layers=n_layers),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, h), lambda i, l, j: (i, 0)),
            pl.BlockSpec((1, h, bn), lambda i, l, j: (l, 0, j)),
            pl.BlockSpec((1, 1, bn), lambda i, l, j: (l, 0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, l, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, h), x.dtype),
        scratch_shapes=[
            pltpu.VMEM((bm, h), jnp.float32),
            pltpu.VMEM((bm, h), jnp.float32),
        ],
        interpret=interpret,
    )(xp, w_stack, b_stack)
    return y[:m, :d_out]


def _layer_chain(x, ws, bs, *, bm, bk, bn, interpret):
    """The megakernel's semantics as a chain of fused_dense layers (hidden
    ReLU, linear head) — the recompute used by its VJP."""
    for i, (w, b) in enumerate(zip(ws, bs)):
        x = fused_dense(x, w, b, relu=i < len(ws) - 1, bm=bm, bk=bk, bn=bn,
                        interpret=interpret)
    return x


@functools.lru_cache(maxsize=None)
def _fused_mlp_vjp(bm: int, bk: int, bn: int, interpret: bool):
    @jax.custom_vjp
    def fm(x, ws, bs):
        return _mlp_forward(x, ws, bs, bm=bm, bn=bn, interpret=interpret)

    def fwd(x, ws, bs):
        return fm(x, ws, bs), (x, ws, bs)

    def bwd(res, dy):
        # inference-first kernel: the backward re-runs the layer chain
        # through fused_dense (whose own VJP is Pallas) rather than
        # shipping a second megakernel.  For non-f32 dtypes this is the
        # gradient of the per-layer-rounded chain, not of the forward's
        # all-f32 VMEM chain (training paths use mlp_apply, which IS the
        # per-layer chain, so the pairing is exact where grads matter)
        x, ws, bs = res
        _, vjp = jax.vjp(
            lambda x_, ws_, bs_: _layer_chain(x_, ws_, bs_, bm=bm, bk=bk,
                                              bn=bn, interpret=interpret),
            x, ws, bs)
        return vjp(dy)

    fm.defvjp(fwd, bwd)
    return fm


@functools.partial(jax.jit, static_argnames=("bm", "bk", "bn", "interpret"))
def fused_mlp(
    x: jnp.ndarray,                 # (M, D_in)
    ws: Tuple[jnp.ndarray, ...],    # per-layer (K_l, N_l)
    bs: Tuple[jnp.ndarray, ...],    # per-layer (N_l,)
    *,
    bm: int = DEFAULT_BM,
    bk: int = DEFAULT_BK,
    bn: int = DEFAULT_BN,
    interpret: bool = False,
) -> jnp.ndarray:
    """Whole-MLP forward (hidden ReLU, linear head) as ONE pallas_call:
    activations stay in VMEM across the layer grid axis (two ping-pong
    scratch buffers) instead of an HBM round-trip per layer.  VMEM working
    set: x block (bm·h) + weight slab (h·bn) + out (bm·bn), each double
    -buffered, + 2 activation buffers (bm·h) floats + the body's (bm·h)
    temporaries, h = padded max layer width.  At the paper's 2048-wide
    nets with the default (256, 512) blocks the TPU compiler needs between
    24 and 28 MiB of scoped VMEM (tests/test_tpu_compile.py compiles it)."""
    assert len(ws) == len(bs) and len(ws) >= 1
    return _fused_mlp_vjp(bm, bk, bn, interpret)(x, tuple(ws), tuple(bs))
