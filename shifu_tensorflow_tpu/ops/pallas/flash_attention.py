"""Flash attention as a Pallas TPU kernel.

The sequence family's long-S cost is attention-score materialization:
full attention builds the (S, S) score matrix in HBM; at S=4096 that is
gigabytes.  The reference
has no attention at all (fixed-width tabular vectors — SURVEY.md §5.7);
this kernel serves the beyond-parity sequence/long-context family.

Design — the standard flash decomposition, Pallas-TPU idioms:

- grid ``(B·H, S/BQ, S/BK)`` with the K/V axis innermost; VMEM scratch
  (running numerator ``acc``, running max ``m``, normalizer ``l``)
  persists across the sequential K/V steps of one (batch·head, q-block);
- each step computes a (BQ, BK) score tile on the MXU
  (``preferred_element_type=f32``), applies the online-softmax update,
  and accumulates ``p @ v`` — the (S, S) matrix never exists anywhere;
- the last K/V step of a query block's run normalizes and writes the
  output block;
- causal + padding masks come from ``broadcasted_iota`` positions, so
  arbitrary (non-multiple-of-block) S works via zero-padding;
- a static ``window`` (causal only: key ``j`` is visible to query ``i``
  iff ``j <= i`` and ``i - j < window``) narrows the innermost grid axis
  to the blocks that intersect the band (:class:`_Band`): a query block
  walks only the key blocks from the one that holds its first row's
  oldest visible key to the one on the diagonal, a key block only the
  query blocks from the diagonal to the one that holds its last key's
  youngest query.  Blocks outside the band are neither fetched nor
  multiplied; where a run is shorter than the axis (the first query
  blocks, the last key blocks) the spare steps repeat a neighbouring
  step's block index and do nothing;
- causal without a window, all three kernels walk the triangle and not
  the square (:class:`_Fold`): a tile wholly above the diagonal, whose
  every score would be masked, is neither fetched nor multiplied; the
  tiles on the diagonal keep their mask.  So that no grid step is idle,
  a grid row is TWO query blocks' runs, a short one and the long one
  that complements it (key blocks likewise in the dK/dV kernel), the
  statistics reset and the output block written where the run changes:
  at S 8,192 and 512-row tiles 8 rows of 17 live steps a head, 136 of
  the square's 256 (:func:`grid_steps` counts them).  An idle step is
  not free (0.18 us: 5.5 ms a train step at that shape, measured), which
  is why the triangle is folded and not walked as a band whose window is
  the sequence;
- not causal, the grid is the whole square and every tile is visited.

The backward pass is a true Pallas FlashAttention-2 backward (new in
r05; the forward now also emits per-row logsumexp): one kernel
accumulates dQ over key blocks, a second accumulates dK/dV over query
blocks, P reconstructed per tile from the saved logsumexp — no S×S
matrix in either pass.  ``STPU_FLASH_BWD=chunked`` selects the previous
chunked-XLA-scan gradient for A/B measurement
(scripts/bench_flash_sweep.py).  Parity vs full attention is asserted
in tests/test_flash.py in interpret mode, which only a test asks for
(``interpret=True``): the program never picks it, so on a host without
a TPU the kernel fails instead of running in the interpreter.  That it
lowers for the v5e is pinned in tests/test_tpu_compile.py.  The
per-row logsumexp and D vectors are carried as (B·H, Sp, 1) arrays:
Mosaic wants a block's last two dimensions divisible by (8, 128) or
equal to the array's, which a (1, BQ) block of a (B·H, Sp) array is not.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


class _Band:
    """Which blocks of the (query block, key block) square a causal
    ``window`` leaves, in whole numbers a grid's index maps can compute.

    Query block ``qi`` (rows ``qi bq .. (qi+1) bq - 1``) sees the key
    blocks ``first_k(qi) .. last_k(qi)``: from the block of its first
    row's oldest visible key, ``qi bq - window + 1``, to the block of its
    last row (the diagonal).  Key block ``ki`` is seen by the query blocks
    ``first_q(ki) .. last_q(ki)``: from the block of its first key to the
    block of its last key's youngest query, ``(ki+1) bk + window - 2``.
    ``nk`` / ``nq`` are the longest such runs: the innermost grid axis."""

    def __init__(self, window: int, sp: int, bq: int, bk: int):
        self.window, self.bq, self.bk = window, bq, bk
        self.q_blocks, self.k_blocks = sp // bq, sp // bk
        self.nk = max(self.last_k(i) - self.first_k(i) + 1
                      for i in range(self.q_blocks))
        self.nq = max(self.last_q(i) - self.first_q(i) + 1
                      for i in range(self.k_blocks))

    # the same expressions serve Python ints (the sizes above) and the
    # traced scalars of an index map or a kernel body
    def first_k(self, qi):
        return _at_least(qi * self.bq - self.window + 1, 0) // self.bk

    def last_k(self, qi):
        return ((qi + 1) * self.bq - 1) // self.bk

    def first_q(self, ki):
        return (ki * self.bk) // self.bq

    def last_q(self, ki):
        last = ((ki + 1) * self.bk + self.window - 2) // self.bq
        return _at_most(last, self.q_blocks - 1)

    def key_block(self, qi, step):
        """(key block of a query block's ``step``-th grid step, whether
        the step is inside its run): the run ends on the diagonal, so the
        steps before a short run's start are the idle ones; they name the
        run's first block, which the next step wants anyway."""
        ki = self.last_k(qi) - (self.nk - 1) + step
        first = self.first_k(qi)
        return _at_least(ki, first), ki >= first

    def query_block(self, ki, step):
        """The same for a key block's run of query blocks, which starts
        on the diagonal: the idle steps trail and repeat the last block."""
        qi = self.first_q(ki) + step
        last = self.last_q(ki)
        return _at_most(qi, last), qi <= last

    @property
    def key_grid(self):
        return self.q_blocks, self.nk

    @property
    def query_grid(self):
        return self.k_blocks, self.nq

    def key_tile(self, row, step):
        """(query block, key block, live, first, last) of a grid point of
        the forward and dQ kernels, where a grid row is one query block's
        run: ``first`` and ``last`` say where a run starts (reset the
        accumulators) and ends (write the output block)."""
        ki, live = self.key_block(row, step)
        return row, ki, live, step == 0, step == self.nk - 1

    def query_tile(self, row, step):
        """(key block, query block, live, first, last) in the dK/dV
        kernel, where a grid row is one key block's run."""
        qi, live = self.query_block(row, step)
        return row, qi, live, step == 0, step == self.nq - 1


class _Square:
    """Every tile of the square (not causal): a grid row IS a block, a
    step the block it walks, and every step live (``None``: no condition
    to lower)."""

    window = None

    def __init__(self, sp: int, bq: int, bk: int):
        self.key_grid = sp // bq, sp // bk
        self.query_grid = sp // bk, sp // bq

    def key_tile(self, row, step):
        return row, step, None, step == 0, step == self.key_grid[1] - 1

    def query_tile(self, row, step):
        return row, step, None, step == 0, step == self.query_grid[1] - 1


class _Fold:
    """The causal triangle without a window, folded so that (nearly) no
    grid step is idle.  Query block ``j`` walks ``last_k(j) + 1`` key
    blocks and query block ``q_blocks - 1 - j`` the more the fewer ``j``
    does, so the two share one grid row: ``j``'s run, then its partner's,
    the accumulators reset and the output block written where the run
    changes.  At equal blocks every row is ``q_blocks + 1`` live steps;
    where the blocks differ, or the middle block of an odd count stands
    alone, the spare steps trail, repeat the last block index and do
    nothing.  The dK/dV kernel folds the key blocks the same way (key
    block ``j`` is walked by the query blocks from ``first_q(j)`` on)."""

    window = None

    def __init__(self, sp: int, bq: int, bk: int):
        runs = _Band(sp, sp, bq, bk)  # no window: it is the sequence
        keys = _FoldedRuns(
            runs.q_blocks, lambda qi: (runs.first_k(qi), runs.last_k(qi)))
        queries = _FoldedRuns(
            runs.k_blocks, lambda ki: (runs.first_q(ki), runs.last_q(ki)))
        self.key_grid, self.key_tile = keys.grid, keys.tile
        self.query_grid, self.query_tile = queries.grid, queries.tile


class _FoldedRuns:
    """``blocks`` runs, ``run_of(i)`` the (first, last) block that block
    ``i`` walks, two to a grid row: ``i``'s, then ``blocks - 1 - i``'s."""

    def __init__(self, blocks: int, run_of):
        self.blocks, self.run_of = blocks, run_of
        rows = -(-blocks // 2)
        self.grid = rows, max(
            sum(self._steps(i) for i in {row, blocks - 1 - row})
            for row in range(rows))

    def _steps(self, i):
        start, end = self.run_of(i)
        return end - start + 1

    def tile(self, row, step):
        """(block whose run it is, block walked, live, first, last) of a
        grid point, in Python ints or in an index map's traced scalars."""
        partner = self.blocks - 1 - row
        n = self._steps(row)
        second = (step >= n) & (partner != row)
        block = _select(second, partner, row)
        since = step - _select(second, n, 0)
        start, end = self.run_of(block)
        first = (step == 0) | (second & (since == 0))
        last = (step == n - 1) | (
            (step == self.grid[1] - 1) & (partner != row))
        walked = start + since
        return block, _at_most(walked, end), walked <= end, first, last


def _select(cond, a, b):
    if isinstance(cond, bool):
        return a if cond else b
    return jnp.where(cond, a, b)


def _at_least(x, lo):
    return max(x, lo) if isinstance(x, int) else jnp.maximum(x, lo)


def _at_most(x, hi):
    return min(x, hi) if isinstance(x, int) else jnp.minimum(x, hi)


def _when(live, tile):
    """Run a tile's body, under ``pl.when`` where the grid has idle steps."""
    if live is None:
        tile()
    else:
        pl.when(live)(tile)


def _valid(qi, ki, block_q, block_k, s_real, causal, walk):
    """(BQ, BK) mask of the keys a tile's queries may see."""
    window = walk.window
    q_pos = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    k_pos = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    valid = k_pos < s_real  # zero-padded keys must not attend
    if causal:
        valid = jnp.logical_and(valid, k_pos <= q_pos)
    if window is not None:
        valid = jnp.logical_and(valid, q_pos - k_pos < window)
    return valid


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref,
                  l_ref, *, scale: float, causal: bool, s_real: int,
                  block_q: int, block_k: int, walk):
    qi, ki, live, first, last = walk.key_tile(
        pl.program_id(1), pl.program_id(2))

    @pl.when(first)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[:] = jnp.zeros_like(l_ref)

    def tile():
        # (BQ, BK) score tile on the MXU; accumulate in f32 regardless of
        # the input dtype so bf16 inputs keep full-precision statistics
        scores = jax.lax.dot_general(
            q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale
        valid = _valid(qi, ki, block_q, block_k, s_real, causal, walk)
        scores = jnp.where(valid, scores, -jnp.inf)

        m_prev = m_ref[:]
        l_prev = l_ref[:]
        m_blk = jnp.max(scores, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_blk)
        # nothing seen yet where m_new is still -inf: keep correction at 0
        corr = jnp.where(jnp.isneginf(m_new), 0.0, jnp.exp(m_prev - m_new))
        p = jnp.exp(scores - m_new)
        p = jnp.where(valid, p, 0.0)
        l_ref[:] = l_prev * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[:] = acc_ref[:] * corr + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[:] = m_new

    _when(live, tile)

    @pl.when(last)
    def _():
        l = jnp.where(l_ref[:] == 0.0, 1.0, l_ref[:])
        o_ref[0] = (acc_ref[:] / l).astype(o_ref.dtype)
        # logsumexp per query row, for the backward kernels: rows with no
        # valid key (l == 0, e.g. zero-padding) get +inf so that
        # exp(S - L) reconstructs P = 0 there instead of NaN
        lse = jnp.where(l_ref[:] > 0.0,
                        m_ref[:] + jnp.log(l_ref[:]), jnp.inf)
        lse_ref[0] = lse


def _padded(s: int, block_q: int, block_k: int):
    """(padded S, query block, key block).  S pads to a common multiple of
    BOTH blocks: rounding to only the larger one truncates the grid for
    the smaller (sp // block floors), silently dropping trailing query
    rows or key blocks."""
    import math

    sp = _round_up(s, math.lcm(block_q, block_k))
    return sp, min(block_q, sp), min(block_k, sp)


def _pad_geom(q, block_q: int, block_k: int):
    b, s, h, d = q.shape
    return b, s, h, d, _round_up(d, 128), *_padded(s, block_q, block_k)


def _prep(x, b, s, h, d, dp, sp):
    """(B, S, H, D) -> (B*H, Sp, Dp), zero-padded."""
    x = jnp.pad(x, ((0, 0), (0, sp - s), (0, 0), (0, dp - d)))
    return x.transpose(0, 2, 1, 3).reshape(b * h, sp, dp)


def _unprep(xp, b, s, h, d, dp, sp):
    return xp.reshape(b, h, sp, dp).transpose(0, 2, 1, 3)[:, :s, :, :d]


def _walk_of(window, causal, sp, bq, bk) -> "_Band | _Fold | _Square":
    """Which tiles of the square the kernels visit: a window's band, the
    causal triangle, or all of them."""
    if window is None:
        return (_Fold if causal else _Square)(sp, bq, bk)
    if not causal or window <= 0:
        raise ValueError(
            f"window={window} needs causal attention and a window > 0")
    return _Band(int(window), sp, bq, bk)


def grid_steps(seq_len: int, block_q: int, block_k: int, *, causal: bool,
               window: "int | None" = None) -> dict:
    """``(live, idle)`` grid steps a batch·head of each of the three
    kernels: a live step fetches and multiplies one tile, an idle one
    repeats its neighbour's block indices and does nothing.  A function of
    the static shapes alone, as the walk is."""
    walk = _walk_of(window, causal, *_padded(seq_len, block_q, block_k))

    def count(tile, grid):
        rows, steps = grid
        live = sum(tile(row, t)[2] is not False
                   for row in range(rows) for t in range(steps))
        return live, rows * steps - live

    keys = count(walk.key_tile, walk.key_grid)
    return {"forward": keys, "dq": keys,
            "dkv": count(walk.query_tile, walk.query_grid)}


def _index_maps(tile):
    """Block index maps of a walk's grid: (of the block whose run a grid
    row is, of the block a step of it walks)."""
    return (lambda bh, row, t: (bh, tile(row, t)[0], 0),
            lambda bh, row, t: (bh, tile(row, t)[1], 0))


def _flash_forward_with_stats(q, k, v, *, causal: bool, block_q: int,
                              block_k: int, interpret: bool,
                              window: "int | None" = None):
    """Returns (out (B,S,H,D), lse (B*H, Sp, 1) padded-layout logsumexp)."""
    from shifu_tensorflow_tpu.obs import compile as obs_compile

    b, s, h, d, dp, sp, bq, bk = _pad_geom(q, block_q, block_k)
    scale = d ** -0.5
    qp = _prep(q, b, s, h, d, dp, sp)
    kp = _prep(k, b, s, h, d, dp, sp)
    vp = _prep(v, b, s, h, d, dp, sp)
    walk = _walk_of(window, causal, sp, bq, bk)
    q_block, kv_block = _index_maps(walk.key_tile)
    # compile-attribution region (obs/compile.py): an EAGER call compiles
    # the kernel inside this frame and journals under the pallas name; a
    # call traced into an outer jitted step compiles later, inside that
    # step's own observed call — attributed there, which is the truth
    with obs_compile.attribute("pallas.flash_attention"):
        out, lse = pl.pallas_call(
            partial(_flash_kernel, scale=scale, causal=causal, s_real=s,
                    block_q=bq, block_k=bk, walk=walk),
            grid=(b * h, *walk.key_grid),
            in_specs=[
                pl.BlockSpec((1, bq, dp), q_block),
                pl.BlockSpec((1, bk, dp), kv_block),
                pl.BlockSpec((1, bk, dp), kv_block),
            ],
            out_specs=[
                pl.BlockSpec((1, bq, dp), q_block),
                pl.BlockSpec((1, bq, 1), q_block),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((b * h, sp, dp), q.dtype),
                jax.ShapeDtypeStruct((b * h, sp, 1), jnp.float32),
            ],
            scratch_shapes=[
                _vmem((bq, dp)),
                _vmem((bq, 1)),
                _vmem((bq, 1)),
            ],
            interpret=interpret,
        )(qp, kp, vp)
    return _unprep(out, b, s, h, d, dp, sp), lse


def _flash_forward(q, k, v, *, causal: bool, block_q: int, block_k: int,
                   interpret: bool, window: "int | None" = None):
    out, _ = _flash_forward_with_stats(
        q, k, v, causal=causal, block_q=block_q, block_k=block_k,
        interpret=interpret, window=window)
    return out


def _vmem(shape):
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.VMEM(shape, jnp.float32)


def _bwd_p_ds(qf, kf, vf, dof, lse, dvec, valid, scale):
    """Shared tile math: reconstruct P from the forward's logsumexp, then
    dS = P * (dP - D).  All f32; (bq, bk) tiles on the MXU."""
    s = jax.lax.dot_general(
        qf, kf, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale
    # rows with no valid key carry lse=+inf -> exp(-inf)=0, NaN-free
    p = jnp.where(valid, jnp.exp(s - lse), 0.0)
    dp = jax.lax.dot_general(
        dof, vf, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    ds = p * (dp - dvec)
    return p, ds


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, d_ref,
                         dq_ref, acc_ref, *, scale: float, causal: bool,
                         s_real: int, block_q: int, block_k: int,
                         walk):
    qi, ki, live, first, last = walk.key_tile(
        pl.program_id(1), pl.program_id(2))

    @pl.when(first)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    def tile():
        qf = q_ref[0].astype(jnp.float32)
        kf = k_ref[0].astype(jnp.float32)
        vf = v_ref[0].astype(jnp.float32)
        dof = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0]   # (bq, 1)
        dvec = d_ref[0]    # (bq, 1)
        valid = _valid(qi, ki, block_q, block_k, s_real, causal, walk)
        _, ds = _bwd_p_ds(qf, kf, vf, dof, lse, dvec, valid, scale)
        acc_ref[:] += jax.lax.dot_general(
            ds, kf, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale

    _when(live, tile)

    @pl.when(last)
    def _():
        dq_ref[0] = acc_ref[:].astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(k_ref, v_ref, q_ref, do_ref, lse_ref, d_ref,
                          dk_ref, dv_ref, dk_acc, dv_acc, *, scale: float,
                          causal: bool, s_real: int, block_q: int,
                          block_k: int, walk):
    ki, qi, live, first, last = walk.query_tile(
        pl.program_id(1), pl.program_id(2))

    @pl.when(first)
    def _():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    def tile():
        qf = q_ref[0].astype(jnp.float32)
        kf = k_ref[0].astype(jnp.float32)
        vf = v_ref[0].astype(jnp.float32)
        dof = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0]
        dvec = d_ref[0]
        valid = _valid(qi, ki, block_q, block_k, s_real, causal, walk)
        p, ds = _bwd_p_ds(qf, kf, vf, dof, lse, dvec, valid, scale)
        # dV += P^T @ dO ; dK += dS^T @ Q * scale  (both (bk, dp))
        dv_acc[:] += jax.lax.dot_general(
            p, dof, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dk_acc[:] += jax.lax.dot_general(
            ds, qf, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale

    _when(live, tile)

    @pl.when(last)
    def _():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _flash_backward(q, k, v, out, lse, g, *, causal: bool, block_q: int,
                    block_k: int, interpret: bool,
                    window: "int | None" = None):
    """True Pallas flash backward: P is reconstructed per tile from the
    forward's logsumexp (no S×S matrix anywhere), dQ accumulates over key
    blocks, dK/dV over query blocks — the FlashAttention-2 decomposition.
    """
    b, s, h, d, dp, sp, bq, bk = _pad_geom(q, block_q, block_k)
    scale = d ** -0.5
    qp = _prep(q, b, s, h, d, dp, sp)
    kp = _prep(k, b, s, h, d, dp, sp)
    vp = _prep(v, b, s, h, d, dp, sp)
    dop = _prep(g, b, s, h, d, dp, sp)
    outp = _prep(out, b, s, h, d, dp, sp)
    # D_i = sum_d dO_i * O_i — cheap elementwise+reduce, XLA does it well
    dvec = jnp.sum(dop.astype(jnp.float32) * outp.astype(jnp.float32),
                   axis=-1, keepdims=True)  # (BH, Sp, 1)
    walk = _walk_of(window, causal, sp, bq, bk)
    q_row, kv_step = _index_maps(walk.key_tile)
    kv_row, q_step = _index_maps(walk.query_tile)

    dq = pl.pallas_call(
        partial(_flash_bwd_dq_kernel, scale=scale, causal=causal,
                s_real=s, block_q=bq, block_k=bk, walk=walk),
        grid=(b * h, *walk.key_grid),
        in_specs=[
            pl.BlockSpec((1, bq, dp), q_row),
            pl.BlockSpec((1, bk, dp), kv_step),
            pl.BlockSpec((1, bk, dp), kv_step),
            pl.BlockSpec((1, bq, dp), q_row),
            pl.BlockSpec((1, bq, 1), q_row),
            pl.BlockSpec((1, bq, 1), q_row),
        ],
        out_specs=pl.BlockSpec((1, bq, dp), q_row),
        out_shape=jax.ShapeDtypeStruct((b * h, sp, dp), q.dtype),
        scratch_shapes=[_vmem((bq, dp))],
        interpret=interpret,
    )(qp, kp, vp, dop, lse, dvec)

    dk, dv = pl.pallas_call(
        partial(_flash_bwd_dkv_kernel, scale=scale, causal=causal,
                s_real=s, block_q=bq, block_k=bk, walk=walk),
        grid=(b * h, *walk.query_grid),
        in_specs=[
            pl.BlockSpec((1, bk, dp), kv_row),
            pl.BlockSpec((1, bk, dp), kv_row),
            pl.BlockSpec((1, bq, dp), q_step),
            pl.BlockSpec((1, bq, dp), q_step),
            pl.BlockSpec((1, bq, 1), q_step),
            pl.BlockSpec((1, bq, 1), q_step),
        ],
        out_specs=[
            pl.BlockSpec((1, bk, dp), kv_row),
            pl.BlockSpec((1, bk, dp), kv_row),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, sp, dp), k.dtype),
            jax.ShapeDtypeStruct((b * h, sp, dp), v.dtype),
        ],
        scratch_shapes=[_vmem((bk, dp)), _vmem((bk, dp))],
        interpret=interpret,
    )(kp, vp, qp, dop, lse, dvec)

    un = lambda xp: _unprep(xp, b, s, h, d, dp, sp)  # noqa: E731
    return un(dq), un(dk), un(dv)


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def flash_attention(q, k, v, causal: bool = False, block_q: int = 128,
                    block_k: int = 128, interpret: bool = False,
                    window: "int | None" = None):
    """Fused flash attention, shapes (B, S, H, D).  ``causal`` keeps the
    keys ``j <= i`` of a query ``i``, ``window`` (causal only) of those
    the ones with ``i - j < window``; all three kernels walk only the
    blocks that hold a visible key: the triangle, or the window's band.

    Forward: the Pallas kernel above.
    Backward: the Pallas FlashAttention-2 backward (_flash_backward) —
    P reconstructed per tile from the forward's saved logsumexp, dQ/dK/dV
    accumulated blockwise, no S×S matrix in either pass.  Set
    ``STPU_FLASH_BWD=chunked`` to fall back to the chunked-XLA-scan
    gradient (the pre-r05 behavior) for A/B measurement
    (scripts/bench_flash_sweep.py).

    ``STPU_FLASH_BWD`` is read at TRACE time: when the gradient is taken
    inside a jitted train step, the chosen branch is baked into the cached
    jaxpr, so flipping the env var mid-process silently keeps whichever
    backward was traced first.  To actually switch, start a new process
    (how bench_flash_sweep.py runs its subprocess-per-case A/B) or clear
    the jit caches (``jax.clear_caches()``) before the next call.
    """
    return _flash_forward(q, k, v, causal=causal, block_q=block_q,
                          block_k=block_k, interpret=interpret,
                          window=window)


def _flash_fwd(q, k, v, causal, block_q, block_k, interpret, window):
    out, lse = _flash_forward_with_stats(
        q, k, v, causal=causal, block_q=block_q, block_k=block_k,
        interpret=interpret, window=window)
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, block_q, block_k, interpret, window, res, g):
    import os

    q, k, v, out, lse = res
    # trace-time read: under jit this branch is frozen into the cached
    # jaxpr — see the flash_attention docstring for the switching contract
    if os.environ.get("STPU_FLASH_BWD", "pallas") == "chunked":
        from shifu_tensorflow_tpu.parallel.ring import chunked_attention

        # chunked fallback: never SMALLER than 512, chunked_attention's
        # own default (default callers pass block_q=block_k=128, which
        # must not shrink the backward chunk)
        block = max(512, block_q, block_k)
        _, vjp = jax.vjp(
            lambda q_, k_, v_: chunked_attention(
                q_, k_, v_, causal=causal, block_size=block,
                window=window),
            q, k, v,
        )
        return vjp(g)
    return _flash_backward(q, k, v, out, lse, g, causal=causal,
                           block_q=block_q, block_k=block_k,
                           interpret=interpret, window=window)


flash_attention.defvjp(_flash_fwd, _flash_bwd)
