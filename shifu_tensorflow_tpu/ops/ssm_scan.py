"""Chunked state-space scan (the Mamba-2 "SSD" form) in plain ``jax.numpy``.

Who runs it: every program but one.  ``models/hybrid_lm.py``
``chunked_scan`` hands a program lowered for the TPU, at float32 and
shapes of whole 128-lane registers (``ops/pallas/ssd_scan.py``
``ssd_pays``: the Nemotron cell's), to the kernels of that module, which
do this arithmetic with the chunk kept in VMEM; every program on the CPU,
``--dtype bfloat16``, a head of 8, a state of 16, a chunk of 7 … 64 and a
sequence the chunk does not divide run the expression below.  It is also
the kernels' oracle: ``tests/test_ssd_kernel.py`` holds their ``y`` and
five gradients to it.

The recurrence, per head with state ``H`` (p, n)::

    H_t = exp(dt_t * a) * H_{t-1} + dt_t * x_t (x) B_t        y_t = H_t C_t

run one step at a time is S sequential element-wise passes over the state
— nothing for the MXU.  The chunked form cuts the sequence into chunks of
``chunk`` steps and turns everything inside a chunk into matrix products:

- inside a chunk, ``y_l += sum_{s<=l} (C_l . B_s) exp(A_l - A_s) dt_s x_s``
  with ``A`` the running sum of ``dt * a`` in the chunk: a (chunk, chunk)
  score matrix per group, a decay mask per head, one product with x;
- a chunk's contribution to the state at its end is one product,
  ``sum_s exp(A_last - A_s) dt_s x_s (x) B_s``;
- the states entering the chunks follow a recurrence over S / chunk steps
  (a ``lax.scan``, element-wise, so it stays float32 whatever the matmul
  precision);
- the entering state's share of the output is one more product,
  ``exp(A_l) C_l H_in``.

The backward pass of THIS path is JAX's transpose of exactly these
products (no custom rule; the kernels bring their own): the decay
exponents are masked to ``-inf`` *before* the ``exp``, so a masked entry
is 0 with gradient 0 and no ``inf * 0`` can arise; the chunk recurrence
transposes into the reverse scan over chunks.
A sequence that the chunk does not divide is padded with ``dt = 0`` steps
(decay 1, no input: the state passes through) and the rows are dropped.

``B`` and ``C`` are shared by the ``h / g`` heads of a group and are never
repeated: the head axis is carried as (group, heads in group).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def ssm_scan_chunked(x: jax.Array, dt: jax.Array, a: jax.Array,
                     b: jax.Array, c: jax.Array, chunk: int) -> jax.Array:
    """``y`` (B, S, h, p) of ``x`` (B, S, h, p), ``dt`` (B, S, h) (already
    positive), ``a`` (h,) (negative), ``b`` and ``c`` (B, S, g, n) with
    ``g`` dividing ``h``.  The ``D * x`` skip term is the caller's."""
    bsz, s, h, p = x.shape
    g, n = b.shape[2], b.shape[3]
    r = h // g
    pad = -s % chunk
    if pad:
        x, dt, b, c = (
            jnp.pad(v, [(0, 0), (0, pad)] + [(0, 0)] * (v.ndim - 2))
            for v in (x, dt, b, c))
    nc = (s + pad) // chunk
    x = x.reshape(bsz, nc, chunk, g, r, p)
    dt = dt.reshape(bsz, nc, chunk, g, r)
    b = b.reshape(bsz, nc, chunk, g, n)
    c = c.reshape(bsz, nc, chunk, g, n)
    log_decay = (dt * a.reshape(g, r)).astype(jnp.float32)
    a_cum = jnp.cumsum(log_decay, axis=2)            # (B, C, L, g, r)
    xdt = x * dt[..., None].astype(x.dtype)

    # ---- inside a chunk: scores per group, decay mask per head
    scores = jnp.einsum("bclgn,bcsgn->bcgls", c, b)  # (B, C, g, L, L)
    a_l = jnp.moveaxis(a_cum, 2, -1)                 # (B, C, g, r, L)
    diff = a_l[..., :, None] - a_l[..., None, :]     # [l, s] = A_l - A_s
    causal = jnp.tril(jnp.ones((chunk, chunk), bool))
    decay = jnp.exp(jnp.where(causal, diff, -jnp.inf))
    weights = scores[:, :, :, None].astype(jnp.float32) * decay
    y = jnp.einsum("bcgrls,bcsgrp->bclgrp", weights.astype(x.dtype), xdt)

    # ---- each chunk's contribution to the state at its end
    to_end = jnp.exp(a_cum[:, :, -1:] - a_cum)       # (B, C, L, g, r)
    chunk_state = jnp.einsum("bcsgn,bcsgrp->bcgrpn", b,
                             xdt * to_end[..., None].astype(x.dtype))

    # ---- the state entering each chunk: a recurrence over the chunks
    chunk_decay = jnp.exp(a_cum[:, :, -1])           # (B, C, g, r)

    def enter(state, inputs):
        decay_c, state_c = inputs
        nxt = decay_c[..., None, None] * state + state_c
        return nxt, state

    zero = jnp.zeros((bsz, g, r, p, n), jnp.float32)
    _, entering = jax.lax.scan(
        enter, zero,
        (jnp.moveaxis(chunk_decay, 1, 0),
         jnp.moveaxis(chunk_state.astype(jnp.float32), 1, 0)))
    entering = jnp.moveaxis(entering, 0, 1)          # (B, C, g, r, p, n)

    # ---- the entering state's share of the output
    y = y + jnp.einsum("bclgn,bcgrpn->bclgrp", c,
                       entering.astype(x.dtype)) \
        * jnp.exp(a_cum)[..., None].astype(x.dtype)
    return y.reshape(bsz, nc * chunk, h, p)[:, :s]
