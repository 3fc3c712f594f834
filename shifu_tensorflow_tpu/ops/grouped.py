"""Grouped matrix products over the experts a chip holds.

A routed expert layer sends each token to ``k`` of ``n`` experts; a chip
holds ``held`` of them.  How many (token, choice) pairs land on a held
expert is data, not shape — anything from none to every pair — and no pair
may be dropped.  A dense product per expert over all tokens would cost
``held`` times the useful work; one product over a buffer sized for the
worst case ``k / held`` times.  Here the work follows the data:

- :func:`plan_tiles` sorts the pairs by expert (pairs of absent experts
  last), pads each held expert's run to a multiple of ``tile`` rows, and
  says for every tile which expert it belongs to and how many tiles are
  in use — all static shapes, sized for the worst case, integers only;
- :func:`expert_mlp` walks the tiles *in use* with a ``lax.while_loop``
  (the trip count is data): gather the tile's token rows, two products
  with that expert's weights with ``relu(.)^2`` between, scale by the gate
  weight, scatter-add into the output.  :func:`gated_expert_mlp` is the
  same walk for the gated expert, ``W_down(silu(W_gate h) * W_up h)``:
  three matrices and a product of two activations.  An expert that received no pair
  owns no tile.  The unsort is the scatter-add: a tile's tokens are
  distinct (a token chooses an expert at most once), padding rows point
  past the end and are dropped;
- the backward pass (``jax.custom_vjp``: a ``while_loop`` has no
  transpose) walks the same tiles once more, recomputes the tile's hidden
  activations and accumulates the input, gate and per-expert weight
  gradients (two matrices' or three).

Plain ``jax.numpy`` / ``lax``: the products are XLA's, at the precision
the caller's context gives; the same code runs on the CPU.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp


def plan_tiles(expert_ids: jax.Array, first: int, held: int, tile: int):
    """Tile plan for (token, choice) pairs ``expert_ids`` (T, k) int32 over
    the held experts ``first .. first + held - 1``.

    Returns ``(pair, tile_expert, n_tiles, counts)``: ``pair`` (M,) the
    flat index ``token * k + choice`` that fills each row of the sorted,
    tile-aligned buffer (``T * k`` for a padding row); ``tile_expert``
    (M / tile,) the local expert of each tile; ``n_tiles`` the tiles in
    use; ``counts`` (held,) pairs per held expert.  ``M`` covers the worst
    case (every token choosing ``min(k, held)`` held experts) plus one
    tile of padding an expert."""
    t, k = expert_ids.shape
    n_pairs = t * k
    m = (-(-t * min(k, held) // tile) + held) * tile
    local = expert_ids.reshape(-1) - first
    key = jnp.where((local >= 0) & (local < held), local, held)
    counts = jnp.zeros((held + 1,), jnp.int32).at[key].add(1)[:held]
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    padded = -(-counts // tile) * tile
    padded_end = jnp.cumsum(padded)
    sorted_start = jnp.cumsum(counts) - counts
    row = jnp.arange(m, dtype=jnp.int32)
    expert = jnp.searchsorted(padded_end, row, side="right").astype(jnp.int32)
    e = jnp.minimum(expert, held - 1)
    rank = row - (padded_end[e] - padded[e])
    live = (expert < held) & (rank < counts[e])
    src = jnp.clip(sorted_start[e] + rank, 0, n_pairs - 1)
    pair = jnp.where(live, order[src], n_pairs)
    tile_expert = e[::tile]
    n_tiles = (padded_end[-1] // tile).astype(jnp.int32)
    return pair, tile_expert, n_tiles, counts


def _tile(i, tile, token, gate):
    tok = jax.lax.dynamic_slice_in_dim(token, i * tile, tile)
    g = jax.lax.dynamic_slice_in_dim(gate, i * tile, tile)
    return tok, g


# An expert's hidden activation, in the two forms the family has.  Each
# gives ``(act, kept)`` forward and, from ``d act``, the gradients of its
# pre-activations (one a weight matrix before the last, in their order).

def _relu2(x, weights, e):
    (w_up,) = weights
    relu = jnp.maximum(x @ w_up[e], 0)
    return jnp.square(relu), relu


def _relu2_bwd(dact, relu):
    return (dact * (2 * relu),)


def _silu_gated(x, weights, e):
    w_gate, w_up = weights
    a, u = x @ w_gate[e], x @ w_up[e]
    sig = jax.nn.sigmoid(a)
    return (a * sig) * u, (a, u, sig)


def _silu_gated_bwd(dact, kept):
    a, u, sig = kept
    # silu'(a) = sig (1 + a (1 - sig))
    return dact * u * (sig * (1 + a * (1 - sig))), dact * (a * sig)


def _walk_forward(hidden, h, weights, w_down, token, gate, tile_expert,
                  n_tiles, tile):
    def body(carry):
        i, out = carry
        tok, g = _tile(i, tile, token, gate)
        e = tile_expert[i]
        x = jnp.take(h, tok, axis=0, mode="fill", fill_value=0)
        act, _ = hidden(x, weights, e)
        y = (act @ w_down[e]) * g[:, None].astype(h.dtype)
        return i + 1, out.at[tok].add(y, mode="drop", unique_indices=True)

    _, out = jax.lax.while_loop(lambda c: c[0] < n_tiles, body,
                                (jnp.int32(0), jnp.zeros_like(h)))
    return out


def _walk_backward(hidden, hidden_bwd, tile, res, d_out):
    """The same tiles once more: the tile's hidden activations are
    recomputed; the input's, the gate weight's and every weight matrix's
    gradients accumulate (``weights`` in their order, then ``w_down``)."""
    h, weights, w_down, token, gate, tile_expert, n_tiles = res

    def body(carry):
        i, dh, dws, dw_down, dgate = carry
        tok, g = _tile(i, tile, token, gate)
        e = tile_expert[i]
        x = jnp.take(h, tok, axis=0, mode="fill", fill_value=0)
        dy = jnp.take(d_out, tok, axis=0, mode="fill", fill_value=0)
        act, kept = hidden(x, weights, e)
        dg = jnp.sum((act @ w_down[e]) * dy, axis=-1).astype(gate.dtype)
        dy = dy * g[:, None].astype(h.dtype)
        dpres = hidden_bwd(dy @ w_down[e].T, kept)
        dw_down = dw_down.at[e].add(act.T @ dy)
        dws = tuple(dw.at[e].add(x.T @ dpre)
                    for dw, dpre in zip(dws, dpres))
        dx = sum(dpre @ w[e].T for w, dpre in zip(weights, dpres))
        dh = dh.at[tok].add(dx, mode="drop", unique_indices=True)
        dgate = jax.lax.dynamic_update_slice_in_dim(dgate, dg, i * tile, 0)
        return i + 1, dh, dws, dw_down, dgate

    _, dh, dws, dw_down, dgate = jax.lax.while_loop(
        lambda c: c[0] < n_tiles, body,
        (jnp.int32(0), jnp.zeros_like(h),
         tuple(jnp.zeros_like(w) for w in weights),
         jnp.zeros_like(w_down), jnp.zeros_like(gate)))
    return dh, dws, dw_down, dgate


@partial(jax.custom_vjp, nondiff_argnums=(7,))
def expert_mlp(h, w_up, w_down, token, gate, tile_expert, n_tiles, tile):
    """``out[t] = sum over t's held choices e of gate * relu(h[t] W_up[e])^2
    W_down[e]``.

    ``h`` (T, d); ``w_up`` (held, d, f), ``w_down`` (held, f, d); ``token``
    (M,) int32 the token of each buffer row (``T`` or more: padding) and
    ``gate`` (M,) its gate weight (0 on padding), in :func:`plan_tiles`'s
    order; ``tile_expert``, ``n_tiles`` from the plan; ``tile`` static."""
    return _walk_forward(_relu2, h, (w_up,), w_down, token, gate,
                         tile_expert, n_tiles, tile)


def _fwd(h, w_up, w_down, token, gate, tile_expert, n_tiles, tile):
    out = _walk_forward(_relu2, h, (w_up,), w_down, token, gate,
                        tile_expert, n_tiles, tile)
    return out, (h, (w_up,), w_down, token, gate, tile_expert, n_tiles)


def _bwd(tile, res, d_out):
    dh, (dw_up,), dw_down, dgate = _walk_backward(
        _relu2, _relu2_bwd, tile, res, d_out)
    return dh, dw_up, dw_down, None, dgate, None, None


expert_mlp.defvjp(_fwd, _bwd)


@partial(jax.custom_vjp, nondiff_argnums=(8,))
def gated_expert_mlp(h, w_gate, w_up, w_down, token, gate, tile_expert,
                     n_tiles, tile):
    """``out[t] = sum over t's held choices e of gate * (silu(h[t]
    W_gate[e]) * (h[t] W_up[e])) W_down[e]``: :func:`expert_mlp`'s walk
    over the same plan with the gated expert's three matrices (``w_gate``
    as ``w_up``, (held, d, f)); the backward pass gives the gradients of
    the three, of ``h`` and of the gate weight ``gate``."""
    return _walk_forward(_silu_gated, h, (w_gate, w_up), w_down, token,
                         gate, tile_expert, n_tiles, tile)


def _gated_fwd(h, w_gate, w_up, w_down, token, gate, tile_expert, n_tiles,
               tile):
    out = _walk_forward(_silu_gated, h, (w_gate, w_up), w_down, token, gate,
                        tile_expert, n_tiles, tile)
    return out, (h, (w_gate, w_up), w_down, token, gate, tile_expert,
                 n_tiles)


def _gated_bwd(tile, res, d_out):
    dh, (dw_gate, dw_up), dw_down, dgate = _walk_backward(
        _silu_gated, _silu_gated_bwd, tile, res, d_out)
    return dh, dw_gate, dw_up, dw_down, None, dgate, None, None


gated_expert_mlp.defvjp(_gated_fwd, _gated_bwd)
