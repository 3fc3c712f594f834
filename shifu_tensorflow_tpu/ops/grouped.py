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
  weight, scatter-add into the output.  An expert that received no pair
  owns no tile.  The unsort is the scatter-add: a tile's tokens are
  distinct (a token chooses an expert at most once), padding rows point
  past the end and are dropped;
- the backward pass (``jax.custom_vjp``: a ``while_loop`` has no
  transpose) walks the same tiles once more, recomputes the tile's hidden
  activations and accumulates the input, gate and per-expert weight
  gradients.

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


def _forward(h, w_up, w_down, token, gate, tile_expert, n_tiles, tile):
    def body(carry):
        i, out = carry
        tok, g = _tile(i, tile, token, gate)
        e = tile_expert[i]
        x = jnp.take(h, tok, axis=0, mode="fill", fill_value=0)
        act = jnp.square(jnp.maximum(x @ w_up[e], 0))
        y = (act @ w_down[e]) * g[:, None].astype(h.dtype)
        return i + 1, out.at[tok].add(y, mode="drop", unique_indices=True)

    _, out = jax.lax.while_loop(lambda c: c[0] < n_tiles, body,
                                (jnp.int32(0), jnp.zeros_like(h)))
    return out


@partial(jax.custom_vjp, nondiff_argnums=(7,))
def expert_mlp(h, w_up, w_down, token, gate, tile_expert, n_tiles, tile):
    """``out[t] = sum over t's held choices e of gate * relu(h[t] W_up[e])^2
    W_down[e]``.

    ``h`` (T, d); ``w_up`` (held, d, f), ``w_down`` (held, f, d); ``token``
    (M,) int32 the token of each buffer row (``T`` or more: padding) and
    ``gate`` (M,) its gate weight (0 on padding), in :func:`plan_tiles`'s
    order; ``tile_expert``, ``n_tiles`` from the plan; ``tile`` static."""
    return _forward(h, w_up, w_down, token, gate, tile_expert, n_tiles, tile)


def _fwd(h, w_up, w_down, token, gate, tile_expert, n_tiles, tile):
    out = _forward(h, w_up, w_down, token, gate, tile_expert, n_tiles, tile)
    return out, (h, w_up, w_down, token, gate, tile_expert, n_tiles)


def _bwd(tile, res, d_out):
    h, w_up, w_down, token, gate, tile_expert, n_tiles = res

    def body(carry):
        i, dh, dw_up, dw_down, dgate = carry
        tok, g = _tile(i, tile, token, gate)
        e = tile_expert[i]
        x = jnp.take(h, tok, axis=0, mode="fill", fill_value=0)
        dy = jnp.take(d_out, tok, axis=0, mode="fill", fill_value=0)
        pre = x @ w_up[e]
        relu = jnp.maximum(pre, 0)
        act = jnp.square(relu)
        dg = jnp.sum((act @ w_down[e]) * dy, axis=-1).astype(gate.dtype)
        dy = dy * g[:, None].astype(h.dtype)
        dpre = (dy @ w_down[e].T) * (2 * relu)
        dw_down = dw_down.at[e].add(act.T @ dy)
        dw_up = dw_up.at[e].add(x.T @ dpre)
        dh = dh.at[tok].add(dpre @ w_up[e].T, mode="drop",
                            unique_indices=True)
        dgate = jax.lax.dynamic_update_slice_in_dim(dgate, dg, i * tile, 0)
        return i + 1, dh, dw_up, dw_down, dgate

    _, dh, dw_up, dw_down, dgate = jax.lax.while_loop(
        lambda c: c[0] < n_tiles, body,
        (jnp.int32(0), jnp.zeros_like(h), jnp.zeros_like(w_up),
         jnp.zeros_like(w_down), jnp.zeros_like(gate)))
    return dh, dw_up, dw_down, None, dgate, None, None


expert_mlp.defvjp(_fwd, _bwd)
