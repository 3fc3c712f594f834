"""Plain reference for the decoder whose blocks mix the sequence by a gated
short convolution or by grouped-query attention with a norm on every
head's q and k, over a leading dense gated feed-forward and sigmoid-scored
gated experts with no shared expert, the head tied to the embedding
(``ModelType: hybrid_lm`` under the public ``lfm2_moe`` keys), written from
the equations of ISSUE 38:

    h = x + Op(RMSNorm_op(x)),   y = h + FFN(RMSNorm_ffn(h))

for every block, one more RMSNorm after the last, logits = that x Emb^T.

- ``Op`` of a ``conv`` block: ``[B ; C ; u] = x W_in`` (hidden -> 3 x
  hidden, three contiguous thirds in that order); ``g = B * u``; ``c_t =
  sum_{j=0..K-1} w_j * g_{t-(K-1)+j}`` with ``g`` zero before the row's
  first token (``K = conv_L_cache`` taps a channel, ``w_{K-1}`` the tap on
  the present token, no bias, no activation); ``out = (C * c) W_out``.
- ``Op`` of a ``full_attention`` block: q as ``num_attention_heads`` heads
  and k, v as ``num_key_value_heads`` heads of ``hidden_size /
  num_attention_heads`` dimensions; ``q <- RMSNorm_q(q)``, ``k <-
  RMSNorm_k(k)`` over each head's dimensions, one learned scale for all
  query heads and one for all key heads, BEFORE the rotation; rotary over
  the whole head (plain frequencies ``theta^(-m / (D/2))``, rotate-half
  pairing); causal softmax attention over every earlier key, scores x
  ``D^-0.5``, KV head ``g`` serving query heads ``g n/kv .. (g+1) n/kv -
  1``; ``W_o``.
- ``FFN`` of the first ``num_dense_layers`` blocks: ``W_2(silu(W_1 h) *
  W_3 h)``.
- ``FFN`` of the others: ``s = sigmoid(h W_r)`` over ALL ``num_experts``
  (the gate product at full float32 precision), the ``num_experts_per_tok``
  largest of ``s + b`` (``b`` the expert bias, which rests at zero), ``w =
  factor x s_top / sum s_top``, ``sum_k w_k W_2^e(silu(W_1^e h) * W_3^e
  h)``; no shared expert.

Straightforward float32 ``jax.numpy``.  Nothing is imported from the
program: the parameters come in as the program's nested dict of arrays
(names are the only thing shared; block ``i`` is ``layers_{2i}``, its
operator, and ``layers_{2i+1}``, its feed-forward; ``W_1``, ``W_3``,
``W_2`` are ``gate``, ``up``, ``down``).  What is deliberately *not* the
program's way of computing:

- the convolution is its ``K`` shifted products written out, each shift a
  concatenation of zeros and the earlier tokens;
- the head norms and the rotation are written out from the formulas, the
  frequencies with Python floats, ``cos`` and ``sin`` as wide as the head;
- attention builds the masked scores of a block of queries against ALL
  keys from the positions, softmax, times values; nothing is skipped; the
  grouped heads are an explicit repeat;
- the experts are a loop over the held ids with dense 0/1 masks: every
  token goes through every held expert and the gate weight (0 where the
  token did not choose it) multiplies the result;
- the tied head is ``Emb^T``; loss and ``jax.grad`` are written out
  (Adam's first step is ``benchmark/reference/hybrid_lm.py``'s, which the
  plane calls).

To fit beside the trainer at the published widths the layers are
rematerialised (``jax.checkpoint`` a layer and a block of queries): that
changes what is stored, not what is computed.

The share: an expert layer routes over all ``num_experts`` and adds only
what the experts ``held`` (first id, count) give; the gate weights are
normalised over all the chosen experts, held or not.  The vocabulary is
the slice the embedding holds.

Departures from the public model are the configuration file's ``assumed``.
"""

from __future__ import annotations

import math

#: queries scored at a time in ``attention_operator``
QUERY_BLOCK = 128
CONV = "conv"


def rms_norm(x, scale, eps):
    import jax.numpy as jnp

    return x * (1.0 / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                               + eps)) * scale


def shifted(g, by: int):
    """``out_t = g_{t - by}`` along a row (axis 1), zero where ``t - by``
    lies outside it: ``by > 0`` reads earlier tokens, ``by < 0`` later
    ones."""
    import jax.numpy as jnp

    if by == 0:
        return g
    zeros = jnp.zeros_like(g[:, :abs(by)])
    if by > 0:
        return jnp.concatenate([zeros, g[:, :-by]], axis=1)
    return jnp.concatenate([g[:, -by:], zeros], axis=1)


def conv_operator(p, x, cfg, shift: int = 0, swap_bc: bool = False,
                  gate_b: bool = True, activation: bool = False,
                  fourth_tap: bool = False, bias: float = 0.0):
    """A ``conv`` block's operator.  The keywords after ``cfg`` build wrong
    models: every tap reading ``shift`` tokens later (+1: the future; -1:
    lagging), ``B`` and ``C`` exchanged, the gate ``B`` left out, a SiLU
    after the taps, one more tap (``w_0`` again, on the token before the
    first tap's), a bias on every channel."""
    import jax

    d, taps = int(cfg["hidden_size"]), int(cfg.get("conv_L_cache", 3))
    bcu = x @ p["in_proj"]["kernel"]
    b, c, u = bcu[..., :d], bcu[..., d:2 * d], bcu[..., 2 * d:]
    if swap_bc:
        b, c = c, b
    g = b * u if gate_b else u
    w = p["conv"]["kernel"]  # (taps, hidden): w[taps - 1] the present token
    conv = sum(w[j] * shifted(g, taps - 1 - j - shift) for j in range(taps))
    if fourth_tap:
        conv = conv + w[0] * shifted(g, taps - shift)
    conv = conv + bias
    if activation:
        conv = jax.nn.silu(conv)
    return (c * conv) @ p["out_proj"]["kernel"]


def rotate_half(u):
    import jax.numpy as jnp

    half = u.shape[-1] // 2
    return jnp.concatenate([-u[..., half:], u[..., :half]], axis=-1)


def apply_rope(u, theta: float):
    """(B, S, H, D) turned over the whole head: ``u cos(p f) +
    rotate_half(u) sin(p f)``, ``f_m = theta^(-m / (D/2))`` repeated over
    the two halves; the angle is the float32 product of the position and
    the float32 frequency."""
    import jax.numpy as jnp

    s, half = u.shape[1], u.shape[-1] // 2
    f = jnp.asarray([theta ** (-m / half) for m in range(half)], jnp.float32)
    angle = jnp.arange(s, dtype=jnp.float32)[:, None] * jnp.concatenate(
        [f, f])[None, :]
    return (u * jnp.cos(angle)[None, :, None, :]
            + rotate_half(u) * jnp.sin(angle)[None, :, None, :])


def head_scale(scale, heads: int, first_only: bool):
    """The learned scale of a head norm as (heads, D): the ONE scale for
    every head; ``first_only`` builds a wrong model, one scale a head of
    which the tree's is the first head's and the others rest at 1."""
    import jax.numpy as jnp

    if not first_only:
        return jnp.broadcast_to(scale, (heads, scale.shape[0]))
    return jnp.concatenate(
        [scale[None], jnp.ones((heads - 1, scale.shape[0]), scale.dtype)])


def attention_operator(p, x, cfg, qk_norm: bool = True,
                       norm_after_rope: bool = False,
                       scale_a_head: bool = False, wide_heads: bool = False,
                       interleaved_groups: bool = False,
                       causal: bool = True):
    """A ``full_attention`` block's operator.  The keywords after ``cfg``
    build wrong models: no head norms, the norms after the rotation, one
    scale a head, half as many heads twice as wide (the norms' scale
    repeated over the two halves), KV head ``i mod kv`` for query head
    ``i``, no mask."""
    import jax
    import jax.numpy as jnp

    nq, nkv = int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"])
    hd = int(cfg.get("head_dim") or int(cfg["hidden_size"]) // nq)
    q_scale, k_scale = p["q_norm"]["scale"], p["k_norm"]["scale"]
    if wide_heads:
        nq, nkv, hd = nq // 2, nkv // 2, 2 * hd
        q_scale, k_scale = (jnp.concatenate([s, s]) for s in (q_scale,
                                                              k_scale))
    eps = float(cfg["norm_eps"])
    theta = float(cfg["rope_parameters"]["rope_theta"])
    bsz, s, _ = x.shape
    q = (x @ p["q_proj"]["kernel"]).reshape(bsz, s, nq, hd)
    k = (x @ p["k_proj"]["kernel"]).reshape(bsz, s, nkv, hd)
    v = (x @ p["v_proj"]["kernel"]).reshape(bsz, s, nkv, hd)

    def normed(q, k):
        if not qk_norm:
            return q, k
        return (rms_norm(q, head_scale(q_scale, nq, scale_a_head), eps),
                rms_norm(k, head_scale(k_scale, nkv, scale_a_head), eps))

    if norm_after_rope:
        q, k = normed(apply_rope(q, theta), apply_rope(k, theta))
    else:
        q, k = normed(q, k)
        q, k = apply_rope(q, theta), apply_rope(k, theta)
    group = nq // nkv
    if interleaved_groups:
        k, v = jnp.tile(k, (1, 1, group, 1)), jnp.tile(v, (1, 1, group, 1))
    else:
        k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)

    @jax.checkpoint
    def attend(blk):
        qb, start = blk
        scores = jnp.einsum("bqhd,bkhd->bhqk", qb, k) / math.sqrt(hd)
        i = start + jnp.arange(qb.shape[1])[:, None]
        j = jnp.arange(s)[None, :]
        if causal:
            scores = jnp.where(j <= i, scores, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd",
                          jax.nn.softmax(scores, axis=-1), v)

    # a block of queries at a time, one after the other (lax.map), so
    # that one block's scores exist at once, in the backward pass too
    size = min(QUERY_BLOCK, s)
    pad = -s % size
    qp = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    blocks = jnp.moveaxis(qp.reshape(bsz, -1, size, nq, hd), 1, 0)
    outs = jax.lax.map(attend, (blocks, jnp.arange(blocks.shape[0]) * size))
    y = jnp.moveaxis(outs, 0, 1).reshape(bsz, s + pad, nq * hd)[:, :s]
    return y @ p["o_proj"]["kernel"]


def gated_mlp(p, x):
    """``W_2(silu(W_1 h) * W_3 h)``: ``gate``, ``up``, ``down``."""
    import jax

    return (jax.nn.silu(x @ p["gate"]["kernel"])
            * (x @ p["up"]["kernel"])) @ p["down"]["kernel"]


def route(p, x, cfg, sigmoid: bool = True, normalise: bool = True):
    """(chosen ids (T, k), weights (T, k)) over ALL ``num_experts``: ``s =
    sigmoid(h W_r)``, the k largest of ``s`` + the expert bias, ``factor x
    s_top / sum s_top``."""
    import jax
    import jax.numpy as jnp

    k = int(cfg["num_experts_per_tok"])
    with jax.default_matmul_precision("highest"):  # the gate is float32
        logits = x @ p["router"]["kernel"]
    scores = (jax.nn.sigmoid(logits) if sigmoid
              else jax.nn.softmax(logits, axis=-1))
    _, ids = jax.lax.top_k(scores + p["e_score_correction_bias"], k)
    weights = jnp.take_along_axis(scores, ids, axis=-1)
    if normalise and cfg.get("norm_topk_prob", True):
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return ids, weights * float(cfg.get("routed_scaling_factor", 1.0))


def moe_layer(p, x, cfg, held=None, sigmoid: bool = True,
              normalise: bool = True, shared: bool = False):
    """``sum_e w_e W_2^e (silu(W_1^e h) * W_3^e h)`` over the chosen
    experts among ``held`` = (first id, count; ``None`` takes
    ``cfg["experts_held"]``).  The keywords after ``held`` build wrong
    models; ``shared`` sends every token through the first held expert
    once more, unweighted, as a shared expert would be."""
    import jax
    import jax.numpy as jnp

    bsz, s, d = x.shape
    flat = x.reshape(-1, d)
    ids, weights = route(p, flat, cfg, sigmoid, normalise)
    first, count = held if held is not None else cfg["experts_held"]

    def expert(local):
        return (jax.nn.silu(flat @ p["experts"]["gate"][local])
                * (flat @ p["experts"]["up"][local])
                ) @ p["experts"]["down"][local]

    out = jnp.zeros_like(flat)
    for local in range(int(count)):
        w = jnp.sum(jnp.where(ids == first + local, weights, 0.0), axis=-1)
        out = out + w[:, None] * expert(local)
    if shared:
        out = out + expert(0)
    return out.reshape(bsz, s, d)


#: the keywords with which the tests build a wrong model, by layer
WRONG = {"conv": ("shift", "swap_bc", "gate_b", "activation", "fourth_tap",
                  "bias"),
         "attention": ("qk_norm", "norm_after_rope", "scale_a_head",
                       "wide_heads", "interleaved_groups", "causal"),
         "experts": ("sigmoid", "normalise", "shared")}


def hidden_states(params, ids, cfg, wrong: dict | None = None):
    """Final-normed hidden states (B, S, hidden) of integer ``ids``.
    ``wrong`` passes a layer's keyword (``shift``, ``sigmoid`` ...);
    ``final_norm: False`` leaves the last norm out."""
    import jax

    wrong = wrong or {}
    eps = float(cfg["norm_eps"])
    kw = {layer: {k: wrong[k] for k in names if k in wrong}
          for layer, names in WRONG.items()}
    dense = int(cfg.get("num_dense_layers", 0))
    x = params["embed"]["embedding"][ids]
    for i, kind in enumerate(cfg["layer_types"]):

        @jax.checkpoint
        def operator(p, x, kind=kind):
            h = rms_norm(x, p["norm"]["scale"], eps)
            if kind == CONV:
                return x + conv_operator(p["mixer"], h, cfg, **kw["conv"])
            return x + attention_operator(p["mixer"], h, cfg,
                                          **kw["attention"])

        @jax.checkpoint
        def feed_forward(p, x, i=i):
            h = rms_norm(x, p["norm"]["scale"], eps)
            if i < dense:
                return x + gated_mlp(p["mixer"], h)
            return x + moe_layer(p["mixer"], h, cfg, **kw["experts"])

        x = operator(params[f"layers_{2 * i}"], x)
        x = feed_forward(params[f"layers_{2 * i + 1}"], x)
    if not wrong.get("final_norm", True):
        return x
    return rms_norm(x, params["final_norm"]["scale"], eps)


def token_ids(x):
    """The rows' feature block (float32, ids as floats) -> int32 ids."""
    import jax.numpy as jnp

    return jnp.asarray(x).astype(jnp.int32)


def head_table(params, wrong: dict | None = None):
    """``Emb`` as the tied head reads it; ``untied: True`` builds a wrong
    model whose head is a copy of the table that no gradient reaches."""
    import jax

    table = params["embed"]["embedding"]
    return jax.lax.stop_gradient(table) if (wrong or {}).get("untied") \
        else table


def logits(params, ids, cfg, wrong: dict | None = None):
    """(B, S, vocab held): the final-normed states x ``Emb^T``."""
    return hidden_states(params, ids, cfg, wrong) @ head_table(
        params, wrong).T


def log_softmax(scores, wrong: dict | None = None):
    """``scores - logsumexp(scores)`` over the vocabulary held.  ``wrong``
    builds a wrong head: ``lse_max`` takes the row's largest score for the
    log-sum-exp (the softmax's tail dropped from the loss), ``lse_constant``
    holds the log-sum-exp constant in the backward pass (the same loss; the
    head's cotangent is then ``-onehot`` without the softmax term)."""
    import jax
    import jax.numpy as jnp

    wrong = wrong or {}
    if not (wrong.get("lse_max") or wrong.get("lse_constant")):
        return jax.nn.log_softmax(scores, axis=-1)
    lse = (jnp.max(scores, axis=-1, keepdims=True) if wrong.get("lse_max")
           else jax.nn.logsumexp(scores, axis=-1, keepdims=True))
    if wrong.get("lse_constant"):
        lse = jax.lax.stop_gradient(lse)
    return scores - lse


def loss(params, batch, cfg, wrong: dict | None = None, shift: int = 1):
    """Mean next-token cross-entropy over the positions of the rows whose
    weight is not 0: position t predicts the id at t + ``shift``."""
    import jax.numpy as jnp

    ids = token_ids(batch["x"])
    scores = logits(params, ids, cfg, wrong)[:, :-shift]
    logp = log_softmax(scores, wrong)
    nll = -jnp.take_along_axis(logp, ids[:, shift:, None], axis=-1)[..., 0]
    live = (jnp.asarray(batch["w"]).reshape(-1) != 0.0).astype(jnp.float32)
    count = jnp.sum(live) * nll.shape[1]
    return jnp.sum(nll * live[:, None]) / jnp.maximum(count, 1.0)


def make_loss(cfg, precision: str = "highest", with_grad: bool = False,
              wrong: dict | None = None, shift: int = 1):
    """Jitted ``(params, batch) -> loss`` (or ``(loss, grads)``) at a matmul
    precision: ``highest`` is the truth, ``default`` what a configuration
    that states float32 at the TPU's default precision asks for."""
    import jax

    def fn(params, batch):
        with jax.default_matmul_precision(precision):
            if with_grad:
                return jax.value_and_grad(loss)(params, batch, cfg, wrong,
                                                shift)
            return loss(params, batch, cfg, wrong, shift)

    return jax.jit(fn)
