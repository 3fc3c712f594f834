"""Plain reference for the decoder of the DeepSeek-V3 block at GLM's sizes
(``ModelType: hybrid_lm`` under the public ``glm4_moe_lite`` keys): latent
attention, a leading dense gated feed-forward, sigmoid-scored gated experts
beside a gated shared expert, and one multi-token prediction module in the
loss.  Written from the equations, ``x`` a block's input:

    h = x + MLA(RMSNorm x),    y = h + FFN_l(RMSNorm h)

- ``MLA``: ``c_q = RMSNorm(x W_qa)``; ``[q_n,h ; q_r,h] = c_q W_qb``, split
  ``qk_nope_head_dim | qk_rope_head_dim`` a head.  ``[c_kv ; k_r] = x
  W_kva``, split ``kv_lora_rank | qk_rope_head_dim``; ``[k_n,h ; v_h] =
  RMSNorm(c_kv) W_kvb``, split ``qk_nope_head_dim | v_head_dim`` a head.
  ``k_r`` has no head axis.  Rotary on ``q_r,h`` and ``k_r`` alone,
  ``rope_theta^(-m / (d_r / 2))``, rotate-half pairing.  ``o_h = softmax(
  [q_n,h ; rot q_r,h] [k_n,h ; rot k_r]^T / sqrt(d_n + d_r) + causal) v_h``;
  the heads side by side times ``W_o``.
- ``FFN`` of the first ``first_k_dense_replace`` blocks: ``W_down(silu(W_gate
  h) * W_up h)``.  Of the others: ``s = sigmoid(h W_r)`` over ALL
  ``n_routed_experts``, the ``num_experts_per_tok`` largest of ``s + b``,
  ``w = routed_scaling_factor x s_top / sum s_top``, the gated experts
  weighted by ``w``, plus the gated shared expert, unscaled.
- the multi-token prediction module, for ``i = 0 .. S - 3`` with ``h_i``
  the last block's output (before the final norm) and ``t`` the ids:
  ``u_i = [RMSNorm_h(h_i) ; RMSNorm_e(Emb(t_{i+1}))] W_m``, ``g = Block(u)``
  (one more MLA + sparse block, causal over ``i``, positions ``i``),
  ``logits_i = RMSNorm_f(g_i) W_head`` against ``t_{i+2}``; ``Emb`` and
  ``W_head`` are the main model's.
- loss = mean cross-entropy of position ``i`` against ``t_{i+1}`` over ``S
  - 1`` positions + ``mtp_loss_weight`` x the module's over ``S - 2``.

Straightforward float32 ``jax.numpy``.  Nothing is imported from the
program: the parameters come in as the program's nested dict of arrays
(names and the splits above are the only thing shared; block ``i`` is
``layers_{2i}``, its attention, and ``layers_{2i+1}``, its feed-forward;
the module is ``mtp``: ``merge/{hnorm, enorm, proj}``, ``attn``, ``ffn``,
``final_norm``).  What is deliberately *not* the program's way:

- the module runs over the ``S - 2`` positions it has targets for, not
  over the row with two positions dropped afterwards;
- attention builds the masked scores of a block of queries against ALL
  keys from the positions, softmax, times values; nothing is skipped; the
  one rotary key is an explicit repeat over the heads;
- the experts are a loop over the held ids with dense 0/1 masks;
- rotary frequencies are Python floats from the public formula;
- loss and ``jax.grad`` are written out (Adam's first step is
  ``benchmark/reference/hybrid_lm.py``'s, which the plane calls).

To fit beside the trainer at the published widths every layer, a block of
queries and each head pass are rematerialised (``jax.checkpoint``): that
changes what is stored, not what is computed.

The share: an expert layer routes over all ``n_routed_experts`` and adds
only what the experts ``experts_held`` (first id, count) give; the gate
weights are normalised over all the chosen experts, held or not; the
shared expert is whole on every chip.  The vocabulary is the slice the
embedding holds.

Departures from the public model are the configuration file's ``assumed``.
"""

from __future__ import annotations

import math

#: queries scored at a time in ``latent_attention``
QUERY_BLOCK = 128


def rms_norm(x, scale, eps):
    import jax.numpy as jnp

    return x * (1.0 / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                               + eps)) * scale


def rotate_half(u):
    import jax.numpy as jnp

    half = u.shape[-1] // 2
    return jnp.concatenate([-u[..., half:], u[..., :half]], axis=-1)


def rotary(u, theta: float):
    """(B, S, H, R) turned whole: ``u cos(p f) + rotate_half(u) sin(p f)``
    at positions ``p = 0 ..``, ``f_m = theta^(-m / (R / 2))`` repeated
    over the two halves; the angle is the float32 product."""
    import jax.numpy as jnp

    s, half = u.shape[1], u.shape[-1] // 2
    f = jnp.asarray([theta ** (-m / half) for m in range(half)], jnp.float32)
    angle = (jnp.arange(s, dtype=jnp.float32)[:, None]
             * jnp.concatenate([f, f])[None, :])
    return (u * jnp.cos(angle)[None, :, None, :]
            + rotate_half(u) * jnp.sin(angle)[None, :, None, :])


def latent_attention(p, x, cfg, heads: "int | None" = None,
                     causal: bool = True, shared_key: str = "repeat"):
    """One MLA.  The keywords after ``cfg`` build wrong models: the first
    ``heads`` heads alone; no mask; the rotary key divided among the heads
    (``shared_key="mean"``: each head gets ``k_r / heads``) or left
    unturned (``"unturned"``)."""
    import jax
    import jax.numpy as jnp

    n = int(cfg["num_attention_heads"]) if heads is None else heads
    d_c = int(cfg["kv_lora_rank"])
    d_n, d_r, d_v = (int(cfg["qk_nope_head_dim"]),
                     int(cfg["qk_rope_head_dim"]), int(cfg["v_head_dim"]))
    eps, theta = float(cfg["rms_norm_eps"]), float(cfg["rope_theta"])
    bsz, s, _ = x.shape
    c_q = rms_norm(x @ p["q_a_proj"]["kernel"], p["q_a_norm"]["scale"], eps)
    q = (c_q @ p["q_b_proj"]["kernel"][:, :n * (d_n + d_r)]).reshape(
        bsz, s, n, d_n + d_r)
    down = x @ p["kv_a_proj"]["kernel"]
    c_kv = rms_norm(down[..., :d_c], p["kv_a_norm"]["scale"], eps)
    k_r = down[..., d_c:].reshape(bsz, s, 1, d_r)
    up = (c_kv @ p["kv_b_proj"]["kernel"][:, :n * (d_n + d_v)]).reshape(
        bsz, s, n, d_n + d_v)
    k_n, v = up[..., :d_n], up[..., d_n:]
    q_r = rotary(q[..., d_n:], theta)
    if shared_key != "unturned":
        k_r = rotary(k_r, theta)
    k_r = jnp.repeat(k_r, n, axis=2)
    if shared_key == "mean":
        k_r = k_r / n
    q = jnp.concatenate([q[..., :d_n], q_r], axis=-1)
    k = jnp.concatenate([k_n, k_r], axis=-1)

    @jax.checkpoint
    def attend(blk):
        qb, start = blk
        scores = jnp.einsum("bqhd,bkhd->bhqk", qb, k) / math.sqrt(d_n + d_r)
        if causal:
            i = start + jnp.arange(qb.shape[1])[:, None]
            scores = jnp.where(jnp.arange(s)[None, :] <= i, scores, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd",
                          jax.nn.softmax(scores, axis=-1), v)

    # a block of queries at a time, one after the other (lax.map), so
    # that one block's scores exist at once, in the backward pass too
    size = min(QUERY_BLOCK, s)
    pad = -s % size
    qp = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    blocks = jnp.moveaxis(qp.reshape(bsz, -1, size, n, d_n + d_r), 1, 0)
    outs = jax.lax.map(attend, (blocks, jnp.arange(blocks.shape[0]) * size))
    y = jnp.moveaxis(outs, 0, 1).reshape(bsz, s + pad, n * d_v)[:, :s]
    return y @ p["o_proj"]["kernel"][:n * d_v]


def gated_mlp(p, x):
    """``W_down(silu(W_gate h) * W_up h)``."""
    import jax

    return (jax.nn.silu(x @ p["gate"]["kernel"])
            * (x @ p["up"]["kernel"])) @ p["down"]["kernel"]


def route(p, x, cfg, scaling: bool = True):
    """(chosen ids (T, k), weights (T, k)) over ALL ``n_routed_experts``:
    ``s = sigmoid(logits)``, the k largest of ``s`` + the correction bias,
    ``factor x s_top / sum s_top``."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):  # the gate is float32
        scores = jax.nn.sigmoid(x @ p["router"]["kernel"])
    _, ids = jax.lax.top_k(scores + p["e_score_correction_bias"],
                           int(cfg["num_experts_per_tok"]))
    weights = jnp.take_along_axis(scores, ids, axis=-1)
    if cfg.get("norm_topk_prob", True):
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    factor = float(cfg.get("routed_scaling_factor", 1.0))
    return ids, weights * (factor if scaling else 1.0)


def moe_layer(p, x, cfg, held=None, scaling: bool = True,
              shared: bool = True):
    """``sum_e w_e E_e(h)`` over the chosen experts among ``held`` = (first
    id, count; ``None`` takes ``cfg["experts_held"]``) plus the shared
    expert.  ``scaling=False`` builds a wrong model; ``shared=False`` also
    is how the share test counts the shared expert once."""
    import jax
    import jax.numpy as jnp

    bsz, s, d = x.shape
    flat = x.reshape(-1, d)
    ids, weights = route(p, flat, cfg, scaling)
    first, count = held if held is not None else cfg["experts_held"]
    out = jnp.zeros_like(flat)
    for local in range(int(count)):
        w = jnp.sum(jnp.where(ids == first + local, weights, 0.0), axis=-1)
        act = (jax.nn.silu(flat @ p["experts"]["gate"][local])
               * (flat @ p["experts"]["up"][local]))
        out = out + w[:, None] * (act @ p["experts"]["down"][local])
    if shared:
        out = out + gated_mlp(p["shared"], flat)
    return out.reshape(bsz, s, d)


#: the keywords with which the tests build a wrong model, by where they act
WRONG = {"attention": ("heads", "causal", "shared_key"),
         "experts": ("scaling", "shared"),
         "mtp": ("mtp", "mtp_weight", "mtp_embed_shift", "mtp_target_shift",
                 "mtp_normed", "mtp_swapped")}


def block(attn, ffn, x, cfg, dense: bool, attn_kw: dict, moe_kw: dict):
    """One block from its two layers' parameters."""
    import jax

    eps = float(cfg["rms_norm_eps"])

    @jax.checkpoint
    def attention(p, x):
        return x + latent_attention(
            p["mixer"], rms_norm(x, p["norm"]["scale"], eps), cfg, **attn_kw)

    @jax.checkpoint
    def feed_forward(p, x):
        h = rms_norm(x, p["norm"]["scale"], eps)
        if dense:
            return x + gated_mlp(p["mixer"], h)
        return x + moe_layer(p["mixer"], h, cfg, **moe_kw)

    return feed_forward(ffn, attention(attn, x))


def last_block_output(params, ids, cfg, wrong: dict | None = None):
    """``h`` (B, S, hidden) of integer ``ids``: the last block's output,
    before the final norm."""
    wrong = wrong or {}
    attn_kw = {k: wrong[k] for k in WRONG["attention"] if k in wrong}
    moe_kw = {k: wrong[k] for k in WRONG["experts"] if k in wrong}
    x = params["embed"]["embedding"][ids]
    for i in range(int(cfg["num_hidden_layers"])):
        x = block(params[f"layers_{2 * i}"], params[f"layers_{2 * i + 1}"],
                  x, cfg, i < int(cfg.get("first_k_dense_replace", 0)),
                  attn_kw, moe_kw)
    return x


def head_logits(params, h, norm_scale, cfg):
    return rms_norm(h, norm_scale, float(cfg["rms_norm_eps"])) @ params[
        "lm_head"]["kernel"]


def logits(params, ids, cfg, wrong: dict | None = None):
    """(B, S, vocab held): position ``i`` scores token ``i + 1``."""
    return head_logits(params, last_block_output(params, ids, cfg, wrong),
                       params["final_norm"]["scale"], cfg)


def mtp_states(params, h, ids, cfg, wrong: dict | None = None):
    """The module's block output ``g`` (B, S - 2, hidden) from the last
    block's ``h`` and the ids.  ``wrong``: ``mtp_embed_shift`` the token
    whose embedding position ``i`` reads (1: the next), ``mtp_normed``
    ``h`` taken after the main model's final norm, ``mtp_swapped`` the two
    halves of ``W_m``'s input the other way round.  (The module's first
    token stands at position 0; rotary scores see differences of positions
    only, so any other first position gives the same block.)"""
    import jax.numpy as jnp

    wrong = wrong or {}
    p, eps = params["mtp"], float(cfg["rms_norm_eps"])
    s = ids.shape[1]
    if wrong.get("mtp_normed"):
        h = rms_norm(h, params["final_norm"]["scale"], eps)
    shift = int(wrong.get("mtp_embed_shift", 1))
    e = params["embed"]["embedding"][ids[:, shift:s - 2 + shift]]
    parts = [rms_norm(h[:, :s - 2], p["merge"]["hnorm"]["scale"], eps),
             rms_norm(e, p["merge"]["enorm"]["scale"], eps)]
    if wrong.get("mtp_swapped"):
        parts.reverse()
    u = jnp.concatenate(parts, axis=-1) @ p["merge"]["proj"]["kernel"]
    attn_kw = {k: wrong[k] for k in WRONG["attention"] if k in wrong}
    moe_kw = {k: wrong[k] for k in WRONG["experts"] if k in wrong}
    return block(p["attn"], p["ffn"], u, cfg, False, attn_kw, moe_kw)


def mtp_logits(params, ids, cfg, wrong: dict | None = None):
    """(B, S - 2, vocab held): position ``i`` scores token ``i + 2``."""
    h = last_block_output(params, ids, cfg, wrong)
    return head_logits(params, mtp_states(params, h, ids, cfg, wrong),
                       params["mtp"]["final_norm"]["scale"], cfg)


def token_ids(x):
    """The rows' feature block (float32, ids as floats) -> int32 ids."""
    import jax.numpy as jnp

    return jnp.asarray(x).astype(jnp.int32)


def cross_entropy(params, h, norm_scale, targets, live, cfg):
    """Mean over the live rows' positions of ``-log softmax(logits)[t]``."""
    import jax
    import jax.numpy as jnp

    @jax.checkpoint
    def total(kernel, scale, h):
        logp = jax.nn.log_softmax(
            rms_norm(h, scale, float(cfg["rms_norm_eps"])) @ kernel, axis=-1)
        nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
        return jnp.sum(nll * live[:, None])

    count = jnp.sum(live) * targets.shape[1]
    return total(params["lm_head"]["kernel"], norm_scale, h) / jnp.maximum(
        count, 1.0)


def losses(params, batch, cfg, wrong: dict | None = None):
    """``(next-token loss, the module's loss)``; the second is 0.0 without
    ``num_nextn_predict_layers``.  ``wrong``: ``mtp_target_shift`` the
    token position ``i`` of the module is scored against (2)."""
    import jax.numpy as jnp

    wrong = wrong or {}
    ids = token_ids(batch["x"])
    live = (jnp.asarray(batch["w"]).reshape(-1) != 0.0).astype(jnp.float32)
    h = last_block_output(params, ids, cfg, wrong)
    main = cross_entropy(params, h[:, :-1], params["final_norm"]["scale"],
                         ids[:, 1:], live, cfg)
    if not int(cfg.get("num_nextn_predict_layers", 0)):
        return main, 0.0
    s = ids.shape[1]
    shift = int(wrong.get("mtp_target_shift", 2))
    g = mtp_states(params, h, ids, cfg, wrong)
    return main, cross_entropy(params, g, params["mtp"]["final_norm"]["scale"],
                               ids[:, shift:s - 2 + shift], live, cfg)


def loss(params, batch, cfg, wrong: dict | None = None):
    """What the step differentiates: the next-token loss + ``mtp_loss_weight``
    x the module's.  ``wrong``: ``mtp=False`` drops the term,
    ``mtp_weight`` takes another weight."""
    wrong = wrong or {}
    main, ahead = losses(params, batch, cfg, wrong)
    if not wrong.get("mtp", True):
        return main
    weight = float(wrong.get("mtp_weight", cfg.get("mtp_loss_weight", 0.3)))
    return main + weight * ahead


def make_loss(cfg, precision: str = "highest", with_grad: bool = False,
              wrong: dict | None = None):
    """Jitted ``(params, batch) -> loss`` (or ``(loss, grads)``) at a matmul
    precision: ``highest`` is the truth, ``default`` what a configuration
    that states float32 at the TPU's default precision asks for."""
    import jax

    def fn(params, batch):
        with jax.default_matmul_precision(precision):
            if with_grad:
                return jax.value_and_grad(loss)(params, batch, cfg, wrong)
            return loss(params, batch, cfg, wrong)

    return jax.jit(fn)
