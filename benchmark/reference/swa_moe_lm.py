"""Plain reference for the sliding-window + full attention, gated-expert
decoder (``ModelType: hybrid_lm`` under the public ``mellum`` keys): blocks
``h = x + Attn(RMSNorm(x))``, ``y = h + MoE(RMSNorm(h))``, grouped-query
attention with rotary positions (plain on the ``sliding_attention``
layers, YaRN on the ``full_attention`` ones), softmax-scored top-k gated
experts with no shared expert, a final RMSNorm and an untied head.

Straightforward float32 ``jax.numpy``.  Nothing is imported from the
program: the parameters come in as the program's nested dict of arrays
(names are the only thing shared; block ``i`` is ``layers_{2i}``, its
attention, and ``layers_{2i+1}``, its experts).  What is deliberately
*not* the program's way of computing:

- rotary: the frequencies are written out from the public formulas
  (:func:`rope_frequencies`), ``cos`` and ``sin`` are full-width tables and
  the rotation is ``u cos + rotate_half(u) sin`` as published;
- attention builds the masked scores of a block of queries against ALL
  keys from the positions (``j <= i`` and, on a sliding layer,
  ``i - j < sliding_window``), softmax, times values; nothing is skipped;
  the grouped heads are an explicit repeat;
- the experts are a loop over the held ids with dense 0/1 masks: every
  token goes through every held expert and the gate weight (0 where the
  token did not choose it) multiplies the result;
- loss, ``jax.grad`` and Adam's first step are written out.

To fit beside the trainer at the published widths the layers are
rematerialised (``jax.checkpoint`` a layer and a block of queries): that
changes what is stored, not what is computed.

The share: an expert layer routes over all ``num_experts`` and adds only
what the experts ``held`` (first id, count) give; the gate weights are
normalised over all the chosen experts, held or not.  The vocabulary is
the slice the embedding holds.

Departures from the public model are the configuration file's ``assumed``:
rotate-half pairing, no QK-norm and no bias, softmax before top-k, no
auxiliary loss, no MTP head.
"""

from __future__ import annotations

import math

#: queries scored at a time in ``attention_layer``
QUERY_BLOCK = 256


def rms_norm(x, scale, eps):
    import jax.numpy as jnp

    return x * (1.0 / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                               + eps)) * scale


def yarn_correction_range(rope: dict, head_dim: int):
    """``(low, high)``: the floor of the dimension that turns ``beta_fast``
    times over the original context and the ceiling of the one that turns
    ``beta_slow`` times, ``c(r) = (D/2) ln(L / (2 pi r)) / ln theta``."""
    def c(r):
        return (head_dim / 2) * math.log(
            float(rope["original_max_position_embeddings"])
            / (2 * math.pi * r)) / math.log(float(rope["rope_theta"]))

    low = math.floor(c(float(rope.get("beta_fast", 32))))
    high = math.ceil(c(float(rope.get("beta_slow", 1))))
    return max(low, 0), min(high, head_dim - 1)


def rope_frequencies(rope: dict, head_dim: int, yarn: bool = True,
                     attention_factor: bool = True):
    """``(f (D/2,) as Python floats, a)``: ``f_m = theta^(-m / (D/2))`` and
    ``a = 1``; under ``rope_type: yarn`` ``f_m = (1 - g_m) b_m / factor +
    g_m b_m``, ``g_m = 1 - clip((m - low) / (high - low), 0, 1)``, and
    ``a = attention_factor`` (``0.1 ln factor + 1`` where not given).
    ``yarn=False`` / ``attention_factor=False`` build the wrong models."""
    half = head_dim // 2
    theta = float(rope["rope_theta"])
    base = [theta ** (-m / half) for m in range(half)]
    if rope.get("rope_type", "default") != "yarn":
        return base, 1.0
    factor = float(rope["factor"])
    a = float(rope.get("attention_factor")
              or 0.1 * math.log(factor) + 1.0)
    if not attention_factor:
        a = 1.0
    if not yarn:
        return base, a
    low, high = yarn_correction_range(rope, head_dim)
    freqs = []
    for m, b in enumerate(base):
        ramp = min(max((m - low) / max(high - low, 1e-3), 0.0), 1.0)
        g = 1.0 - ramp
        freqs.append((1.0 - g) * b / factor + g * b)
    return freqs, a


def rotate_half(u):
    import jax.numpy as jnp

    half = u.shape[-1] // 2
    return jnp.concatenate([-u[..., half:], u[..., :half]], axis=-1)


def apply_rope(u, freqs, a):
    """``a (u cos(p f) + rotate_half(u) sin(p f))`` for (B, S, H, D), ``f``
    repeated over the two halves; the angle is the float32 product."""
    import jax.numpy as jnp

    s = u.shape[1]
    f = jnp.asarray(freqs, jnp.float32)
    angle = jnp.arange(s, dtype=jnp.float32)[:, None] * jnp.concatenate(
        [f, f])[None, :]
    cos = (jnp.cos(angle) * a)[None, :, None, :]
    sin = (jnp.sin(angle) * a)[None, :, None, :]
    return u * cos + rotate_half(u) * sin


def attention_layer(p, x, cfg, kind: str, causal: bool = True,
                    window: bool = True, rope: bool = True,
                    yarn: bool = True, attention_factor: bool = True):
    """One attention layer of ``kind`` ``sliding_attention`` or
    ``full_attention``.  The keywords after ``kind`` build wrong models."""
    import jax
    import jax.numpy as jnp

    nq, nkv = int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"])
    hd = int(cfg["head_dim"])
    bsz, s, _ = x.shape
    q = (x @ p["q_proj"]["kernel"]).reshape(bsz, s, nq, hd)
    k = (x @ p["k_proj"]["kernel"]).reshape(bsz, s, nkv, hd)
    v = (x @ p["v_proj"]["kernel"]).reshape(bsz, s, nkv, hd)
    params = (cfg.get("rope_parameters") or {}).get(kind)
    if rope and params is not None:
        freqs, a = rope_frequencies(params, hd, yarn, attention_factor)
        q, k = apply_rope(q, freqs, a), apply_rope(k, freqs, a)
    k = jnp.repeat(k, nq // nkv, axis=2)
    v = jnp.repeat(v, nq // nkv, axis=2)
    span = (int(cfg["sliding_window"])
            if window and kind == "sliding_attention" else None)

    @jax.checkpoint
    def attend(block):
        qb, start = block
        scores = jnp.einsum("bqhd,bkhd->bhqk", qb, k) / math.sqrt(hd)
        i = start + jnp.arange(qb.shape[1])[:, None]
        j = jnp.arange(s)[None, :]
        seen = jnp.ones((qb.shape[1], s), bool)
        if causal:
            seen = seen & (j <= i)
        if span is not None:
            seen = seen & (i - j < span)
        scores = jnp.where(seen, scores, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd",
                          jax.nn.softmax(scores, axis=-1), v)

    # a block of queries at a time, one after the other (lax.map), so
    # that one block's scores exist at once, in the backward pass too
    blk = min(QUERY_BLOCK, s)
    pad = -s % blk
    qp = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    blocks = jnp.moveaxis(qp.reshape(bsz, -1, blk, nq, hd), 1, 0)
    outs = jax.lax.map(attend, (blocks, jnp.arange(blocks.shape[0]) * blk))
    y = jnp.moveaxis(outs, 0, 1).reshape(bsz, s + pad, nq * hd)[:, :s]
    return y @ p["o_proj"]["kernel"]


def route(p, x, cfg, renormalise: bool = True, softmax: bool = True):
    """(chosen ids (T, k), weights (T, k)) over ALL ``num_experts``:
    softmax over the router's logits, the k largest, ``p / sum p``."""
    import jax
    import jax.numpy as jnp

    k = int(cfg["num_experts_per_tok"])
    with jax.default_matmul_precision("highest"):  # the gate is float32
        logits = x @ p["router"]["kernel"]
    scores = (jax.nn.softmax(logits, axis=-1) if softmax
              else jax.nn.sigmoid(logits))
    _, ids = jax.lax.top_k(scores, k)
    weights = jnp.take_along_axis(scores, ids, axis=-1)
    if renormalise and cfg.get("norm_topk_prob", True):
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return ids, weights * float(cfg.get("routed_scaling_factor", 1.0))


def moe_layer(p, x, cfg, held=None, renormalise: bool = True,
              softmax: bool = True, gate: bool = True):
    """``sum_e w_e W_down,e (silu(W_gate,e h) * W_up,e h)`` over the chosen
    experts among ``held`` = (first id, count); ``None`` takes
    ``cfg["experts_held"]``.  No shared expert."""
    import jax
    import jax.numpy as jnp

    bsz, s, d = x.shape
    flat = x.reshape(-1, d)
    ids, weights = route(p, flat, cfg, renormalise, softmax)
    first, count = held if held is not None else cfg["experts_held"]
    out = jnp.zeros_like(flat)
    for local in range(int(count)):
        w = jnp.sum(jnp.where(ids == first + local, weights, 0.0), axis=-1)
        act = flat @ p["experts"]["up"][local]
        if gate:
            act = jax.nn.silu(flat @ p["experts"]["gate"][local]) * act
        out = out + w[:, None] * (act @ p["experts"]["down"][local])
    return out.reshape(bsz, s, d)


#: the keywords with which the tests build a wrong model, by layer
WRONG = {"attention": ("causal", "window", "rope", "yarn",
                       "attention_factor"),
         "experts": ("renormalise", "softmax", "gate")}


def hidden_states(params, ids, cfg, wrong: dict | None = None):
    """Final-normed hidden states (B, S, hidden) of integer ``ids``.
    ``wrong`` passes a layer's keyword (``window``, ``gate`` ...)."""
    import jax

    wrong = wrong or {}
    eps = float(cfg["rms_norm_eps"])
    sparse = cfg.get("mlp_layer_types") or ["sparse"] * len(
        cfg["layer_types"])
    if set(sparse) != {"sparse"}:
        raise ValueError("the reference has sparse feed-forward layers only")
    x = params["embed"]["embedding"][ids]
    for i, kind in enumerate(cfg["layer_types"]):
        attn_kw = {k: wrong[k] for k in WRONG["attention"] if k in wrong}
        moe_kw = {k: wrong[k] for k in WRONG["experts"] if k in wrong}

        @jax.checkpoint
        def attention(p, x, kind=kind, kw=attn_kw):
            return x + attention_layer(
                p["mixer"], rms_norm(x, p["norm"]["scale"], eps), cfg, kind,
                **kw)

        @jax.checkpoint
        def experts(p, x, kw=moe_kw):
            return x + moe_layer(
                p["mixer"], rms_norm(x, p["norm"]["scale"], eps), cfg, **kw)

        x = attention(params[f"layers_{2 * i}"], x)
        x = experts(params[f"layers_{2 * i + 1}"], x)
    return rms_norm(x, params["final_norm"]["scale"], eps)


def token_ids(x):
    """The rows' feature block (float32, ids as floats) -> int32 ids."""
    import jax.numpy as jnp

    return jnp.asarray(x).astype(jnp.int32)


def loss(params, batch, cfg, wrong: dict | None = None, shift: int = 1):
    """Mean next-token cross-entropy over the positions of the rows whose
    weight is not 0: position t predicts the id at t + ``shift``."""
    import jax
    import jax.numpy as jnp

    ids = token_ids(batch["x"])
    h = hidden_states(params, ids, cfg, wrong)
    logits = h[:, :-shift] @ params["lm_head"]["kernel"]
    logp = jax.nn.log_softmax(logits, axis=-1)
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


def adam_first_move(grad, lr: float, b1: float = 0.9, b2: float = 0.999,
                    eps: float = 1e-8):
    """What Adam's first step (moments at zero, bias-corrected) adds to a
    parameter: ``m = (1-b1) g``, ``v = (1-b2) g^2``, corrected to ``g`` and
    ``g^2``, so ``-lr g / (|g| + eps)``."""
    import jax.numpy as jnp

    m_hat = (1 - b1) * grad / (1 - b1)
    v_hat = (1 - b2) * grad * grad / (1 - b2)
    return -lr * m_hat / (jnp.sqrt(v_hat) + eps)


def adam_first_moment(grad, b1: float = 0.9):
    """Adam's first moment after its first step from zero: ``(1-b1) g``,
    the gradient with its magnitude, which the move above divides out."""
    return (1 - b1) * grad
