"""Plain reference for the decoder whose attention layers differ in head
count and rotary by layer type, with a leading dense gated feed-forward
and sigmoid-scored small gated experts beside a gated shared expert
(``ModelType: hybrid_lm`` under the public ``laguna`` keys): blocks
``h = x + Attn(RMSNorm(x))``, ``y = h + FFN(RMSNorm(h))``, a final RMSNorm
and an untied head.

- ``Attn`` of block ``l``: ``n_l = num_attention_heads_per_layer[l]`` query
  heads over ``num_key_value_heads`` KV heads (a KV head serves ``n_l /
  kv`` of them); rotary by the layer type's ``rope_parameters`` entry over
  the FIRST ``partial_rotary_factor x head_dim`` dimensions of a head (plain
  frequencies, or YaRN's with cos and sin scaled by ``attention_factor``),
  the other dimensions passing through unchanged and unscaled; key ``j``
  visible to query ``i`` iff ``j <= i`` and, on a ``sliding_attention``
  layer, ``i - j < sliding_window``.
- ``FFN`` of a ``dense`` block: ``W_down(silu(W_gate h) * W_up h)``.
- ``FFN`` of a ``sparse`` block: ``s = sigmoid(h W_r)`` over ALL
  ``num_experts``, the ``num_experts_per_tok`` largest, ``w = factor x s_top
  / sum s_top`` (``moe_routed_scaling_factor``), the gated experts weighted
  by ``w``, plus the gated shared expert, unscaled, for every token.

Straightforward float32 ``jax.numpy``.  Nothing is imported from the
program: the parameters come in as the program's nested dict of arrays
(names are the only thing shared; block ``i`` is ``layers_{2i}``, its
attention, and ``layers_{2i+1}``, its feed-forward).  What is deliberately
*not* the program's way of computing:

- rotary: the frequencies are written out from the public formulas with
  Python floats, ``cos`` and ``sin`` are tables as wide as the part that
  turns, and that part is ``u cos + rotate_half(u) sin`` as published;
- attention builds the masked scores of a block of queries against ALL
  keys from the positions, softmax, times values; nothing is skipped; the
  grouped heads are an explicit repeat;
- the experts are a loop over the held ids with dense 0/1 masks: every
  token goes through every held expert and the gate weight (0 where the
  token did not choose it) multiplies the result;
- loss and ``jax.grad`` are written out (Adam's first step is
  ``benchmark/reference/hybrid_lm.py``'s, which the plane calls).

To fit beside the trainer at the published widths the layers are
rematerialised (``jax.checkpoint`` a layer and a block of queries): that
changes what is stored, not what is computed.

The share: an expert layer routes over all ``num_experts`` and adds only
what the experts ``held`` (first id, count) give; the gate weights are
normalised over all the chosen experts, held or not; the shared expert is
whole on every chip.  The vocabulary is the slice the embedding holds.

Departures from the public model are the configuration file's ``assumed``:
``gating: true`` read as the gated feed-forwards above and nothing more (no
gate on attention's output), sigmoid scores with normalised top-k, a
correction bias that rests at zero (``e_score_correction_bias`` is added
to the scores the choice is made by, as the program holds it), rotate-half
pairing, no QK-norm and no bias, no auxiliary loss.
"""

from __future__ import annotations

import math

#: queries scored at a time in ``attention_layer``
QUERY_BLOCK = 128
SLIDING, FULL = "sliding_attention", "full_attention"


def rms_norm(x, scale, eps):
    import jax.numpy as jnp

    return x * (1.0 / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                               + eps)) * scale


def heads_of(cfg: dict, block: int, by_type: bool = True) -> int:
    """Query heads of block ``block``'s attention."""
    per_layer = cfg.get("num_attention_heads_per_layer")
    if by_type and per_layer:
        return int(per_layer[block])
    return int(cfg["num_attention_heads"])


def rope_entry(cfg: dict, kind: str) -> "dict | None":
    """The layer type's ``rope_parameters`` entry; a number among the
    entries (``original_max_position_embeddings``) and the config's own
    ``partial_rotary_factor`` stand for an entry that states none."""
    entries = cfg.get("rope_parameters") or {}
    if kind not in entries:
        return None
    shared = {k: v for k, v in entries.items() if not isinstance(v, dict)}
    if "partial_rotary_factor" in cfg:
        shared["partial_rotary_factor"] = cfg["partial_rotary_factor"]
    return {**shared, **entries[kind]}


def yarn_correction_range(rope: dict, rotary_dim: int):
    """``(low, high)``: the floor of the dimension that turns ``beta_fast``
    times over the original context and the ceiling of the one that turns
    ``beta_slow`` times, ``c(r) = (R/2) ln(L / (2 pi r)) / ln theta`` for
    the ``R`` dimensions that turn."""
    def c(r):
        return (rotary_dim / 2) * math.log(
            float(rope["original_max_position_embeddings"])
            / (2 * math.pi * r)) / math.log(float(rope["rope_theta"]))

    low = math.floor(c(float(rope.get("beta_fast", 32))))
    high = math.ceil(c(float(rope.get("beta_slow", 1))))
    return max(low, 0), min(high, rotary_dim - 1)


def rope_frequencies(rope: dict, rotary_dim: int, yarn: bool = True):
    """``(f (R/2,) as Python floats, a)`` for the ``R = rotary_dim``
    dimensions that turn: ``f_m = theta^(-m / (R/2))`` and ``a = 1``; under
    ``rope_type: yarn`` ``f_m = (1 - g_m) b_m / factor + g_m b_m``, ``g_m =
    1 - clip((m - low) / (high - low), 0, 1)``, and ``a =
    attention_factor`` (``0.1 ln factor + 1`` where not given).
    ``yarn=False`` builds a wrong model (plain frequencies, ``a`` kept)."""
    half = rotary_dim // 2
    theta = float(rope["rope_theta"])
    base = [theta ** (-m / half) for m in range(half)]
    if rope.get("rope_type", "default") != "yarn":
        return base, 1.0
    factor = float(rope["factor"])
    a = float(rope.get("attention_factor") or 0.1 * math.log(factor) + 1.0)
    if not yarn:
        return base, a
    low, high = yarn_correction_range(rope, rotary_dim)
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


def apply_rope(u, freqs, a, scale_pass: bool = False):
    """(B, S, H, D) with its first ``R = 2 len(freqs)`` dimensions turned,
    ``a (t cos(p f) + rotate_half(t) sin(p f))`` for ``t = u[..., :R]``
    (``f`` repeated over ``t``'s two halves; the angle is the float32
    product), and ``u[..., R:]`` as it came.  ``scale_pass`` builds a wrong
    model: ``a`` on the part that passes through too."""
    import jax.numpy as jnp

    s, r = u.shape[1], 2 * len(freqs)
    f = jnp.asarray(freqs, jnp.float32)
    angle = jnp.arange(s, dtype=jnp.float32)[:, None] * jnp.concatenate(
        [f, f])[None, :]
    cos = (jnp.cos(angle) * a)[None, :, None, :]
    sin = (jnp.sin(angle) * a)[None, :, None, :]
    t, rest = u[..., :r], u[..., r:]
    return jnp.concatenate(
        [t * cos + rotate_half(t) * sin, rest * a if scale_pass else rest],
        axis=-1)


def attention_layer(p, x, cfg, block: int, heads_by_type: bool = True,
                    partial: bool = True, scale_pass: bool = False,
                    yarn: bool = True, window: "int | None" = None,
                    causal: bool = True):
    """Block ``block``'s attention.  The keywords after ``block`` build
    wrong models: one head count for every layer (the first
    ``num_attention_heads`` of the layer's heads, in groups of that many
    over the KV heads), the whole head turned, the pass-through half
    scaled, plain frequencies, another ``window``, no mask."""
    import jax
    import jax.numpy as jnp

    kind = cfg["layer_types"][block]
    nq, nkv = heads_of(cfg, block, heads_by_type), int(
        cfg["num_key_value_heads"])
    hd = int(cfg["head_dim"])
    bsz, s, _ = x.shape
    q = (x @ p["q_proj"]["kernel"][:, :nq * hd]).reshape(bsz, s, nq, hd)
    k = (x @ p["k_proj"]["kernel"]).reshape(bsz, s, nkv, hd)
    v = (x @ p["v_proj"]["kernel"]).reshape(bsz, s, nkv, hd)
    rope = rope_entry(cfg, kind)
    if rope is not None:
        share = float(rope.get("partial_rotary_factor", 1.0)) if partial \
            else 1.0
        freqs, a = rope_frequencies(rope, int(hd * share), yarn)
        q = apply_rope(q, freqs, a, scale_pass)
        k = apply_rope(k, freqs, a, scale_pass)
    k = jnp.repeat(k, nq // nkv, axis=2)
    v = jnp.repeat(v, nq // nkv, axis=2)
    span = None
    if kind == SLIDING:
        span = int(cfg["sliding_window"]) if window is None else window

    @jax.checkpoint
    def attend(blk):
        qb, start = blk
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
    size = min(QUERY_BLOCK, s)
    pad = -s % size
    qp = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    blocks = jnp.moveaxis(qp.reshape(bsz, -1, size, nq, hd), 1, 0)
    outs = jax.lax.map(attend, (blocks, jnp.arange(blocks.shape[0]) * size))
    y = jnp.moveaxis(outs, 0, 1).reshape(bsz, s + pad, nq * hd)[:, :s]
    return y @ p["o_proj"]["kernel"][:nq * hd]


def gated_mlp(p, x, gated: bool = True):
    """``W_down(silu(W_gate h) * W_up h)``; ``gated=False`` builds a wrong
    model, ``W_down relu(W_up h)^2``."""
    import jax
    import jax.numpy as jnp

    act = x @ p["up"]["kernel"]
    if gated:
        act = jax.nn.silu(x @ p["gate"]["kernel"]) * act
    else:
        act = jnp.square(jax.nn.relu(act))
    return act @ p["down"]["kernel"]


def route(p, x, cfg, sigmoid: bool = True, scaling: bool = True):
    """(chosen ids (T, k), weights (T, k)) over ALL ``num_experts``:
    ``s = sigmoid(logits)``, the k largest of ``s`` + the correction bias,
    ``factor x s_top / sum s_top``."""
    import jax
    import jax.numpy as jnp

    k = int(cfg["num_experts_per_tok"])
    with jax.default_matmul_precision("highest"):  # the gate is float32
        logits = x @ p["router"]["kernel"]
    scores = (jax.nn.sigmoid(logits) if sigmoid
              else jax.nn.softmax(logits, axis=-1))
    _, ids = jax.lax.top_k(scores + p["e_score_correction_bias"], k)
    weights = jnp.take_along_axis(scores, ids, axis=-1)
    if cfg.get("norm_topk_prob", True):
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    factor = float(cfg.get("moe_routed_scaling_factor", 1.0))
    return ids, weights * (factor if scaling else 1.0)


def moe_layer(p, x, cfg, held=None, sigmoid: bool = True,
              scaling: bool = True, shared: bool = True,
              shared_scaled: bool = False, shared_gated: bool = True):
    """``sum_e w_e W_down,e (silu(W_gate,e h) * W_up,e h)`` over the chosen
    experts among ``held`` = (first id, count; ``None`` takes
    ``cfg["experts_held"]``) plus the shared expert.  The keywords after
    ``held`` build wrong models; ``shared=False`` is also how the share
    test counts the shared expert once."""
    import jax
    import jax.numpy as jnp

    bsz, s, d = x.shape
    flat = x.reshape(-1, d)
    ids, weights = route(p, flat, cfg, sigmoid, scaling)
    first, count = held if held is not None else cfg["experts_held"]
    out = jnp.zeros_like(flat)
    for local in range(int(count)):
        w = jnp.sum(jnp.where(ids == first + local, weights, 0.0), axis=-1)
        act = (jax.nn.silu(flat @ p["experts"]["gate"][local])
               * (flat @ p["experts"]["up"][local]))
        out = out + w[:, None] * (act @ p["experts"]["down"][local])
    if shared:
        every = gated_mlp(p["shared"], flat, shared_gated)
        if shared_scaled:
            every = every * float(cfg.get("moe_routed_scaling_factor", 1.0))
        out = out + every
    return out.reshape(bsz, s, d)


#: the keywords with which the tests build a wrong model, by layer
WRONG = {"attention": ("heads_by_type", "partial", "scale_pass", "yarn",
                       "window", "causal"),
         "experts": ("sigmoid", "scaling", "shared", "shared_scaled",
                     "shared_gated"),
         "dense": ("dense_routed",)}


def hidden_states(params, ids, cfg, wrong: dict | None = None):
    """Final-normed hidden states (B, S, hidden) of integer ``ids``.
    ``wrong`` passes a layer's keyword (``window``, ``sigmoid`` ...);
    ``dense_routed`` weighs the dense layer as a router over that one
    expert would, by ``moe_routed_scaling_factor``."""
    import jax

    wrong = wrong or {}
    eps = float(cfg["rms_norm_eps"])
    attn_kw = {k: wrong[k] for k in WRONG["attention"] if k in wrong}
    moe_kw = {k: wrong[k] for k in WRONG["experts"] if k in wrong}
    dense_weight = (float(cfg.get("moe_routed_scaling_factor", 1.0))
                    if wrong.get("dense_routed") else 1.0)
    mlps = cfg.get("mlp_layer_types") or ["sparse"] * len(cfg["layer_types"])
    x = params["embed"]["embedding"][ids]
    for i, mlp in enumerate(mlps):

        @jax.checkpoint
        def attention(p, x, i=i):
            return x + attention_layer(
                p["mixer"], rms_norm(x, p["norm"]["scale"], eps), cfg, i,
                **attn_kw)

        @jax.checkpoint
        def feed_forward(p, x, mlp=mlp):
            h = rms_norm(x, p["norm"]["scale"], eps)
            if mlp == "dense":
                return x + dense_weight * gated_mlp(p["mixer"], h)
            return x + moe_layer(p["mixer"], h, cfg, **moe_kw)

        x = attention(params[f"layers_{2 * i}"], x)
        x = feed_forward(params[f"layers_{2 * i + 1}"], x)
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
