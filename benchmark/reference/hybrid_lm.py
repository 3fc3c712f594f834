"""Plain reference for the hybrid decoder family (``ModelType: hybrid_lm``):
Mamba-2 mixers, routed + shared relu² experts, causal grouped-query
attention, one mixer a layer in a pre-norm residual, chosen by a pattern
string (``M`` / ``E`` / ``*``), as the public ``nemotron_h`` configuration
describes it.

Straightforward float32 ``jax.numpy``.  Nothing is imported from the
program: the parameters come in as the program's nested dict of arrays
(names are the only thing shared).  What is deliberately *not* the
program's way of computing:

- the state-space recurrence is a ``lax.scan`` over single time steps
  (``H_t = exp(dt_t A) H_{t-1} + dt_t x_t (x) B_t``, ``y_t = H_t C_t + D x_t``),
  not the chunked form; state products are element-wise multiplies and
  sums, so the matmul precision does not touch them;
- the experts are a loop over the held ids with dense 0/1 masks: every
  token goes through every held expert and the gate weight (0 where the
  token did not choose it) multiplies the result;
- attention builds the masked scores of a block of queries against all
  keys, softmax, times values; the grouped heads are an explicit repeat;
- loss, ``jax.grad`` and Adam's first step are written out.

To fit beside the trainer at the published widths the layers are
rematerialised (``jax.checkpoint`` a layer, and a block of time steps in
the scan): that changes what is stored, not what is computed.

The share: an expert layer routes over all ``n_routed_experts`` and adds
only what the experts ``held`` (first id, count) give; the gate weights
are normalised over all the chosen experts, held or not.  The vocabulary
is the slice the embedding holds.

Departures from the public model, as the configuration file notes under
``assumed``: no rotary embedding (the public ``nemotron_h`` modelling code
applies none); ``e_score_correction_bias`` is a parameter that no
gradient reaches (it only picks experts).
"""

from __future__ import annotations

import math

#: queries scored at a time in ``attention_mixer``
QUERY_BLOCK = 512
#: time steps rematerialised together in ``ssm_recurrence``
TIME_BLOCK = 64


def rms_norm(x, scale, eps):
    import jax.numpy as jnp

    return x * (1.0 / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                               + eps)) * scale


def relu2(x):
    import jax.numpy as jnp

    return jnp.square(jnp.maximum(x, 0.0))


def ssm_recurrence(x, dt, a, b, c):
    """``y_t = H_t C_t`` with ``H_t = exp(dt_t a) H_{t-1} + dt_t x_t (x) B_t``.

    x (B, S, h, p), dt (B, S, h), a (h,), b and c (B, S, h, n); state
    (B, h, p, n), zero at t = 0.  One time step at a time."""
    import jax
    import jax.numpy as jnp

    bsz, s, h, p = x.shape
    n = b.shape[-1]
    pad = -s % TIME_BLOCK
    if pad:  # dt = 0: the state passes through, the rows are dropped
        x, dt, b, c = (jnp.pad(v, [(0, 0), (0, pad)] + [(0, 0)] * (v.ndim - 2))
                       for v in (x, dt, b, c))

    def one(hstate, t):
        xt, dtt, bt, ct = t
        decay = jnp.exp(dtt * a)[..., None, None]
        hstate = decay * hstate + (dtt[..., None] * xt)[..., None] \
            * bt[..., None, :]
        return hstate, jnp.sum(hstate * ct[..., None, :], axis=-1)

    @jax.checkpoint
    def block(hstate, ts):
        return jax.lax.scan(one, hstate, ts)

    def blocks(v):  # (B, S, ...) -> (S / T, T, B, ...)
        v = jnp.moveaxis(v, 1, 0)
        return v.reshape((-1, TIME_BLOCK) + v.shape[1:])

    h0 = jnp.zeros((bsz, h, p, n), jnp.float32)
    _, y = jax.lax.scan(block, h0, tuple(map(blocks, (x, dt, b, c))))
    y = y.reshape((-1,) + y.shape[2:])
    return jnp.moveaxis(y, 0, 1)[:, :s]


def mamba_mixer(p, x, cfg, drop_d_term: bool = False):
    import jax
    import jax.numpy as jnp

    heads, hd = int(cfg["mamba_num_heads"]), int(cfg["mamba_head_dim"])
    groups, n = int(cfg["n_groups"]), int(cfg["ssm_state_size"])
    k = int(cfg["conv_kernel"])
    inner = heads * hd
    bsz, s, _ = x.shape
    zxbcdt = x @ p["in_proj"]["kernel"]
    z = zxbcdt[..., :inner]
    xbc = zxbcdt[..., inner:2 * inner + 2 * groups * n]
    dt = zxbcdt[..., 2 * inner + 2 * groups * n:]
    # causal depthwise conv: out_t = bias + sum_j w[j] * in_{t - (k-1) + j}
    w, bias = p["conv"]["kernel"], p["conv"]["bias"]
    padded = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
    conv = bias
    for j in range(k):
        conv = conv + padded[:, j:j + s] * w[j]
    xbc = jax.nn.silu(conv)
    xs = xbc[..., :inner].reshape(bsz, s, heads, hd)
    b = xbc[..., inner:inner + groups * n].reshape(bsz, s, groups, n)
    c = xbc[..., inner + groups * n:].reshape(bsz, s, groups, n)
    b = jnp.repeat(b, heads // groups, axis=2)
    c = jnp.repeat(c, heads // groups, axis=2)
    dt = jax.nn.softplus(dt + p["dt_bias"])
    a = -jnp.exp(p["A_log"])
    y = ssm_recurrence(xs, dt, a, b, c)
    if not drop_d_term:
        y = y + p["D"][:, None] * xs
    y = y.reshape(bsz, s, inner) * jax.nn.silu(z)
    # grouped RMSNorm, the gate before the norm
    g = y.reshape(bsz, s, groups, inner // groups)
    g = g * (1.0 / jnp.sqrt(jnp.mean(g * g, axis=-1, keepdims=True)
                            + float(cfg["layer_norm_epsilon"])))
    y = g.reshape(bsz, s, inner) * p["norm"]["scale"]
    return y @ p["out_proj"]["kernel"]


def route(p, x, cfg, renormalise: bool = True):
    """(chosen ids (T, k), weights (T, k)) over ALL routed experts."""
    import jax
    import jax.numpy as jnp

    k = int(cfg["num_experts_per_tok"])
    with jax.default_matmul_precision("highest"):  # the gate is float32
        scores = jax.nn.sigmoid(x @ p["router"]["kernel"])
    _, ids = jax.lax.top_k(scores + p["e_score_correction_bias"], k)
    weights = jnp.take_along_axis(scores, ids, axis=-1)
    if renormalise and cfg.get("norm_topk_prob", True):
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-20)
    return ids, weights * float(cfg["routed_scaling_factor"])


def moe_mixer(p, x, cfg, held=None, drop_shared: bool = False,
              renormalise: bool = True):
    """Routed part of the experts ``held`` = (first id, count) plus the
    shared expert.  ``held=None`` takes ``cfg["experts_held"]``."""
    import jax.numpy as jnp

    bsz, s, d = x.shape
    flat = x.reshape(-1, d)
    ids, weights = route(p, flat, cfg, renormalise)
    first, count = held if held is not None else cfg["experts_held"]
    out = jnp.zeros_like(flat)
    for local in range(int(count)):
        gate = jnp.sum(jnp.where(ids == first + local, weights, 0.0), axis=-1)
        up = p["experts"]["up"][local]
        down = p["experts"]["down"][local]
        out = out + gate[:, None] * (relu2(flat @ up) @ down)
    if not drop_shared:
        out = out + relu2(flat @ p["shared"]["up"]["kernel"]) \
            @ p["shared"]["down"]["kernel"]
    return out.reshape(bsz, s, d)


def attention_mixer(p, x, cfg, causal: bool = True):
    import jax
    import jax.numpy as jnp

    nq, nkv = int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"])
    hd = int(cfg["head_dim"])
    bsz, s, _ = x.shape
    q = (x @ p["q_proj"]["kernel"]).reshape(bsz, s, nq, hd)
    k = (x @ p["k_proj"]["kernel"]).reshape(bsz, s, nkv, hd)
    v = (x @ p["v_proj"]["kernel"]).reshape(bsz, s, nkv, hd)
    k = jnp.repeat(k, nq // nkv, axis=2)
    v = jnp.repeat(v, nq // nkv, axis=2)

    @jax.checkpoint
    def attend(block):
        qb, start = block
        scores = jnp.einsum("bqhd,bkhd->bhqk", qb, k) / math.sqrt(hd)
        if causal:
            q_pos = start + jnp.arange(qb.shape[1])[:, None]
            scores = jnp.where(jnp.arange(s)[None, :] <= q_pos, scores,
                               -jnp.inf)
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


MIXERS = {"M": mamba_mixer, "E": moe_mixer, "*": attention_mixer}
#: the keywords with which the tests build a wrong model, by mixer
WRONG = {"M": ("drop_d_term",), "E": ("drop_shared", "renormalise"),
         "*": ("causal",)}


def hidden_states(params, ids, cfg, wrong: dict | None = None):
    """Final-normed hidden states (B, S, hidden) of integer ``ids``.
    ``wrong`` passes a mixer's keyword (``drop_shared``, ``causal`` ...):
    the tests build wrong models with it."""
    import jax

    wrong = wrong or {}
    eps = float(cfg["layer_norm_epsilon"])
    x = params["embed"]["embedding"][ids]
    for i, kind in enumerate(cfg["hybrid_override_pattern"]):
        p = params[f"layers_{i}"]
        kwargs = {k: wrong[k] for k in WRONG[kind] if k in wrong}

        @jax.checkpoint
        def layer(p, x, kind=kind, kwargs=kwargs):
            return x + MIXERS[kind](p["mixer"],
                                    rms_norm(x, p["norm"]["scale"], eps),
                                    cfg, **kwargs)

        x = layer(p, x)
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
