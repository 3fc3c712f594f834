"""Plain reference for the looped decoder: one stack of weight-shared
full-attention blocks with sandwich norms, run ``total_ut_steps`` times a
forward pass, each pass read by an exit gate and the head, trained under
the expected-exit loss (``ModelType: hybrid_lm`` under the public ``ouro``
keys), written from the equations of ISSUE 42.

A block, the same weights in every pass, every norm an RMSNorm with a
learned scale, no bias anywhere::

    h = x + RMSNorm_2(Attn(RMSNorm_1(x)))
    y = h + RMSNorm_4(W_down(silu(W_gate u) * W_up u)),   u = RMSNorm_3(h)

``Attn``: causal multi-head attention, ``num_attention_heads`` query and
key/value heads of ``head_dim``, rotary over the whole head at frequencies
``rope_theta^(-m / (head_dim / 2))`` with rotate-half pairing, scores x
``head_dim^-0.5``.  The loop: ``h_0 = Emb(ids)``; for t = 1 .. T, ``h_t =
RMSNorm_final(Blocks(h_{t-1}))``, which the next pass takes in and the head
and the gate read.  At each position i with a target: ``L_t = CE(h_t W_head,
id_{i+1})``, ``lambda_t = sigmoid(h_t . w_g + b_g)``; ``p_1 = lambda_1``,
``p_t = lambda_t prod_{j<t} (1 - lambda_j)`` for 1 < t < T, ``p_T =
prod_{j<T} (1 - lambda_j)``; the loss is the mean over live positions of
``sum_t p_t L_t - beta H(p)``, ``H(p) = -sum_t p_t log p_t``, ``beta =
exit_entropy_weight``.

Straightforward float32 ``jax.numpy``.  Nothing is imported from the
program: the parameters come in as the program's nested dict of arrays
(names are the only thing shared; block ``i`` is ``layers_{2i}``, its
attention, and ``layers_{2i+1}``, its feed-forward, each with ``norm``,
``post_norm`` and ``mixer``; ``final_norm``, ``exit_gate``, ``lm_head``).
What is deliberately *not* the program's way of computing:

- the loop is written out pass by pass, the blocks inside it;
- the rotation is written out from the formulas, the frequencies with
  Python floats, ``cos`` and ``sin`` as wide as the head;
- attention builds the masked scores of a block of queries against ALL
  keys from the positions, softmax, times values; nothing is skipped;
- the exit distribution is the products of the lambdas as written, its
  entropy the sum of ``p log p``; the gate is computed on every pass;
- loss and ``jax.grad`` are written out (Adam's first step is
  ``benchmark/reference/hybrid_lm.py``'s, which the plane calls).

To fit beside the trainer at the published widths each block application
and each pass's head are rematerialised (``jax.checkpoint``), and a block
of queries at a time: that changes what is stored, not what is computed.

Departures from the public model are the configuration file's ``assumed``.
"""

from __future__ import annotations

import math

#: queries scored at a time in :func:`attention`
QUERY_BLOCK = 128


def rms_norm(x, scale, eps):
    import jax.numpy as jnp

    return x * (1.0 / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                               + eps)) * scale


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


def attention(p, x, cfg, causal: bool = True):
    """A block's attention; ``causal=False`` builds a wrong model, no
    mask."""
    import jax
    import jax.numpy as jnp

    n, hd = int(cfg["num_attention_heads"]), int(cfg["head_dim"])
    theta = float(cfg["rope_theta"])
    bsz, s, _ = x.shape
    q = apply_rope((x @ p["q_proj"]["kernel"]).reshape(bsz, s, n, hd), theta)
    k = apply_rope((x @ p["k_proj"]["kernel"]).reshape(bsz, s, n, hd), theta)
    v = (x @ p["v_proj"]["kernel"]).reshape(bsz, s, n, hd)

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

    # a block of queries at a time, one after the other (lax.map), so that
    # one block's scores exist at once, in the backward pass too
    size = min(QUERY_BLOCK, s)
    pad = -s % size
    qp = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    blocks = jnp.moveaxis(qp.reshape(bsz, -1, size, n, hd), 1, 0)
    outs = jax.lax.map(attend, (blocks, jnp.arange(blocks.shape[0]) * size))
    y = jnp.moveaxis(outs, 0, 1).reshape(bsz, s + pad, n * hd)[:, :s]
    return y @ p["o_proj"]["kernel"]


def gated_mlp(p, x):
    """``W_down(silu(W_gate u) * W_up u)``."""
    import jax

    return (jax.nn.silu(x @ p["gate"]["kernel"])
            * (x @ p["up"]["kernel"])) @ p["down"]["kernel"]


def block(p_attn, p_ffn, x, cfg, wrong: dict):
    """One block: the attention sub-layer, then the feed-forward one, each
    with its norm on the way in and, unless ``post_norms: False`` builds a
    wrong model, on the way out."""
    eps = float(cfg["rms_norm_eps"])
    post = wrong.get("post_norms", True)
    a = attention(p_attn["mixer"], rms_norm(x, p_attn["norm"]["scale"], eps),
                  cfg, wrong.get("causal", True))
    h = x + (rms_norm(a, p_attn["post_norm"]["scale"], eps) if post else a)
    f = gated_mlp(p_ffn["mixer"], rms_norm(h, p_ffn["norm"]["scale"], eps))
    return h + (rms_norm(f, p_ffn["post_norm"]["scale"], eps) if post else f)


def passes(params, ids, cfg, wrong: dict):
    """[(h_t, the last block's output before the final norm)] for t = 1 ..
    T.  ``wrong`` builds wrong models: ``passes`` another T,
    ``carry_unnormed`` the next pass reading the state before the final
    norm, ``truncated`` the gradient of earlier passes stopped (the state
    entering a pass after the first is cut off from it)."""
    import jax

    eps = float(cfg["rms_norm_eps"])
    steps = int(wrong.get("passes", cfg["total_ut_steps"]))
    blocks = len(cfg["layer_types"])

    @jax.checkpoint
    def apply_block(p_attn, p_ffn, x):
        return block(p_attn, p_ffn, x, cfg, wrong)

    x = params["embed"]["embedding"][ids]
    out = []
    for t in range(steps):
        if t and wrong.get("truncated"):
            x = jax.lax.stop_gradient(x)
        for i in range(blocks):
            x = apply_block(params[f"layers_{2 * i}"],
                            params[f"layers_{2 * i + 1}"], x)
        normed = rms_norm(x, params["final_norm"]["scale"], eps)
        out.append((normed, x))
        x = x if wrong.get("carry_unnormed") else normed
    return out


def pass_nll(h, kernel, ids):
    """Each position's next-token loss (B, S - 1) of one pass's head."""
    import jax
    import jax.numpy as jnp

    logp = jax.nn.log_softmax(h[:, :-1] @ kernel, axis=-1)
    return -jnp.take_along_axis(logp, ids[:, 1:, None], axis=-1)[..., 0]


def exit_distribution(lams, wrong: dict):
    """``p`` (T, B, S - 1) from the gates ``lams`` of every pass:
    ``lambda_t prod_{j<t} (1 - lambda_j)``, the last pass the remainder;
    ``last_takes_gate`` builds a wrong model whose last pass is
    ``lambda_T prod_{j<T} (1 - lambda_j)`` too."""
    import jax.numpy as jnp

    p, stay = [], 1.0
    for t, lam in enumerate(lams):
        if t < len(lams) - 1 or wrong.get("last_takes_gate"):
            p.append(lam * stay)
        else:
            p.append(stay * jnp.ones_like(lam))
        stay = stay * (1.0 - lam)
    return jnp.stack(p)


def exits(params, ids, cfg, wrong: dict | None = None):
    """(each pass's next-token losses (T, B, S - 1), the exit distribution
    (T, B, S - 1)).  ``gate_unnormed`` builds a wrong model whose gate
    reads the state before the final norm."""
    import jax
    import jax.numpy as jnp

    wrong = wrong or {}
    gate = params["exit_gate"]
    states, lams = passes(params, ids, cfg, wrong), []
    for normed, raw in states:
        read = raw if wrong.get("gate_unnormed") else normed
        lams.append(jax.nn.sigmoid(
            (read[:, :-1] @ gate["kernel"])[..., 0] + gate["bias"][0]))

    @jax.checkpoint
    def head(normed):
        return pass_nll(normed, params["lm_head"]["kernel"], ids)

    # a pass's head at a time (lax.map), so that one pass's scores exist at
    # once, in the backward pass too
    nll = jax.lax.map(head, jnp.stack([normed for normed, _ in states]))
    return nll, exit_distribution(lams, wrong)


def token_ids(x):
    """The rows' feature block (float32, ids as floats) -> int32 ids."""
    import jax.numpy as jnp

    return jnp.asarray(x).astype(jnp.int32)


def loss(params, batch, cfg, wrong: dict | None = None):
    """The mean over the live rows' positions of ``sum_t p_t L_t - beta
    H(p)``.  ``wrong`` builds wrong models beside those of :func:`exits`:
    ``beta`` another weight of the entropy, ``last_only`` the last pass's
    loss alone, ``detach_p`` the exit distribution cut off from the
    gradient."""
    import jax
    import jax.numpy as jnp

    wrong = wrong or {}
    ids = token_ids(batch["x"])
    nll, p = exits(params, ids, cfg, wrong)
    if wrong.get("detach_p"):
        p = jax.lax.stop_gradient(p)
    entropy = -jnp.sum(p * jnp.log(p), axis=0)
    beta = float(wrong.get("beta", cfg["exit_entropy_weight"]))
    each = (nll[-1] if wrong.get("last_only")
            else jnp.sum(p * nll, axis=0) - beta * entropy)
    live = (jnp.asarray(batch["w"]).reshape(-1) != 0.0).astype(jnp.float32)
    count = jnp.sum(live) * each.shape[1]
    return jnp.sum(each * live[:, None]) / jnp.maximum(count, 1.0)


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
