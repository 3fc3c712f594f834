"""Hybrid decoder family (``ModelType: hybrid_lm``): a causal language
model whose layers are each ONE mixer in a pre-norm residual,
``x <- x + mixer(RMSNorm(x))``, the mixer chosen per layer by a pattern
string as the public ``nemotron_h`` configuration writes it (a ``mellum``,
``laguna``, ``glm4_moe_lite`` or ``lfm2_moe`` configuration's block,
operator then feed-forward, is two such layers: config/model_config.py
``HybridLMConfig``):

- ``M``  Mamba-2 (``ssm.conv`` + ``ssm.scan``: the chunked scan by the
  path :func:`chunked_scan` picks: ops/ssm_scan.py's expression, or,
  where the static shapes say so (ops/pallas/ssd_scan.py ``ssd_pays``)
  and the program is lowered for the TPU, one kernel a direction that
  keeps a chunk's mask and the running state in VMEM; which one a
  compiled step holds is in its op names, ``ssd_scan_fwd`` and
  ``ssd_scan_bwd``), gate before the grouped RMSNorm;
- ``E``  routed experts: a score over ALL ``n_routed_experts`` (``sigmoid``
  with top-k of score + correction bias, or ``softmax`` with top-k of the
  score), weights normalised over the k chosen and scaled; the expert
  ``W_down relu(W_up h)^2`` (``hidden_act: relu2``, beside one shared
  expert of the same form) or gated, ``W_down(silu(W_gate h) * W_up h)``
  (``silu``; a shared expert, where ``n_shared_experts`` asks for one, is
  gated too, unscaled, for every token).  The layer is told which experts
  it holds (``experts_held`` = first id, count): it routes over all of
  them, computes the held experts' part for the tokens that chose them
  (ops/grouped.py: sort, grouped products, unsort) and leaves out what
  the absent experts would add.  No token is dropped;
- ``D``  the dense gated feed-forward ``W_down(silu(W_gate h) * W_up h)``
  of width ``intermediate_size``: every token, no router;
- ``*``  causal grouped-query attention; ``W`` the same inside
  ``sliding_window`` (key ``j`` visible to query ``i`` iff ``j <= i`` and
  ``i - j < sliding_window``).  The query heads are the layer type's
  (``HybridLMConfig.heads_for``) over one ``num_key_value_heads``.  Rotary
  positions where the layer's type has ``rope_parameters`` (``default``
  or ``yarn``: :func:`rope_tables`) over the first
  ``partial_rotary_factor`` of a head's dimensions; none otherwise (the
  Mamba layers carry position).  Where the configuration has ``qk_norm``
  an RMSNorm over each head's q and over each head's k comes before the
  rotation, one learned scale of ``head_dim`` for all query heads and one
  for all key heads.  The rotation (:func:`rotate`) is
  :func:`apply_rope`'s expression, or, where the static shapes say so
  (ops/pallas/rope.py ``lanes_pay``: a head of whole 128-lane registers,
  float32) and the program is lowered for the TPU, one kernel pass a
  tensor that reads q or k as its projection leaves it and writes it
  head-major, where the flash kernels read it; which one a compiled
  step holds is in its op names (``rope_lanes``);
- ``L``  causal latent attention (:class:`LatentAttentionMixer`): queries
  and keys / values through low-rank latents with an RMSNorm on each, a
  head's query and key of two parts of which the second turns and, on the
  key, is one for every head; the core is the ``*`` layer's, over every
  earlier key, at the head size the two parts make.  q is turned in place
  (:func:`rotate` from the first dimension that turns) and the keys and
  values come off the ``[k_n ; v]`` heads and the one rotary key
  (:func:`latent_heads`): the expressions, or, where the static shapes
  say so (ops/pallas/rope.py ``lanes_pay``, ``heads_pay``) and the
  program is lowered for the TPU, one kernel pass each that writes
  head-major, where the flash kernels read (op names ``rope_lanes``,
  ``latent_lanes``; ``…_t`` their transposes);
- ``C``  a gated short convolution (:class:`ShortConvMixer`): ``[B ; C ;
  u] = x W_in``, a depth-wise causal convolution of ``conv_L_cache`` taps
  a channel over ``B * u`` (no bias, no activation; the last tap is the
  present token's), ``(C * conv) W_out``.

The head is its own ``kernel`` or, where the configuration ties it
(``tie_word_embeddings``), the token embedding's table read transposed
(:class:`LMHead`): the table's gradient is then the lookup's scatter-add
plus the head's dense product.  The norm after the last block is
``final_norm`` under every family's keys (the ``lfm2_moe`` family's public
code calls it ``embedding_norm``; it is the final norm).

Where ``num_nextn_predict_layers`` is 1 a multi-token prediction module
(:class:`MTPModule`) reads the last block's output ``h_i`` (before the
final norm) and the embedding of the next token, runs one more block of
the last block's kinds over ``[RMSNorm(h_i) ; RMSNorm(Emb(t_{i+1}))] W_m``,
its own final norm and the shared head against ``t_{i+2}``; the step's
loss is the next-token loss + ``mtp_loss_weight`` x the module's.

Where ``total_ut_steps`` is T > 1 the stack is looped
(:meth:`HybridLM.passes`): the same layers run T times a forward pass,
``h_t = final_norm(layers(h_{t-1}))`` from ``h_0`` the embedding, and after
every pass the head and an exit gate (:class:`ExitGate`, ``lambda_t =
sigmoid(h_t w_g + b_g)``) read ``h_t``.  The exit distribution is ``p_t =
lambda_t prod_{j<t} (1 - lambda_j)``, the last pass the remainder, and the
step's loss is the mean over positions of ``sum_t p_t L_t -
exit_entropy_weight x H(p)`` (:meth:`HybridLM.loop_losses`).  Where the
configuration has ``post_norms`` a layer is ``x + RMSNorm_post(
mixer(RMSNorm(x)))``.

Ingest compatibility (as models/sequence.py): a PSV row carries its
``S`` token ids in the float32 feature block; the model casts them on
device.  The loss is the family's own — mean next-token cross-entropy over
the rows whose weight is not 0 — so the module exposes :meth:`losses`
beside ``__call__`` (logits) and the trainer's step builders take
:func:`batch_loss` through ``models/factory.py`` ``family_loss``.  Every layer and the head
are rematerialised in the backward pass; a layer keeps its flash core's
output and log-sum-exp (:func:`remat_layer`), the two residuals the
backward kernels read beside q, k and v, so no flash forward kernel runs a
second time.

Initialisation: normal, ``initializer_range`` for every matrix; the token
embedding at ``embedding_initializer_range`` and every mixer's projection
back onto the residual stream at ``output_initializer_range`` where the
configuration states them (at one range for all, uniform attention makes
every token of a row the running mean of its window by the second block,
and a row's tokens then choose the same experts: PERF.md section 6).

The phase names (``jax.named_scope``; obs/profile.py ``PHASE_SCOPES``):
``embed.gather``, ``ssm.proj`` (in/out projections, gate and grouped
norm), ``ssm.conv``, ``ssm.scan``, ``moe.route``, ``moe.experts``,
``moe.shared``, ``mlp.dense`` (a ``D`` layer), ``attn.proj`` (q, k, v, o),
``attn.qknorm`` (the two head norms, where the configuration has them),
``attn.rope`` (the rotation of q and k; on an ``L`` layer every pass that
lays q, k and v out for the core: q turned in place, the one rotary key
turned and written into every head, ``[k_n ; v]`` taken apart), ``attn.core``
(a ``*`` or ``L`` layer's core: on an ``L`` layer the attention call and
nothing else), ``attn.window`` (a ``W`` layer's), ``attn.latent`` (an ``L``
layer's projections onto its two latents and their norms), ``attn.expand``
(the latents' projections up to heads), ``conv.proj`` (a ``C`` layer's
``W_in`` and ``W_out``), ``conv.mix`` (``B * u``, the taps, ``C *``),
``lm.head`` (the head's pass, tied or not); and around all of
those, where there is the module, ``mtp.merge`` (two norms, the next
token's embedding, ``W_m``), ``mtp.block`` and ``mtp.head`` (the first
scope on an op's path names its phase, so the module's block and head
pass are told from the main model's); in a looped stack ``loop.norm``
(the norms on the mixers' outputs and the final norm between passes) and
``loop.exit`` (the gates, the exit distribution, the mixture of the passes'
losses and its entropy; each pass's head stays ``lm.head``).  The residual
stream's own norms and adds carry no scope.
"""

from __future__ import annotations

import math
from typing import Any, Callable

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from shifu_tensorflow_tpu.config.model_config import (
    HybridLMConfig,
    RopeParameters,
)
from shifu_tensorflow_tpu.ops import grouped, ssm_scan
from shifu_tensorflow_tpu.ops.pallas import flash_attention as flash
from shifu_tensorflow_tpu.ops.pallas import rope as rope_kernel
from shifu_tensorflow_tpu.ops.pallas import ssd_scan as ssd_kernel

#: rows of a grouped-product tile.  A tile costs its rows' products or the
#: read of its expert's weights, whichever is longer (2688 x 1856 float32
#: twice: 40 MB, as long as ~480 rows' products on a v5e).  Sized for the
#: deployed load, 16 data-parallel chips' 6,144 pairs an expert a step:
#: 1,024 rows are twice the products a weight read hides and pad half a
#: tile in six.  The one-chip benchmark cell sends an expert 384-650 pairs,
#: where this size multiplies more padding than rows; PERF.md section 6
#: has both sizes' numbers there, for a perf_opt to choose by.  A
#: configuration whose share sends an expert a load that ends on this
#: tile's edge states its own (``expert_tile``)
EXPERT_TILE = 1024


def _normal(std):
    return nn.initializers.normal(stddev=std)


def _matmul(x, kernel, dtype):
    return jnp.dot(x.astype(dtype), kernel.astype(dtype))


class RMSNorm(nn.Module):
    eps: float
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],),
                           jnp.float32)
        x = x.astype(self.dtype)
        inv = jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                            + jnp.asarray(self.eps, self.dtype))
        return x * inv * scale.astype(self.dtype)


class Weights(nn.Module):
    """``<name>/kernel`` of a given shape, handed back as it is."""

    shape: tuple
    std: float

    @nn.compact
    def __call__(self):
        return self.param("kernel", _normal(self.std), self.shape,
                          jnp.float32)


class Kernel(nn.Module):
    """A bias-free projection whose parameter is ``<name>/kernel``."""

    features: int
    std: float
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        kernel = self.param("kernel", _normal(self.std),
                            (x.shape[-1], self.features), jnp.float32)
        return _matmul(x, kernel, self.dtype)


def _dt_bias_init(cfg: HybridLMConfig):
    """Inverse softplus of a log-uniform draw in [time_step_min,
    time_step_max], floored: the published Mamba-2 initialisation."""
    def init(key, shape, dtype=jnp.float32):
        u = jax.random.uniform(key, shape, jnp.float32)
        dt = jnp.exp(u * (math.log(cfg.time_step_max)
                          - math.log(cfg.time_step_min))
                     + math.log(cfg.time_step_min))
        dt = jnp.maximum(dt, cfg.time_step_floor)
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)

    return init


class ConvParams(nn.Module):
    """``(kernel (width, channels), bias or None)`` of a depth-wise
    convolution: tap ``width - 1`` is the one on the present token."""

    width: int
    channels: int
    bias: bool = True

    @nn.compact
    def __call__(self):
        bound = 1.0 / math.sqrt(self.width)  # torch's Conv1d default
        kernel = self.param(
            "kernel",
            lambda k, s, d=jnp.float32: jax.random.uniform(
                k, s, d, -bound, bound),
            (self.width, self.channels), jnp.float32)
        bias = (self.param("bias", nn.initializers.zeros, (self.channels,),
                           jnp.float32) if self.bias else None)
        return kernel, bias


def chunked_scan(x, dt, a, b, c, chunk: int):
    """``ssm_scan.ssm_scan_chunked`` by the path the static shapes pick:
    where ``ssd_kernel.ssd_pays`` (float32, a chunk and a state of whole
    128-lane registers, a group's heads filling whole registers, a
    sequence of whole chunks) a program lowered for the TPU gets the
    kernels, which keep a chunk's mask and the running state in VMEM;
    every other program, and every other shape or dtype, the
    expression."""
    heads, groups = x.shape[2], b.shape[2]
    if not ssd_kernel.ssd_pays(chunk, x.shape[3], heads // groups,
                               b.shape[3], x.dtype, x.shape[1]):
        return ssm_scan.ssm_scan_chunked(x, dt, a, b, c, chunk)
    return jax.lax.platform_dependent(
        x, dt, a, b, c,
        tpu=lambda *v: ssd_kernel.ssd_scan(*v, chunk),
        default=lambda *v: ssm_scan.ssm_scan_chunked(*v, chunk))


class MambaMixer(nn.Module):
    """One ``M`` layer's mixer; its scan is :func:`chunked_scan`'s (the
    expression or the kernels, by static shapes and platform)."""

    cfg: HybridLMConfig
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        c, dt_ = self.cfg, self.dtype
        heads, hd, groups, n = (c.mamba_num_heads, c.mamba_head_dim,
                                c.n_groups, c.ssm_state_size)
        inner = heads * hd
        conv_dim = inner + 2 * groups * n
        bsz, s, _ = x.shape
        with jax.named_scope("ssm.proj"):
            zxbcdt = Kernel(inner + conv_dim + heads, c.initializer_range,
                            dt_, name="in_proj")(x)
        z = zxbcdt[..., :inner]
        xbc = zxbcdt[..., inner:inner + conv_dim]
        dt = zxbcdt[..., inner + conv_dim:]
        kernel, bias = ConvParams(c.conv_kernel, conv_dim, name="conv")()
        with jax.named_scope("ssm.conv"):
            padded = jnp.pad(xbc, ((0, 0), (c.conv_kernel - 1, 0), (0, 0)))
            conv = bias.astype(dt_)
            for j in range(c.conv_kernel):
                conv = conv + padded[:, j:j + s] * kernel[j].astype(dt_)
            xbc = nn.silu(conv)
        xs = xbc[..., :inner].reshape(bsz, s, heads, hd)
        b = xbc[..., inner:inner + groups * n].reshape(bsz, s, groups, n)
        cc = xbc[..., inner + groups * n:].reshape(bsz, s, groups, n)
        dt_bias = self.param("dt_bias", _dt_bias_init(c), (heads,),
                             jnp.float32)
        a_log = self.param(
            "A_log",
            lambda k, s_, d=jnp.float32: jnp.log(
                jnp.arange(1, s_[0] + 1, dtype=d)),
            (heads,), jnp.float32)
        d_skip = self.param("D", nn.initializers.ones, (heads,), jnp.float32)
        with jax.named_scope("ssm.scan"):
            dt = nn.softplus(dt + dt_bias.astype(dt_))
            y = chunked_scan(xs, dt, -jnp.exp(a_log), b, cc, c.chunk_size)
            y = y.astype(dt_) + d_skip.astype(dt_)[:, None] * xs
        with jax.named_scope("ssm.proj"):
            y = y.reshape(bsz, s, inner) * nn.silu(z)
            # grouped RMSNorm, the gate before the norm
            y = GroupNormScale(groups, c.layer_norm_epsilon, dt_,
                               name="norm")(y)
            return Kernel(c.hidden_size, c.output_std, dt_,
                          name="out_proj")(y)


class ShortConvMixer(nn.Module):
    """One ``C`` layer's mixer, a gated short convolution: ``[B ; C ; u] =
    x W_in`` (three contiguous thirds), ``g = B * u``, ``c_t = sum_j w_j *
    g_{t - (K - 1) + j}`` with ``g`` zero before the row's first token (a
    depth-wise causal convolution of ``K = conv_L_cache`` taps written as
    its shifted products, ``w_{K-1}`` the tap on the present token, no
    bias, no activation), ``out = (C * c) W_out``."""

    cfg: HybridLMConfig
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        c, dt_ = self.cfg, self.dtype
        d, taps, s = c.hidden_size, c.conv_L_cache, x.shape[1]
        with jax.named_scope("conv.proj"):
            bcu = Kernel(3 * d, c.initializer_range, dt_, name="in_proj")(x)
        kernel, _ = ConvParams(taps, d, bias=False, name="conv")()
        with jax.named_scope("conv.mix"):
            gated = bcu[..., :d] * bcu[..., 2 * d:]
            padded = jnp.pad(gated, ((0, 0), (taps - 1, 0), (0, 0)))
            conv = sum(padded[:, j:j + s] * kernel[j].astype(dt_)
                       for j in range(taps))
            y = bcu[..., d:2 * d] * conv
        with jax.named_scope("conv.proj"):
            return Kernel(d, c.output_std, dt_, name="out_proj")(y)


class GroupNormScale(nn.Module):
    """RMSNorm over each of ``groups`` slices of the last axis, then one
    scale over the whole axis."""

    groups: int
    eps: float
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, y):
        scale = self.param("scale", nn.initializers.ones, (y.shape[-1],),
                           jnp.float32)
        g = y.reshape(y.shape[:-1] + (self.groups, -1))
        g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True)
                              + jnp.asarray(self.eps, self.dtype))
        return g.reshape(y.shape) * scale.astype(self.dtype)


class ExpertWeights(nn.Module):
    """``up``, ``down`` of the held experts, after ``gate`` where the
    expert is gated."""

    held: int
    width: int
    std: float
    down_std: float
    gated: bool = False

    @nn.compact
    def __call__(self, hidden: int):
        into = (self.held, hidden, self.width)
        gate = ((self.param("gate", _normal(self.std), into, jnp.float32),)
                if self.gated else ())
        up = self.param("up", _normal(self.std), into, jnp.float32)
        down = self.param("down", _normal(self.down_std),
                          (self.held, self.width, hidden), jnp.float32)
        return gate + (up, down)


class FeedForward(nn.Module):
    """What every token goes through: ``W_down relu(W_up h)^2``, or gated,
    ``W_down(silu(W_gate h) * W_up h)``.  A shared expert beside the
    routed ones, and the ``D`` layer's mixer."""

    width: int
    std: float
    down_std: float
    dtype: Any = jnp.float32
    gated: bool = False

    @nn.compact
    def __call__(self, x):
        h = Kernel(self.width, self.std, self.dtype, name="up")(x)
        if self.gated:
            h = nn.silu(Kernel(self.width, self.std, self.dtype,
                               name="gate")(x)) * h
        else:
            h = jnp.square(nn.relu(h))
        return Kernel(x.shape[-1], self.down_std, self.dtype,
                      name="down")(h)


class MoEMixer(nn.Module):
    """Returns ``(out, (pairs on held experts, largest held expert's
    pairs))``: the counters ride the step's auxiliary output."""

    cfg: HybridLMConfig
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        c, dt_ = self.cfg, self.dtype
        first, held = c.experts_held
        bsz, s, d = x.shape
        flat = x.reshape(-1, d)
        with jax.named_scope("moe.route"):
            # the gate is float32 at full precision whatever the step's
            # dtype: a rounded score flips choices between near-tied experts
            w_r = Weights((d, c.n_routed_experts), c.initializer_range,
                          name="router")()
            logits = jnp.dot(flat.astype(jnp.float32), w_r,
                             precision=jax.lax.Precision.HIGHEST)
            if c.scoring_func == "softmax":
                scores = jax.nn.softmax(logits, axis=-1)
                _, ids = jax.lax.top_k(scores, c.num_experts_per_tok)
            else:
                scores = nn.sigmoid(logits)
                bias = self.param(
                    "e_score_correction_bias", nn.initializers.zeros,
                    (c.n_routed_experts,), jnp.float32)
                _, ids = jax.lax.top_k(scores + bias, c.num_experts_per_tok)
            weights = jnp.take_along_axis(scores, ids, axis=-1)
            if c.norm_topk_prob:
                weights = weights / (jnp.sum(weights, axis=-1, keepdims=True)
                                     + 1e-20)
            weights = weights * c.routed_scaling_factor
            tile = c.expert_tile or EXPERT_TILE
            pair, tile_expert, n_tiles, counts = grouped.plan_tiles(
                ids, first, held, tile)
            k = c.num_experts_per_tok
            token = jnp.where(pair < ids.size, pair // k, flat.shape[0])
            gate = jnp.where(
                pair < ids.size,
                jnp.take(weights.reshape(-1), pair, mode="clip"), 0.0)
        gated = c.hidden_act == "silu"
        matrices = ExpertWeights(held, c.moe_intermediate_size,
                                 c.initializer_range, c.output_std, gated,
                                 name="experts")(d)
        with jax.named_scope("moe.experts"):
            walk = grouped.gated_expert_mlp if gated else grouped.expert_mlp
            out = walk(flat.astype(dt_), *(w.astype(dt_) for w in matrices),
                       token, gate, tile_expert, n_tiles,
                       tile).astype(dt_)
        if c.n_shared_experts:
            with jax.named_scope("moe.shared"):
                out = out + FeedForward(
                    c.moe_shared_expert_intermediate_size
                    * c.n_shared_experts,
                    c.initializer_range, c.output_std, dt_, gated,
                    name="shared")(flat)
        return (out.reshape(bsz, s, d),
                jnp.stack([jnp.sum(counts), jnp.max(counts)]))


def rope_frequencies(rope: RopeParameters, head_dim: int):
    """``(frequencies (head_dim / 2,) float64, scale of cos and sin)`` of a
    head whose ``head_dim`` dimensions all turn (under a
    ``partial_rotary_factor`` the caller passes the dimensions that do).
    ``default``: ``theta^(-m / (head_dim / 2))``, scale 1.  ``yarn``:
    ``(1 - g_m) b_m / factor + g_m b_m`` with ``g_m = 1 - clip((m - low) /
    (high - low), 0, 1)``, ``low`` / ``high`` the floor / ceiling of the
    dimensions that turn ``beta_fast`` / ``beta_slow`` times over the
    original context, and the scale ``attention_factor``."""
    half = head_dim // 2
    base = rope.rope_theta ** (-np.arange(half, dtype=np.float64) / half)
    if rope.rope_type != "yarn":
        return base, 1.0

    def turns(r):  # the dimension that turns r times over the context
        return half * math.log(rope.original_max_position_embeddings
                               / (2 * math.pi * r)) / math.log(
                                   rope.rope_theta)

    low = max(math.floor(turns(rope.beta_fast)), 0)
    high = min(math.ceil(turns(rope.beta_slow)), head_dim - 1)
    ramp = np.clip((np.arange(half) - low) / max(high - low, 1e-3), 0, 1)
    keep = 1.0 - ramp
    scale = rope.attention_factor or 0.1 * math.log(rope.factor) + 1.0
    return (1 - keep) * base / rope.factor + keep * base, scale


def rope_tables(rope: RopeParameters, seq: int, head_dim: int):
    """``(cos, sin)`` (S, R / 2) float32 for the ``R =
    rope.rotary_dim(head_dim)`` dimensions that turn, the scale folded in:
    the angle is the float32 product of the position and the float32
    frequency, as the public implementations compute it."""
    freq, scale = rope_frequencies(rope, rope.rotary_dim(head_dim))
    angle = (jnp.arange(seq, dtype=jnp.float32)[:, None]
             * jnp.asarray(freq, jnp.float32)[None, :])
    return jnp.cos(angle) * scale, jnp.sin(angle) * scale


def apply_rope(u, cos, sin, offset: int = 0):
    """``u cos + rotate_half(u) sin`` over ``R = 2 x`` (the tables' width)
    dimensions of the last axis of (B, S, H, D), from ``offset`` on,
    ``rotate_half(u) = (-u[R/2:R], u[:R/2])`` of those: dimension ``offset
    + m`` pairs with ``offset + m + R/2``.  The dimensions before
    ``offset`` and from ``offset + R`` on pass through, unchanged and
    unscaled."""
    half = cos.shape[-1]
    u1 = u[..., offset:offset + half]
    u2 = u[..., offset + half:offset + 2 * half]
    cos = cos[None, :, None, :].astype(u.dtype)
    sin = sin[None, :, None, :].astype(u.dtype)
    parts = [u1 * cos - u2 * sin, u2 * cos + u1 * sin]
    if offset:
        parts.insert(0, u[..., :offset])
    if offset + 2 * half < u.shape[-1]:
        parts.append(u[..., offset + 2 * half:])
    return jnp.concatenate(parts, axis=-1)


def rotate(u, cos, sin, offset: int = 0):
    """:func:`apply_rope` by the path the static shapes pick: where
    ``rope_kernel.lanes_pay`` (a head of whole 128-lane registers,
    float32) a program lowered for the TPU gets the kernel, whose one pass
    leaves the result as the flash kernels read it; every other program,
    and every other head or dtype, the expression."""
    if not rope_kernel.lanes_pay(u.shape[-1], 2 * cos.shape[-1], u.dtype,
                                 offset):
        return apply_rope(u, cos, sin, offset)
    return jax.lax.platform_dependent(
        u, cos, sin,
        tpu=lambda *v: rope_kernel.rope_lanes(*v, None, offset),
        default=lambda *v: apply_rope(*v, offset))


def latent_heads(kv, k_r, cos, sin, nope: int):
    """Latent attention's ``(keys, values)`` (B, S, H, ·) of ``kv`` (B, S,
    H, nope + V), a head ``[k_n ; v]``, and the ONE rotary key ``k_r`` (B,
    S, 1, R): a head's key is ``[k_n ; rot(k_r)]`` (the broadcast's
    transpose sums the heads' gradients).  This expression, or, where
    ``rope_kernel.heads_pay`` (float32, keys and values of whole 128-lane
    registers) and the program is lowered for the TPU, one kernel pass
    that reads ``kv`` as its projection leaves it and writes both
    head-major, where the flash kernels read them."""
    def expression(kv, k_r, cos, sin):
        k_r = jnp.broadcast_to(apply_rope(k_r, cos, sin),
                               kv.shape[:3] + k_r.shape[3:])
        return (jnp.concatenate([kv[..., :nope], k_r], axis=-1),
                kv[..., nope:])

    if not rope_kernel.heads_pay(nope, k_r.shape[-1], kv.shape[-1] - nope,
                                 kv.shape[2], kv.dtype):
        return expression(kv, k_r, cos, sin)
    return jax.lax.platform_dependent(
        kv, k_r, cos, sin,
        tpu=lambda *v: rope_kernel.latent_lanes(*v, nope),
        default=expression)


class AttentionMixer(nn.Module):
    """``kind`` ``*``: ``attention`` over every earlier key under
    ``attn.core``; ``W``: ``attention`` (the caller's windowed one) under
    ``attn.window``."""

    cfg: HybridLMConfig
    attention: Callable
    dtype: Any = jnp.float32
    kind: str = "*"

    @nn.compact
    def __call__(self, x):
        c, dt_ = self.cfg, self.dtype
        nq, nkv, hd = (c.heads_for(self.kind), c.num_key_value_heads,
                       c.head_dim)
        bsz, s, _ = x.shape
        std = c.initializer_range
        with jax.named_scope("attn.proj"):
            q = Kernel(nq * hd, std, dt_, name="q_proj")(x).reshape(
                bsz, s, nq, hd)
            k = Kernel(nkv * hd, std, dt_, name="k_proj")(x).reshape(
                bsz, s, nkv, hd)
            v = Kernel(nkv * hd, std, dt_, name="v_proj")(x).reshape(
                bsz, s, nkv, hd)
        if c.qk_norm:
            with jax.named_scope("attn.qknorm"):
                # over each head's dimensions, before the rotation: one
                # scale for all query heads, one for all key heads
                q = RMSNorm(c.layer_norm_epsilon, dt_, name="q_norm")(q)
                k = RMSNorm(c.layer_norm_epsilon, dt_, name="k_norm")(k)
        rope = c.rope_for(self.kind)
        if rope is not None:
            with jax.named_scope("attn.rope"):
                cos, sin = rope_tables(rope, s, hd)
                q, k = rotate(q, cos, sin), rotate(k, cos, sin)
        with jax.named_scope("attn.window" if self.kind == "W"
                             else "attn.core"):
            # each KV head serves nq / nkv query heads: the repeat's
            # transpose sums their gradients
            k = jnp.repeat(k, nq // nkv, axis=2)
            v = jnp.repeat(v, nq // nkv, axis=2)
            y = self.attention(q, k, v)
        with jax.named_scope("attn.proj"):
            return Kernel(c.hidden_size, c.output_std, dt_, name="o_proj")(
                y.reshape(bsz, s, nq * hd))


class LatentAttentionMixer(nn.Module):
    """One ``L`` layer's mixer, in the expanded form training computes:
    ``c_q = RMSNorm(x W_qa)``, ``[q_n ; q_r] = c_q W_qb`` a head;
    ``[c_kv ; k_r] = x W_kva``, ``[k_n ; v] = RMSNorm(c_kv) W_kvb`` a head;
    ``q = [q_n ; rot(q_r)]``, ``k = [k_n ; rot(k_r)]`` with the one ``k_r``
    for every head (whose gradient is the sum of the heads');
    ``attention`` over every earlier key; ``W_o``.  Neither q nor k is
    built by concatenation nor ``[k_n ; v]`` sliced where the kernels run:
    :func:`rotate` turns q's last dimensions in place and
    :func:`latent_heads` hands back keys and values, each by one pass that
    reads what the projection left and writes what the core reads."""

    cfg: HybridLMConfig
    attention: Callable
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        c, dt_ = self.cfg, self.dtype
        n, d_c = c.num_attention_heads, c.kv_lora_rank
        d_n, d_r, d_v = c.qk_nope_head_dim, c.qk_rope_head_dim, c.v_head_dim
        bsz, s, _ = x.shape
        std, eps = c.initializer_range, c.layer_norm_epsilon
        with jax.named_scope("attn.latent"):
            c_q = RMSNorm(eps, dt_, name="q_a_norm")(
                Kernel(c.q_lora_rank, std, dt_, name="q_a_proj")(x))
            kv = Kernel(d_c + d_r, std, dt_, name="kv_a_proj")(x)
            c_kv = RMSNorm(eps, dt_, name="kv_a_norm")(kv[..., :d_c])
            k_r = kv[..., d_c:].reshape(bsz, s, 1, d_r)
        with jax.named_scope("attn.expand"):
            q = Kernel(n * (d_n + d_r), std, dt_, name="q_b_proj")(
                c_q).reshape(bsz, s, n, d_n + d_r)
            kv = Kernel(n * (d_n + d_v), std, dt_, name="kv_b_proj")(
                c_kv).reshape(bsz, s, n, d_n + d_v)
        with jax.named_scope("attn.rope"):
            cos, sin = rope_tables(c.rope_for("L"), s, d_r)
            q = rotate(q, cos, sin, d_n)
            k, v = latent_heads(kv, k_r, cos, sin, d_n)
        with jax.named_scope("attn.core"):
            y = self.attention(q, k, v)
        with jax.named_scope("attn.proj"):
            return Kernel(c.hidden_size, c.output_std, dt_, name="o_proj")(
                y.reshape(bsz, s, n * d_v))


class Layer(nn.Module):
    kind: str
    cfg: HybridLMConfig
    attention: Callable
    dtype: Any = jnp.float32
    window_attention: "Callable | None" = None

    @nn.compact
    def __call__(self, x):
        h = RMSNorm(self.cfg.layer_norm_epsilon, self.dtype, name="norm")(x)
        stats = jnp.zeros((2,), jnp.int32)
        if self.kind == "M":
            y = MambaMixer(self.cfg, self.dtype, name="mixer")(h)
        elif self.kind == "E":
            y, stats = MoEMixer(self.cfg, self.dtype, name="mixer")(h)
        elif self.kind == "D":
            c = self.cfg
            with jax.named_scope("mlp.dense"):
                y = FeedForward(c.intermediate_size, c.initializer_range,
                                c.output_std, self.dtype, True,
                                name="mixer")(h)
        elif self.kind == "L":
            y = LatentAttentionMixer(self.cfg, self.attention, self.dtype,
                                     name="mixer")(h)
        elif self.kind == "C":
            y = ShortConvMixer(self.cfg, self.dtype, name="mixer")(h)
        else:
            attention = (self.window_attention if self.kind == "W"
                         else self.attention)
            y = AttentionMixer(self.cfg, attention, self.dtype, self.kind,
                               name="mixer")(h)
        if self.cfg.post_norms:
            with jax.named_scope("loop.norm"):
                y = RMSNorm(self.cfg.layer_norm_epsilon, self.dtype,
                            name="post_norm")(y)
        return x + y, stats


#: what a layer's rematerialisation keeps for the backward pass: the flash
#: core's output and log-sum-exp, the backward kernels' residuals beside
#: q, k and v; everything else is recomputed from the layer's input
KEEP_FLASH_CORE = jax.checkpoint_policies.save_only_these_names(
    flash.OUT_NAME, flash.LSE_NAME)


def remat_layer():
    """:class:`Layer` rematerialised under :data:`KEEP_FLASH_CORE`, so the
    backward runs the flash forward kernel of no layer a second time.  A
    layer that runs no flash core (``M``, ``E``, ``D``, ``C``, or attention
    off the Pallas kernels) holds neither name: it keeps nothing and
    recomputes all it did."""
    return nn.remat(Layer, policy=KEEP_FLASH_CORE)


#: the widest row whose maximum XLA:TPU turns into a ``reduce-window``
#: (:func:`log_softmax`)
WINDOWED_ROW = 8192


def log_softmax(x):
    """``jax.nn.log_softmax(x, axis=-1)``, its values to the bit, in a
    form whose row maximum the chip's compiler reduces plainly.

    At a row of at most :data:`WINDOWED_ROW` columns XLA:TPU compiles the
    maximum, broadcast back over the row, to a ``reduce-window`` ``2V -
    1`` wide (``EmitReduceWindowLane``): 47 ms a pass at 2 x 8,191 rows of
    8,192 columns, where a row of 8,320 takes a plain reduce (PERF.md
    section 6, PR 38 and PR 39).  The sweep of the head alone through the
    v5e compiler (PR 39; ``tests/test_conv_lm_lowers.py``): every width from
    128 to 8,192 windows (the powers of two, 384, 640, 1,000, 1,536,
    3,072, 6,144, 7,680, 8,064, 8,184, 8,191), none above (8,193, 8,194,
    8,200, 8,256, 8,320, 8,448, 9,216, 10,240, 12,288, 12,544, 16,256,
    16,384, 19,360, 24,576, 32,768), at 16, 512 and 16,382 rows and a
    hidden size of 64 or 2,048 alike.  There an optimization barrier
    stands between the maximum and the subtraction, which the rewrite
    cannot see through; wider rows take jax's own expression, and trace to
    the program they did."""
    if x.shape[-1] > WINDOWED_ROW:
        return jax.nn.log_softmax(x, axis=-1)
    top = jax.lax.optimization_barrier(jax.lax.stop_gradient(
        jnp.max(x, axis=-1, keepdims=True)))
    shifted = x - top
    return shifted - jnp.log(jnp.sum(jnp.exp(shifted), axis=-1,
                                     keepdims=True))


class LMHead(nn.Module):
    """Head + next-token cross-entropy (``lm.head``): ``__call__`` gives
    ``(sum of the live rows' token losses, per-row mean loss (B,))`` and is
    what the backward pass rematerialises; ``logits`` the scores.  Untied
    it holds its own ``kernel``; ``tied`` it holds nothing and is handed
    the token embedding's ``table`` (vocab, hidden): the scores are ``h
    table^T``, and the table's gradient is the lookup's scatter-add plus
    this product's.  The scores' log-softmax is :func:`log_softmax`."""

    vocab: int
    hidden: int
    std: float
    dtype: Any = jnp.float32
    tied: bool = False

    def setup(self):
        if not self.tied:
            self.kernel = self.param("kernel", _normal(self.std),
                                     (self.hidden, self.vocab), jnp.float32)

    def kernel_of(self, table=None):
        """(hidden, vocab): what the scores multiply the states by."""
        return table.T if self.tied else self.kernel

    def logits(self, h, table=None):
        return _matmul(h, self.kernel_of(table), self.dtype).astype(
            jnp.float32)

    def __call__(self, h, ids, live, table=None):
        with jax.named_scope("lm.head"):
            nll = next_token_nll(self.logits(h[:, :-1], table), ids)
            return jnp.sum(nll * live[:, None]), jnp.mean(nll, axis=-1)


def next_token_nll(scores, ids):
    """Each position's next-token loss (B, S - 1) from the scores of
    positions 0 .. S - 2."""
    logp = log_softmax(scores)
    return -jnp.take_along_axis(logp, ids[:, 1:, None], axis=-1)[..., 0]


def passes_nll(states, kernel, ids, dtype):
    """Each pass's next-token losses (T, B, S - 1) from the passes' states
    (T, B, S, d): :class:`LMHead`'s scores and loss, one pass after the
    other (``lax.map``) and each rematerialised, so that one pass's scores
    exist at a time, in the backward pass too (four passes of 4,096 x
    49,152 scores side by side are 3.2 GB)."""
    @jax.checkpoint
    def one(h):
        with jax.named_scope("lm.head"):
            return next_token_nll(
                _matmul(h[:, :-1], kernel, dtype).astype(jnp.float32), ids)

    return jax.lax.map(one, states)


class ExitGate(nn.Module):
    """``h w_g + b_g`` (B, S): the logit of the exit gate ``lambda =
    sigmoid(h w_g + b_g)`` at each position of a pass's normed state."""

    std: float
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, h):
        kernel = self.param("kernel", _normal(self.std), (h.shape[-1], 1),
                            jnp.float32)
        bias = self.param("bias", nn.initializers.zeros, (1,), jnp.float32)
        return (_matmul(h, kernel, self.dtype)
                + bias.astype(self.dtype))[..., 0]


def exit_log_probs(z):
    """``log p`` (T, ...) of the exit distribution from the gates' logits
    ``z`` (T - 1, ...) of passes 1 .. T - 1: ``p_t = lambda_t prod_{j<t}
    (1 - lambda_j)`` and the last pass the remainder, ``p_T = prod_{j<T}
    (1 - lambda_j)``, each a sum of log-sigmoids."""
    stayed = jnp.cumsum(jax.nn.log_sigmoid(-z), axis=0)
    before = jnp.concatenate([jnp.zeros_like(z[:1]), stayed[:-1]])
    return jnp.concatenate([jax.nn.log_sigmoid(z) + before, stayed[-1:]])


def fold_stats(stats, s):
    """The step's counters with one more expert layer's: pairs summed, the
    largest held expert's kept."""
    return jnp.stack([stats[0] + s[0], jnp.maximum(stats[1], s[1])])


class MTPMerge(nn.Module):
    """``[RMSNorm_h(h) ; RMSNorm_e(e)] W_m``: what the module's block
    reads."""

    cfg: HybridLMConfig
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, h, e):
        c = self.cfg
        h = RMSNorm(c.layer_norm_epsilon, self.dtype, name="hnorm")(h)
        e = RMSNorm(c.layer_norm_epsilon, self.dtype, name="enorm")(e)
        return Kernel(c.hidden_size, c.initializer_range, self.dtype,
                      name="proj")(jnp.concatenate([h, e], axis=-1))


class MTPModule(nn.Module):
    """One multi-token prediction module: position ``i`` reads the main
    model's ``h_i`` and the embedding ``e_i`` of token ``i + 1``, and
    hands the shared head the final-normed output of one more block
    (attention then feed-forward, the last block's kinds), causal over
    ``i`` at positions ``i``.  Returns ``(hidden states, counters)``."""

    cfg: HybridLMConfig
    attention: Callable
    dtype: Any = jnp.float32
    window_attention: "Callable | None" = None

    @nn.compact
    def __call__(self, h, e):
        c = self.cfg
        stats = jnp.zeros((2,), jnp.int32)
        with jax.named_scope("mtp.merge"):
            u = nn.remat(MTPMerge)(c, self.dtype, name="merge")(h, e)
        with jax.named_scope("mtp.block"):
            for name, kind in zip(("attn", "ffn"),
                                  c.hybrid_override_pattern[-2:]):
                u, s = remat_layer()(kind, c, self.attention, self.dtype,
                                     self.window_attention, name=name)(u)
                stats = fold_stats(stats, s)
        with jax.named_scope("mtp.head"):
            return RMSNorm(c.layer_norm_epsilon, self.dtype,
                           name="final_norm")(u), stats


class HybridLM(nn.Module):
    """``__call__(x)``: logits (B, S, vocab held).  ``losses(x, w)``: what
    the trainer's loss is made of (see :func:`batch_loss`).
    ``window_attention`` is the ``W`` layers' core (``attention`` inside
    ``sliding_window``)."""

    cfg: HybridLMConfig
    attention: Callable
    dtype: Any = jnp.float32
    window_attention: "Callable | None" = None

    def setup(self):
        c = self.cfg
        self.embed = nn.Embed(c.vocab_size, c.hidden_size,
                              embedding_init=_normal(c.embedding_std),
                              param_dtype=jnp.float32)
        layer = remat_layer()
        self.layers = [layer(kind, c, self.attention, self.dtype,
                             self.window_attention)
                       for kind in c.hybrid_override_pattern]
        self.final_norm = RMSNorm(c.layer_norm_epsilon, self.dtype)
        self.lm_head = nn.remat(LMHead)(c.vocab_size, c.hidden_size,
                                        c.initializer_range, self.dtype,
                                        c.tie_word_embeddings)
        if c.num_nextn_predict_layers:
            self.mtp = MTPModule(c, self.attention, self.dtype,
                                 self.window_attention)
        if c.looped:
            self.exit_gate = ExitGate(c.initializer_range, self.dtype)

    def embedded(self, x):
        """(the token embeddings (B, S, d), ids (B, S), zero counters)."""
        ids = x.astype(jnp.int32)
        with jax.named_scope("embed.gather"):
            h = jnp.take(self.embed.embedding, ids, axis=0).astype(self.dtype)
        return h, ids, jnp.zeros((2,), jnp.int32)

    def stack(self, h, stats):
        """One pass through every layer: (its output, counters)."""
        for layer in self.layers:
            h, s = layer(h)
            stats = fold_stats(stats, s)
        return h, stats

    def trunk(self, x):
        """(the last block's output (B, S, d), ids (B, S), counters)."""
        h, ids, stats = self.embedded(x)
        h, stats = self.stack(h, stats)
        return h, ids, stats

    def passes(self, x):
        """(the final-normed state after each of the ``total_ut_steps``
        passes of the stack, ids, counters): the same layers every pass,
        each pass reading the one before's normed state."""
        h, ids, stats = self.embedded(x)
        states = []
        for _ in range(self.cfg.total_ut_steps):
            h, stats = self.stack(h, stats)
            with jax.named_scope("loop.norm"):
                h = self.final_norm(h)
            states.append(h)
        return states, ids, stats

    def exits(self, x):
        """(each pass's next-token losses (T, B, S - 1), the log of the exit
        distribution over the passes at each position with a target (T, B,
        S - 1), counters).  The gates read passes 1 .. T - 1: the last pass
        takes the remainder."""
        states, ids, stats = self.passes(x)
        nll = passes_nll(jnp.stack(states),
                         self.lm_head.kernel_of(self.head_table()), ids,
                         self.dtype)
        with jax.named_scope("loop.exit"):
            z = jnp.stack([self.exit_gate(h[:, :-1]) for h in states[:-1]])
            return nll, exit_log_probs(z), stats

    def loop_losses(self, x, w):
        """``(loss, per-row loss (B, 1), counters, exit counters)`` of a
        looped stack: the mean over the live rows' positions of ``sum_t p_t
        L_t - exit_entropy_weight x H(p)``, the gates' gradient through
        ``p``.  The exit counters are the same means of the expected exit
        step ``sum_t t p_t`` (``exit_steps_mean``), of ``H(p)``
        (``exit_entropy``) and of the last pass's loss
        (``loop_last_loss``)."""
        nll, logp, stats = self.exits(x)
        live = (w.reshape(-1) != 0.0).astype(jnp.float32)[:, None]
        count = jnp.maximum(jnp.sum(live) * nll.shape[-1], 1.0)
        with jax.named_scope("loop.exit"):
            p = jnp.exp(logp)
            entropy = -jnp.sum(p * logp, axis=0)
            each = (jnp.sum(p * nll, axis=0)
                    - self.cfg.exit_entropy_weight * entropy)
            step = jnp.arange(1, nll.shape[0] + 1, dtype=p.dtype)
            expected = jnp.sum(p * step[:, None, None], axis=0)

            def mean(v):
                return jnp.sum(v * live) / count

            return (mean(each), jnp.mean(each, axis=-1, keepdims=True), stats,
                    {"exit_steps_mean": mean(expected),
                     "exit_entropy": mean(entropy),
                     "loop_last_loss": mean(nll[-1])})

    def __call__(self, x):
        if self.cfg.looped:  # the last pass's scores
            states, _, _ = self.passes(x)
            if self.is_initializing():
                self.exit_gate(states[0])
            return self.lm_head.logits(states[-1], self.head_table())
        h, ids, _ = self.trunk(x)
        if self.cfg.num_nextn_predict_layers and self.is_initializing():
            self.mtp_hidden(h, ids)  # ``init`` builds its parameters too
        return self.lm_head.logits(self.final_norm(h), self.head_table())

    def head_table(self):
        """What a tied head reads: the token embedding's table; nothing
        where the head has its own kernel."""
        return (self.embed.embedding if self.cfg.tie_word_embeddings
                else None)

    def mtp_hidden(self, h, ids):
        """The module's final-normed hidden states (B, S - 1, d) from the
        trunk's ``h``, and its counters.  All ``S`` positions go through
        its block, the last with the row's first token for a next one;
        position ``S - 1`` is dropped here, and ``S - 2``, whose second
        next token the row does not hold, where the head is read: both are
        causal, so neither reaches an earlier position."""
        with jax.named_scope("mtp.merge"):
            e = jnp.take(self.embed.embedding, jnp.roll(ids, -1, axis=1),
                         axis=0).astype(self.dtype)
        g, stats = self.mtp(h, e)
        return g[:, :-1], stats

    def mtp_logits(self, x):
        """(B, S - 2, vocab held): position ``i`` scores token ``i + 2``."""
        h, ids, _ = self.trunk(x)
        return self.lm_head.logits(self.mtp_hidden(h, ids)[0][:, :-1],
                                   self.head_table())

    def losses(self, x, w):
        """``(next-token loss, the module's loss or None, per-row mean
        next-token loss (B, 1), counters)``, each loss a mean over the live
        rows' positions: a row of weight 0 is padding and joins no sum."""
        h, ids, stats = self.trunk(x)
        live = (w.reshape(-1) != 0.0).astype(jnp.float32)
        table = self.head_table()
        total, per_row = self.lm_head(self.final_norm(h), ids, live, table)
        count = jnp.sum(live) * (ids.shape[1] - 1)
        main, ahead = total / jnp.maximum(count, 1.0), None
        if self.cfg.num_nextn_predict_layers:
            g, s = self.mtp_hidden(h, ids)
            stats = fold_stats(stats, s)
            with jax.named_scope("mtp.head"):
                # the head's own shift by one, over tokens 1 .. S - 1
                total, _ = self.lm_head(g, ids[:, 1:], live, table)
            count = jnp.sum(live) * (ids.shape[1] - 2)
            ahead = total / jnp.maximum(count, 1.0)
        return main, ahead, per_row[:, None], stats


#: what the counters of a step are called where they surface
COUNTER_NAMES = ("moe_held_pairs", "moe_held_max")


def batch_loss(model: HybridLM):
    """``(params, batch) -> (loss, per-row loss, {counter: value})`` for
    the trainer's step builders.  The counters sum (pairs that chose a held
    expert) and take the largest (pairs on one held expert) over the
    step's expert layers, the multi-token prediction module's included;
    with that module the two losses the step's loss is made of ride
    beside them (``main_loss``, ``mtp_loss``); with a looped stack its
    exit counters do (``HybridLM.loop_losses``)."""
    def fn(params, batch):
        if model.cfg.looped:
            loss, per_row, stats, extra = model.apply(
                {"params": params}, batch["x"], batch["w"],
                method="loop_losses")
            return loss, per_row, {**dict(zip(COUNTER_NAMES, stats)),
                                   **extra}
        main, ahead, per_row, stats = model.apply(
            {"params": params}, batch["x"], batch["w"], method="losses")
        counters = dict(zip(COUNTER_NAMES, stats))
        if ahead is None:
            return main, per_row, counters
        counters.update(main_loss=main, mtp_loss=ahead)
        return (main + model.cfg.mtp_loss_weight * ahead, per_row,
                counters)

    return fn
