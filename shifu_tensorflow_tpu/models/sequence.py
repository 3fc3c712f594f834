"""Sequence model family: transformer encoder over event sequences.

Beyond-reference capability (the reference is strictly fixed-width tabular,
SURVEY.md §5.7) that makes the framework's sequence-parallel primitives
(parallel/ring.py) first-class consumers instead of free-floating ops: the
fraud workload's natural extension is per-entity event sequences
(transaction histories), and long histories must scale past one chip's
sequence capacity.

Ingest compatibility: each PSV row carries ``seq_len`` steps of
``F = num_features / seq_len`` values, flattened in step order — so the
entire existing pipeline (schema projection, ZSCALE, binary shard cache,
streaming, fixed-shape batching) is unchanged; the model reshapes
``(B, seq_len*F) -> (B, seq_len, F)`` on device.

Attention selection (``train.params.SeqAttention``):
- ``full``    — single-device reference attention;
- ``chunked`` — single-device flash-style online-softmax scan over K/V
  blocks (parallel/ring.py chunked_attention): O(S·block) memory, no
  S×S materialization — for sequence lengths where full attention's
  score matrix approaches HBM;
- ``flash``   — the Pallas TPU fused kernel
  (ops/pallas/flash_attention.py), same memory property on-chip;
- ``ring``    — K/V rotation via ppermute + online softmax, O(S/P)
  memory per chip (parallel/ring.py ring_attention), sequence sharded
  over the mesh 'seq' axis;
- ``ulysses`` — all-to-all head-parallel attention (requires P | heads);
- ``auto``  — ring when the mesh has a 'seq' axis of size > 1, else
  full (no single-device regime is measured on the attached chip;
  ``STPU_CHUNKED_MIN_SEQ`` re-enables the chunked cutover from data —
  see ``_chunked_min_seq``).
"""

from __future__ import annotations

from functools import partial
from typing import Callable, Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp

from shifu_tensorflow_tpu.models.dnn import _xavier_bias_init

AttentionFn = Callable[[jax.Array, jax.Array, jax.Array], jax.Array]


class EncoderBlock(nn.Module):
    """Pre-LN transformer block; attention is injected so the same module
    runs single-device (full) or sequence-parallel (ring/Ulysses)."""

    d_model: int
    num_heads: int
    attention: AttentionFn
    mlp_ratio: int = 4
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, h: jax.Array) -> jax.Array:  # (B, S, d)
        b, s, _ = h.shape
        d_head = self.d_model // self.num_heads
        x = nn.LayerNorm(dtype=self.dtype)(h)
        qkv = nn.Dense(3 * self.d_model, dtype=self.dtype, name="qkv")(x)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        shape4 = (b, s, self.num_heads, d_head)
        attn = self.attention(q.reshape(shape4), k.reshape(shape4),
                              v.reshape(shape4))
        h = h + nn.Dense(self.d_model, dtype=self.dtype, name="proj")(
            attn.reshape(b, s, self.d_model)
        )
        x = nn.LayerNorm(dtype=self.dtype)(h)
        x = nn.Dense(self.mlp_ratio * self.d_model, dtype=self.dtype,
                     name="mlp_up")(x)
        x = nn.gelu(x)
        return h + nn.Dense(self.d_model, dtype=self.dtype,
                            name="mlp_down")(x)


class SequenceClassifier(nn.Module):
    """Event-sequence binary classifier: per-step projection + learned
    positional embedding → ``num_blocks`` encoder blocks → mean pool over
    all positions (rows are fixed-length; there is no padding mask — add
    one before feeding variable-length padded sequences) → sigmoid head.
    Output (B, 1), the standard trainer/eval contract."""

    seq_len: int
    d_model: int
    num_heads: int
    num_blocks: int
    attention: AttentionFn
    dtype: jnp.dtype = jnp.float32
    #: rematerialize blocks: the backward recomputes each block instead
    #: of storing its activations — pair with SeqAttention=chunked for
    #: long-S training (``SeqRemat`` in ModelConfig params)
    remat: bool = False

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:  # (B, seq_len * F)
        b, flat = x.shape
        if flat % self.seq_len:
            raise ValueError(
                f"feature width {flat} not divisible by SeqLen={self.seq_len}"
            )
        f = flat // self.seq_len
        h = x.reshape(b, self.seq_len, f)
        h = nn.Dense(self.d_model, dtype=self.dtype, name="step_proj")(h)
        pos = self.param(
            "pos_embedding",
            nn.initializers.normal(stddev=0.02),
            (self.seq_len, self.d_model),
            self.dtype,
        )
        h = h + pos[None, :, :]
        block_cls = nn.remat(EncoderBlock) if self.remat else EncoderBlock
        for i in range(self.num_blocks):
            h = block_cls(
                d_model=self.d_model, num_heads=self.num_heads,
                attention=self.attention, dtype=self.dtype,
                name=f"block_{i}",
            )(h)
        pooled = jnp.mean(nn.LayerNorm(dtype=self.dtype)(h), axis=1)
        logit = nn.Dense(
            1, dtype=self.dtype,
            kernel_init=nn.initializers.xavier_uniform(),
            bias_init=_xavier_bias_init,
            name="shifu_output_0",
        )(pooled)
        return nn.sigmoid(logit)


def make_attention(
    impl: str,
    mesh: "jax.sharding.Mesh | None",
    *,
    seq_len: int = 0,
    num_heads: int = 0,
    causal: bool = False,
    window: "int | None" = None,
) -> AttentionFn:
    """Resolve ``SeqAttention`` to a callable; 'auto' picks ring iff the
    mesh has a 'seq' axis of size > 1.  Shape constraints (seq axis must
    divide SeqLen; Ulysses additionally needs it to divide SeqHeads) are
    validated HERE so misconfiguration is a config error naming the keys,
    not an opaque shard_map/all_to_all trace failure.  ``causal`` masks
    keys after the query (the single-device implementations; the decoder
    family, models/hybrid_lm.py, passes it); ``window`` (causal only)
    also those ``window`` or more positions before it.  Both are a mask
    in ``full`` and ``chunked``; ``flash`` leaves the blocks that hold no
    visible key out of its grid (above the diagonal, or outside the
    window's band), and only where it is not causal visits them all."""
    from shifu_tensorflow_tpu.parallel import ring

    if window is not None and not causal:
        raise ValueError("a window needs causal attention")

    seq_axis = mesh.shape.get(ring.SEQ_AXIS, 1) if mesh is not None else 1
    has_seq = seq_axis > 1
    if impl == "auto":
        cut = _chunked_min_seq()
        if has_seq:
            impl = "ring"
        elif seq_len and cut > 0 and seq_len >= cut:
            impl = "chunked"
        else:
            impl = "full"
    if impl == "full":
        if causal:
            return partial(ring.full_attention, causal=True, window=window)
        return ring.full_attention
    if impl == "chunked":
        def attention(q, k, v):
            return ring.chunked_attention(
                q, k, v, causal=causal, block_size=_chunked_block(),
                window=window)

        return attention
    if impl == "flash":
        from shifu_tensorflow_tpu.ops.pallas import flash_attention as fa

        def attention(q, k, v, _f=fa.flash_attention):
            if causal:
                return _f(q, k, v, True, CAUSAL_FLASH_BLOCK,
                          CAUSAL_FLASH_BLOCK, False, window)
            return _f(q, k, v)

        return attention
    if causal:
        raise ValueError(
            f"SeqAttention={impl!r} has no causal form here "
            "(full | chunked | flash)")
    if impl in ("ring", "ulysses"):
        if not has_seq:
            raise ValueError(
                f"SeqAttention={impl!r} needs a mesh with a "
                f"'{ring.SEQ_AXIS}' axis > 1 (shifu.tpu.mesh-shape, e.g. "
                "\"data:2,seq:4\")"
            )
        if seq_len and seq_len % seq_axis:
            raise ValueError(
                f"SeqLen={seq_len} not divisible by the mesh "
                f"'{ring.SEQ_AXIS}' axis size {seq_axis}"
            )
        if impl == "ulysses" and num_heads and num_heads % seq_axis:
            raise ValueError(
                f"SeqAttention=ulysses needs SeqHeads divisible by the "
                f"'{ring.SEQ_AXIS}' axis: SeqHeads={num_heads}, "
                f"axis={seq_axis}"
            )
        sharded = (
            ring.ring_attention_sharded
            if impl == "ring"
            else ring.ulysses_attention_sharded
        )

        def attention(q, k, v, _mesh=mesh, _f=sharded):
            return _f(_mesh, q, k, v)

        return attention
    raise ValueError(
        f"unknown SeqAttention {impl!r} "
        "(auto | full | chunked | flash | ring | ulysses)"
    )


# Single-device attention cutover, measured not guessed (same policy as
# the Pallas embedding constant, models/embeddings.py).  DEFAULT 0 =
# ``auto`` NEVER swaps full -> chunked: where chunked or flash beats
# XLA's fused full attention is not measured on the attached chip, so no
# measured win region exists yet.  chunked/flash stay as explicit
# SeqAttention opt-ins: their value is MEMORY (no S×S materialization —
# full attention physically cannot run once B·H·S² bytes approach HBM),
# and a measured deployment sets STPU_CHUNKED_MIN_SEQ to its own
# feasibility/win boundary.
def _chunked_min_seq() -> int:
    import os

    try:
        return int(os.environ.get("STPU_CHUNKED_MIN_SEQ", "0"))
    except ValueError:
        return 0


#: query and key rows of a flash-kernel tile on the causal path.  The
#: kernel's own default, 128, makes a grid step per 128 x 128 scores: at
#: S 4,096 that is 528 steps a head and pass over the causal triangle
#: (1,024 over the square), and the steps' overhead outweighs their
#: products; at 512 it is 36, and a tile's scores are 1 MB of VMEM.
CAUSAL_FLASH_BLOCK = 512


def _chunked_block() -> int:
    import os

    try:
        return int(os.environ.get("STPU_CHUNKED_BLOCK", "512"))
    except ValueError:
        return 512
