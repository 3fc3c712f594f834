"""Model factory: ModelConfig.json → flax module.

Parity surface: the reference's ``generate_from_modelconf`` builds the net
from ``train.params`` at graph-construction time (ssgd_monitor.py:91-127);
here the same JSON contract selects and parameterizes a module from the
model zoo.  ``model_type`` extends the contract to the BASELINE.json
families; absent, it defaults to the reference's plain DNN.
"""

from __future__ import annotations

import flax.linen as nn
import jax.numpy as jnp

from shifu_tensorflow_tpu.config.model_config import ModelConfig, TrainParams
from shifu_tensorflow_tpu.models.dnn import ShifuDNN
from shifu_tensorflow_tpu.models.embeddings import HashedEmbedding
from shifu_tensorflow_tpu.models.multi_task import MultiTaskDNN
from shifu_tensorflow_tpu.models.wide_deep import WideDeep


class EmbeddingAugmented(nn.Module):
    """Wraps a base model: hashed-embeds designated columns and concatenates
    the embeddings to the raw features before the base net (BASELINE.json
    config #4)."""

    base: nn.Module
    embed_indices: tuple[int, ...]
    hash_size: int
    embed_dim: int
    dtype: jnp.dtype = jnp.float32
    shard_table: bool = True
    mesh: "jax.sharding.Mesh | None" = None

    @nn.compact
    def __call__(self, x):
        emb = HashedEmbedding(
            hash_size=self.hash_size, features=self.embed_dim,
            dtype=self.dtype, shard_table=self.shard_table,
            mesh=self.mesh, name="hashed_columns",
        )(x[:, jnp.asarray(self.embed_indices)])
        return self.base(jnp.concatenate([x, emb], axis=-1))


def _column_positions(column_nums, feature_columns) -> tuple[int, ...]:
    """Map absolute column numbers to positions within the selected feature
    vector (features arrive already projected to feature_columns order)."""
    pos = {c: i for i, c in enumerate(feature_columns)}
    out = []
    for c in column_nums:
        if c in pos:
            out.append(pos[c])
    return tuple(out)


def build_model(
    model_config: ModelConfig,
    feature_columns: tuple[int, ...] | None = None,
    dtype: jnp.dtype = jnp.float32,
    shard_embeddings: bool = True,
    mesh=None,
) -> nn.Module:
    """``shard_embeddings=False`` (no 'model' mesh axis present) drops the
    table's partitioning annotation.  ``mesh`` is what the step's ``jit``
    partitions over: the sequence family picks its attention by it
    (ring/Ulysses need the 'seq' axis) and the hashed embedding runs its
    backward per device on it (models/embeddings.py ``take_rows``)."""
    p: TrainParams = model_config.params
    nodes = p.num_hidden_nodes[: p.num_hidden_layers]
    acts = p.activation_funcs[: p.num_hidden_layers]

    if p.model_type == "hybrid_lm":
        import jax

        from shifu_tensorflow_tpu.models.hybrid_lm import HybridLM
        from shifu_tensorflow_tpu.models.sequence import make_attention

        # the Pallas kernel has no partitioning rule and no CPU lowering;
        # the chunked scan is plain XLA and runs anywhere
        single = mesh is None or mesh.size == 1
        impl = ("flash" if single and jax.default_backend() == "tpu"
                else "chunked")
        window = p.hybrid_lm.sliding_window
        return HybridLM(
            cfg=p.hybrid_lm,
            attention=make_attention(impl, None, causal=True),
            dtype=dtype,
            window_attention=(
                make_attention(impl, None, causal=True, window=window)
                if "W" in p.hybrid_lm.hybrid_override_pattern else None),
        )
    if p.seq_len > 0 and p.model_type != "sequence":
        raise ValueError(
            f"SeqLen={p.seq_len} conflicts with ModelType={p.model_type!r}: "
            "sequence params only apply to ModelType=sequence"
        )
    if p.model_type == "sequence":
        from shifu_tensorflow_tpu.models.sequence import (
            SequenceClassifier,
            make_attention,
        )

        if p.seq_len <= 0:
            raise ValueError("ModelType=sequence requires SeqLen > 0")
        if p.seq_d_model % p.seq_heads:
            raise ValueError(
                f"SeqDModel={p.seq_d_model} not divisible by "
                f"SeqHeads={p.seq_heads}"
            )
        return SequenceClassifier(
            seq_len=p.seq_len,
            d_model=p.seq_d_model,
            num_heads=p.seq_heads,
            num_blocks=p.seq_blocks,
            attention=make_attention(
                p.seq_attention, mesh,
                seq_len=p.seq_len, num_heads=p.seq_heads,
            ),
            dtype=dtype,
            remat=p.seq_remat,
        )

    if p.model_type == "wide_deep":
        wide_idx = (
            _column_positions(p.wide_column_nums, feature_columns)
            if feature_columns and p.wide_column_nums
            else tuple()
        )
        base: nn.Module = WideDeep(
            hidden_nodes=nodes, activations=acts, wide_indices=wide_idx,
            cross_hash_size=p.cross_hash_size if p.wide_column_nums else 0,
            dtype=dtype,
        )
    elif p.model_type == "multi_task":
        base = MultiTaskDNN(
            hidden_nodes=nodes, activations=acts, num_tasks=p.num_tasks,
            dtype=dtype,
        )
    else:
        base = ShifuDNN(hidden_nodes=nodes, activations=acts, dtype=dtype)

    if (p.embedding_columns and p.embedding_hash_size > 0
            and p.embedding_placement != "host"):
        embed_idx = (
            _column_positions(p.embedding_columns, feature_columns)
            if feature_columns
            else tuple(range(len(p.embedding_columns)))
        )
        if embed_idx:
            return EmbeddingAugmented(
                base=base, embed_indices=embed_idx,
                hash_size=p.embedding_hash_size, embed_dim=p.embedding_dim,
                dtype=dtype, shard_table=shard_embeddings,
                mesh=mesh,
            )
    # EmbeddingPlacement=host: the gather happens on the HOST (the table
    # exceeds HBM by assumption — models/host_embedding.py); the Trainer
    # widens the base model's input with the gathered embeddings, so the
    # device graph here is just the base net over the augmented features
    return base


def family_loss(model: nn.Module):
    """The loss a model family brings itself, or ``None`` for the families
    whose loss is ``ops/losses.py``'s on a ``(B, 1)`` prediction: a
    callable ``(params, batch) -> (loss, per-row loss (B, 1), counters)``
    that the trainer's step builders differentiate in place of
    ``get_loss(name)(apply(x), y, w)`` (train/trainer.py)."""
    from shifu_tensorflow_tpu.models import hybrid_lm

    if isinstance(model, hybrid_lm.HybridLM):
        return hybrid_lm.batch_loss(model)
    return None
