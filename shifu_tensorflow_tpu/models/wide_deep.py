"""Wide & Deep binary classifier (BASELINE.json config #2).

Beyond-reference capability: the reference only ships the plain DNN, but the
north-star workload list includes "Wide & Deep binary classifier with
crossed categorical feature columns" (BASELINE.json configs).  TPU-first
design: the wide part is a single fused matmul over the designated wide
feature slice plus an optional hashed-cross embedding lookup; the deep part
reuses the DenseTower; logits are summed before one sigmoid, so the whole
model is two matmul chains XLA fuses trivially.
"""

from __future__ import annotations

from typing import Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp

from shifu_tensorflow_tpu.models.dnn import DenseTower, _xavier_bias_init
from shifu_tensorflow_tpu.models.embeddings import HashedCross


class WideDeep(nn.Module):
    """wide linear (+ optional hashed-cross table) + deep tower, summed
    logits, sigmoid output."""

    hidden_nodes: Sequence[int]
    activations: Sequence[str]
    wide_indices: tuple[int, ...] = ()  # positions in the feature vector
    cross_hash_size: int = 0  # >0 enables a hashed-cross wide table
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        # Flax already names the modules on the stack (deep/hidden_layer0
        # ...); this one name is the phase `obs profile --phases` sums
        with jax.named_scope("deep.mlp"):
            deep = DenseTower(self.hidden_nodes, self.activations,
                              self.dtype, name="deep")(x)
            deep_logit = nn.Dense(
                1, kernel_init=nn.initializers.xavier_uniform(),
                bias_init=_xavier_bias_init, dtype=self.dtype,
                name="deep_logit",
            )(deep)

        wide_x = x[:, jnp.asarray(self.wide_indices)] if self.wide_indices else x
        wide_logit = nn.Dense(
            1, kernel_init=nn.initializers.zeros_init(),
            use_bias=False, dtype=self.dtype, name="wide_logit",
        )(wide_x)

        logit = deep_logit + wide_logit
        if self.cross_hash_size > 0:
            # crossed categorical: hash the wide slice jointly into one id
            # per row and look up a scalar weight (classic wide&deep cross)
            logit = logit + HashedCross(
                hash_size=self.cross_hash_size, features=1, name="wide_cross",
                dtype=self.dtype,
            )(wide_x)
        return nn.sigmoid(logit)
