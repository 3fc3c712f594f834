"""Operations and bytes of one training step of the decoder whose blocks
mix the sequence by a gated short convolution or by grouped-query
attention with a norm on every head's q and k (``ModelType: hybrid_lm``
under the public ``lfm2_moe`` keys), from the configuration's shapes and
the tokens a step: the least the algorithm needs for forward + backward.
Recomputation (the program rematerialises every layer and the head) is NOT
useful work and is not counted; nor is element-wise work (norms, rotary,
activations, Adam's arithmetic) where a count is of FLOPs.

Every matrix product ``(tokens, in) x (in, out)`` costs ``2 * tokens * in *
out`` forward and twice that backward.  Attention counts the ``i + 1`` keys
query ``i`` sees at the head's own size (``hidden_size /
num_attention_heads`` = 64 in the shipped cell): the flash kernels pad a
head to 128 lanes, and that padding is not useful work, so it shows as a
lost share.  The tied head's product is counted once (it is one product,
whoever holds the table).

``cfg`` is the configuration's ``model_config.train.params`` (the public
``config.json`` keys and the share).
"""

from __future__ import annotations

# a gated feed-forward's parameters, the held experts' products and bytes
# for the pairs that landed on them, the uniform share of the pairs and the
# keys a causal query sees are the mixed-head and the sliding-window
# decoders' counts, as they are
from benchmark.shapes_mixed_lm import (
    F32,
    experts_bytes,
    experts_flops,
    gated_parameters,
)
from benchmark.shapes_swa_lm import held_pairs, keys_seen

CONV = "conv"


def hidden(cfg: dict) -> int:
    return int(cfg["hidden_size"])


def conv_layers(cfg: dict) -> int:
    return sum(k == CONV for k in cfg["layer_types"])


def attention_layers(cfg: dict) -> int:
    return len(cfg["layer_types"]) - conv_layers(cfg)


def dense_blocks(cfg: dict) -> int:
    return int(cfg.get("num_dense_layers", 0))


def sparse_layers(cfg: dict) -> int:
    return len(cfg["layer_types"]) - dense_blocks(cfg)


def heads_and_dim(cfg: dict):
    """(query heads, KV heads, a head's dimensions)."""
    n = int(cfg["num_attention_heads"])
    return (n, int(cfg["num_key_value_heads"]),
            int(cfg.get("head_dim") or hidden(cfg) // n))


def conv_matrices(cfg: dict) -> int:
    """``W_in`` (hidden -> 3 x hidden) and ``W_out``."""
    return 4 * hidden(cfg) ** 2


def conv_parameters(cfg: dict) -> int:
    """A ``conv`` operator: its two matrices and ``conv_L_cache`` taps a
    channel, no bias."""
    return conv_matrices(cfg) + int(cfg.get("conv_L_cache", 3)) * hidden(cfg)


def attention_matrices(cfg: dict) -> int:
    n, kv, d = heads_and_dim(cfg)
    return 2 * hidden(cfg) * (n + kv) * d


def attention_parameters(cfg: dict) -> int:
    """q, k, v, o and the two head norms' scales."""
    return attention_matrices(cfg) + 2 * heads_and_dim(cfg)[2]


def sparse_parameters(cfg: dict) -> int:
    """A sparse feed-forward: the router's kernel and the expert bias
    (which rests at zero), the held experts; no shared expert."""
    return ((hidden(cfg) + 1) * int(cfg["num_experts"])
            + int(cfg["experts_held"][1])
            * gated_parameters(cfg, "moe_intermediate_size"))


def parameter_count(cfg: dict) -> int:
    """Every element of the parameter tree: the blocks with their two
    norms, the embedding (which is also the head) and the final norm; the
    expert biases (one ``num_experts`` wide a sparse layer; no gradient
    reaches them) included."""
    d = hidden(cfg)
    return (conv_layers(cfg) * conv_parameters(cfg)
            + attention_layers(cfg) * attention_parameters(cfg)
            + len(cfg["layer_types"]) * 2 * d
            + dense_blocks(cfg) * gated_parameters(cfg, "intermediate_size")
            + sparse_layers(cfg) * sparse_parameters(cfg)
            + int(cfg["vocab_size"]) * d + d)


def core_flops(cfg: dict, tokens: int, seq: int) -> float:
    """One layer's scores and values products at the head's own size, fwd
    + bwd."""
    n, _, d = heads_and_dim(cfg)
    return 3.0 * (tokens // seq) * keys_seen(seq, None) * n * 2 * (2 * d)


def core_bytes(cfg: dict, tokens: int) -> float:
    """q read, k and v read at their own heads and o written forward; q,
    k, v, o and dO read and dq, dk and dv written backward (the repeat of
    a KV head over its query heads is not the algorithm's)."""
    n, kv, d = heads_and_dim(cfg)
    return float(F32 * tokens * d * (6 * n + 6 * kv))


def mix_bytes(cfg: dict, tokens: int) -> float:
    """One ``conv.mix`` a layer: ``B``, ``C`` and ``u`` read and ``y``
    written forward; ``B``, ``C``, ``u`` and ``dOut`` read and ``dB``,
    ``dC`` and ``du`` written backward (the taps are a few KB)."""
    return float(F32 * tokens * hidden(cfg) * (4 + 7))


def train_step_flops(cfg: dict, tokens: int, seq: int) -> float:
    """Every token through each operator's matrices, the router, the dense
    layers and the tied head; the cores over the keys seen at the head's
    own size; the held experts at uniform routing."""
    d = hidden(cfg)
    every_token = (
        int(cfg["vocab_size"]) * d
        + conv_layers(cfg) * conv_matrices(cfg)
        + attention_layers(cfg) * attention_matrices(cfg)
        + dense_blocks(cfg) * gated_parameters(cfg, "intermediate_size")
        + sparse_layers(cfg) * d * int(cfg["num_experts"]))
    return (6.0 * tokens * every_token
            + attention_layers(cfg) * core_flops(cfg, tokens, seq)
            + sparse_layers(cfg) * experts_flops(
                cfg, held_pairs(cfg, tokens)))
