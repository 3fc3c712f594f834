"""Operations and bytes of one training step of the looped decoder (one
stack of weight-shared full-attention blocks with sandwich norms, run
``total_ut_steps`` times a forward pass, each pass read by an exit gate and
the untied head: ``ModelType: hybrid_lm`` under the public ``ouro`` keys),
from the configuration's shapes and the tokens a step: the least the
algorithm needs for forward + backward.  Recomputation (the program
rematerialises every layer and every head pass) is NOT useful work and is
not counted; nor is element-wise work (norms, rotary, activations, the exit
distribution, Adam's arithmetic) where a count is of FLOPs.

Every matrix product ``(tokens, in) x (in, out)`` costs ``2 * tokens * in *
out`` forward and twice that backward, and runs once a pass: the weights
are shared, the work is not.  Attention counts the ``i + 1`` keys query
``i`` sees.  The gate reads passes 1 .. T - 1 (the last pass takes the
remainder of the exit distribution).

``cfg`` is the configuration's ``model_config.train.params`` (the public
``config.json`` keys).
"""

from __future__ import annotations

from benchmark.shapes_swa_lm import F32, keys_seen


def hidden(cfg: dict) -> int:
    return int(cfg["hidden_size"])


def passes(cfg: dict) -> int:
    return int(cfg["total_ut_steps"])


def blocks(cfg: dict) -> int:
    return len(cfg["layer_types"])


def applications(cfg: dict) -> int:
    """Block applications a forward pass: the blocks, every pass."""
    return passes(cfg) * blocks(cfg)


def heads_and_dim(cfg: dict):
    """(query heads, key/value heads, a head's dimensions)."""
    return (int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"]),
            int(cfg["head_dim"]))


def attention_matrices(cfg: dict) -> int:
    """q, k, v and o."""
    n, kv, d = heads_and_dim(cfg)
    return 2 * hidden(cfg) * (n + kv) * d


def mlp_matrices(cfg: dict) -> int:
    """gate, up and down at ``intermediate_size``."""
    return 3 * hidden(cfg) * int(cfg["intermediate_size"])


def block_parameters(cfg: dict) -> int:
    """The two sub-layers and their four norms (two in, two out)."""
    return attention_matrices(cfg) + mlp_matrices(cfg) + 4 * hidden(cfg)


def parameter_count(cfg: dict) -> int:
    """Every element of the parameter tree: the blocks, the embedding and
    the untied head, the final norm and the exit gate's kernel and bias."""
    d = hidden(cfg)
    return (blocks(cfg) * block_parameters(cfg)
            + 2 * int(cfg["vocab_size"]) * d + d + d + 1)


def core_flops(cfg: dict, tokens: int, seq: int) -> float:
    """One block application's scores and values products, fwd + bwd."""
    n, _, d = heads_and_dim(cfg)
    return 3.0 * (tokens // seq) * keys_seen(seq, None) * n * 2 * (2 * d)


def core_bytes(cfg: dict, tokens: int) -> float:
    """One block application's core: q, k and v read and o written
    forward; q, k, v, o and dO read and dq, dk and dv written backward."""
    n, kv, d = heads_and_dim(cfg)
    return float(F32 * tokens * d * (6 * n + 6 * kv))


def train_step_flops(cfg: dict, tokens: int, seq: int) -> float:
    """Every token through every block's matrices once a pass, the head
    once a pass and the gate on T - 1 passes; the cores over the keys
    seen, once a block application."""
    d = hidden(cfg)
    every_token = (
        applications(cfg) * (attention_matrices(cfg) + mlp_matrices(cfg))
        + passes(cfg) * int(cfg["vocab_size"]) * d
        + (passes(cfg) - 1) * d)
    return (6.0 * tokens * every_token
            + applications(cfg) * core_flops(cfg, tokens, seq))
