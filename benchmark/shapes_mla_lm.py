"""Operations and bytes of one training step of the latent-attention decoder
with a multi-token prediction module (``ModelType: hybrid_lm`` under the
public ``glm4_moe_lite`` keys: the DeepSeek-V3 block), from the
configuration's shapes and the tokens a step: the least the algorithm needs
for forward + backward.  Recomputation (the program rematerialises every
layer and both head passes) is NOT useful work and is not counted; nor is
element-wise work (norms, rotary, activations, Adam's arithmetic).

Every matrix product ``(tokens, in) x (in, out)`` costs ``2 * tokens * in *
out`` forward and twice that backward.  Attention counts the ``i + 1`` keys
query ``i`` sees, scores over ``qk_nope_head_dim + qk_rope_head_dim`` and
values over ``v_head_dim``.  The module's block, its ``W_m`` and the second
head pass are counted over the step's tokens as the main model's are (the
module has targets for two positions a row fewer, the head for one).

``cfg`` is the configuration's ``model_config.train.params`` (the public
``config.json`` keys and the share).
"""

from __future__ import annotations

F32 = 4


def modules(cfg: dict) -> int:
    """Multi-token prediction modules: one more attention + sparse block,
    ``W_m`` and a head pass each."""
    return int(cfg.get("num_nextn_predict_layers", 0))


def dense_blocks(cfg: dict) -> int:
    return int(cfg.get("first_k_dense_replace", 0))


def attention_layers(cfg: dict, with_modules: bool = True) -> int:
    return int(cfg["num_hidden_layers"]) + with_modules * modules(cfg)


def sparse_layers(cfg: dict, with_modules: bool = True) -> int:
    return attention_layers(cfg, with_modules) - dense_blocks(cfg)


def heads_and_dims(cfg: dict):
    """(heads, a query's and key's head, a value's head)."""
    return (int(cfg["num_attention_heads"]),
            int(cfg["qk_nope_head_dim"]) + int(cfg["qk_rope_head_dim"]),
            int(cfg["v_head_dim"]))


def latent_parameters(cfg: dict) -> int:
    """The four matrices onto and from the two latents (``attn.latent`` +
    ``attn.expand``): ``W_qa``, ``W_kva``, ``W_qb``, ``W_kvb``."""
    d, d_q, d_c = (int(cfg["hidden_size"]), int(cfg["q_lora_rank"]),
                   int(cfg["kv_lora_rank"]))
    n, qk, v = heads_and_dims(cfg)
    return (d * d_q + d * (d_c + int(cfg["qk_rope_head_dim"]))
            + d_q * n * qk
            + d_c * n * (int(cfg["qk_nope_head_dim"]) + v))


def attention_parameters(cfg: dict) -> int:
    """One latent attention's matrices (``W_o`` with them) and the two
    latents' norm scales."""
    n, _, v = heads_and_dims(cfg)
    return (latent_parameters(cfg) + n * v * int(cfg["hidden_size"])
            + int(cfg["q_lora_rank"]) + int(cfg["kv_lora_rank"]))


def gated_parameters(cfg: dict, width_key: str) -> int:
    """A gated feed-forward of the width ``cfg[width_key]``: gate, up,
    down (an expert, the shared expert, the dense layer)."""
    return 3 * int(cfg["hidden_size"]) * int(cfg[width_key])


def shared_parameters(cfg: dict) -> int:
    return int(cfg.get("n_shared_experts", 0)) * gated_parameters(
        cfg, "moe_intermediate_size")


def sparse_parameters(cfg: dict) -> int:
    """A sparse feed-forward: the router's kernel and its correction bias
    (which rests at zero), the held experts, the shared expert."""
    return ((int(cfg["hidden_size"]) + 1) * int(cfg["n_routed_experts"])
            + int(cfg["experts_held"][1])
            * gated_parameters(cfg, "moe_intermediate_size")
            + shared_parameters(cfg))


def parameter_count(cfg: dict) -> int:
    """Every element of the parameter tree, the correction biases (one
    ``n_routed_experts`` wide a sparse layer; no gradient reaches them)
    included."""
    d = int(cfg["hidden_size"])
    block = attention_parameters(cfg) + 2 * d
    return (attention_layers(cfg) * block
            + dense_blocks(cfg) * gated_parameters(cfg, "intermediate_size")
            + sparse_layers(cfg) * sparse_parameters(cfg)
            + 2 * int(cfg["vocab_size"]) * d + d
            + modules(cfg) * (2 * d * d + 3 * d))


def held_pairs(cfg: dict, tokens: int) -> float:
    """(token, choice) pairs that land on a held expert in one sparse
    layer, at uniform routing."""
    return (tokens * int(cfg["num_experts_per_tok"])
            * int(cfg["experts_held"][1]) / int(cfg["n_routed_experts"]))


def keys_seen(seq: int) -> int:
    """Sum over a row's queries of the keys each sees: ``i + 1``."""
    return seq * (seq + 1) // 2


def core_flops(cfg: dict, tokens: int, seq: int) -> float:
    """One layer's scores and values products, fwd + bwd."""
    n, qk, v = heads_and_dims(cfg)
    return 3.0 * (tokens // seq) * keys_seen(seq) * n * 2 * (qk + v)


def core_bytes(cfg: dict, tokens: int) -> float:
    """q, k and v read and o written forward; q, k, v, o and dO read and
    dq, dk and dv written backward, every head with its own key and
    value."""
    n, qk, v = heads_and_dims(cfg)
    return float(F32 * tokens * n * (6 * qk + 6 * v))


def latent_flops(cfg: dict, tokens: int) -> float:
    """One layer's four latent products, fwd + bwd."""
    return 6.0 * tokens * latent_parameters(cfg)


def experts_flops(cfg: dict, pairs: float) -> float:
    """One layer's three products over the held experts, fwd + bwd, for
    the (token, choice) ``pairs`` that landed on them."""
    return 3.0 * pairs * 2 * gated_parameters(cfg, "moe_intermediate_size")


def experts_bytes(cfg: dict, pairs: float) -> float:
    """The held experts' weights read forward and backward and their
    gradients written once; each pair's row read and written each way."""
    weights = int(cfg["experts_held"][1]) * gated_parameters(
        cfg, "moe_intermediate_size")
    return float(F32 * (3 * weights + 4 * pairs * int(cfg["hidden_size"])))


def module_flops(cfg: dict, tokens: int, seq: int) -> float:
    """What the multi-token prediction modules add to the step: a block's
    products, ``W_m`` (two hidden sizes onto one) and a head pass each."""
    d = int(cfg["hidden_size"])
    every_token = (attention_parameters(cfg) - int(cfg["q_lora_rank"])
                   - int(cfg["kv_lora_rank"])
                   + d * int(cfg["n_routed_experts"])
                   + shared_parameters(cfg)
                   + 2 * d * d + int(cfg["vocab_size"]) * d)
    return modules(cfg) * (
        6.0 * tokens * every_token + core_flops(cfg, tokens, seq)
        + experts_flops(cfg, held_pairs(cfg, tokens)))


def train_step_flops(cfg: dict, tokens: int, seq: int) -> float:
    """Every token through each attention's matrices, the router, the
    shared expert, the dense layers and the head; the cores over the keys
    seen; the held experts at uniform routing; the modules."""
    d = int(cfg["hidden_size"])
    blocks = attention_layers(cfg, False)
    every_token = (
        int(cfg["vocab_size"]) * d
        + blocks * (attention_parameters(cfg) - int(cfg["q_lora_rank"])
                    - int(cfg["kv_lora_rank"]))
        + dense_blocks(cfg) * gated_parameters(cfg, "intermediate_size")
        + sparse_layers(cfg, False) * (d * int(cfg["n_routed_experts"])
                                       + shared_parameters(cfg)))
    return (6.0 * tokens * every_token
            + blocks * core_flops(cfg, tokens, seq)
            + sparse_layers(cfg, False) * experts_flops(
                cfg, held_pairs(cfg, tokens))
            + module_flops(cfg, tokens, seq))
