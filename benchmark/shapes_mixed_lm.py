"""Operations and bytes of one training step of the decoder whose attention
layers differ in head count by layer type, with dense gated feed-forward
blocks and sigmoid-scored gated experts beside a gated shared expert
(``ModelType: hybrid_lm`` under the public ``laguna`` keys), from the
configuration's shapes and the tokens a step: the least the algorithm needs
for forward + backward.  Recomputation (the program rematerialises every
layer) is NOT useful work and is not counted; nor is element-wise work
(norms, rotary, activations, Adam's arithmetic).

Every matrix product ``(tokens, in) x (in, out)`` costs ``2 * tokens * in *
out`` forward and twice that backward.  Attention counts the keys a query
sees: ``i + 1`` on a full layer, ``min(i + 1, sliding_window)`` on a
sliding one.

``cfg`` is the configuration's ``model_config.train.params`` (the public
``config.json`` keys and the share).
"""

from __future__ import annotations

# the uniform share of the pairs and the keys a query sees are the
# sliding-window decoder's counts, as they are
from benchmark.shapes_swa_lm import FULL, SLIDING, held_pairs, keys_seen

F32 = 4


def heads_of(cfg: dict, kind: str) -> int:
    """Query heads of a layer of ``kind``: its entry of
    ``num_attention_heads_per_layer``."""
    for k, n in zip(cfg["layer_types"], cfg["num_attention_heads_per_layer"]):
        if k == kind:
            return int(n)
    raise KeyError(kind)


def blocks_of(cfg: dict, kind: str) -> int:
    """Blocks whose attention (``layer_types``) or feed-forward
    (``mlp_layer_types``) is of ``kind``."""
    return (list(cfg["layer_types"]) + list(cfg["mlp_layer_types"])).count(
        kind)


def attention_parameters(cfg: dict, kind: str) -> int:
    d, hd = int(cfg["hidden_size"]), int(cfg["head_dim"])
    return 2 * d * hd * (heads_of(cfg, kind)
                         + int(cfg["num_key_value_heads"]))


def gated_parameters(cfg: dict, width_key: str) -> int:
    """A gated feed-forward of the width ``cfg[width_key]``: gate, up,
    down (an expert, the shared expert, the dense layer)."""
    return 3 * int(cfg["hidden_size"]) * int(cfg[width_key])


def router_parameters(cfg: dict) -> int:
    """The router's kernel and the correction bias that rests at zero."""
    return (int(cfg["hidden_size"]) + 1) * int(cfg["num_experts"])


def feed_forward_parameters(cfg: dict, kind: str) -> int:
    if kind == "dense":
        return gated_parameters(cfg, "intermediate_size")
    return (router_parameters(cfg)
            + int(cfg["experts_held"][1])
            * gated_parameters(cfg, "moe_intermediate_size")
            + gated_parameters(cfg, "shared_expert_intermediate_size"))


def parameter_count(cfg: dict) -> int:
    """Every element of the parameter tree, the correction biases (one
    ``num_experts`` wide a sparse block; no gradient reaches them)
    included."""
    d = int(cfg["hidden_size"])
    blocks = sum(attention_parameters(cfg, a) + feed_forward_parameters(cfg, m)
                 + 2 * d
                 for a, m in zip(cfg["layer_types"], cfg["mlp_layer_types"]))
    return blocks + 2 * int(cfg["vocab_size"]) * d + d


def attention_flops(cfg: dict, tokens: int, seq: int, kind: str) -> float:
    """One layer's scores and values products, fwd + bwd, over the keys
    its queries see, at the layer type's heads."""
    window = int(cfg["sliding_window"]) if kind == SLIDING else None
    pairs = (tokens // seq) * keys_seen(seq, window)
    return 3.0 * pairs * heads_of(cfg, kind) * 2 * 2 * int(cfg["head_dim"])


def attention_bytes(cfg: dict, tokens: int, kind: str) -> float:
    """q read and o written forward beside k and v at their own heads;
    q, o, dO, k, v read and dq, dk, dv written backward."""
    nq, nkv = heads_of(cfg, kind), int(cfg["num_key_value_heads"])
    return float(F32 * tokens * int(cfg["head_dim"])
                 * (2 * nq + 2 * nkv + 4 * nq + 4 * nkv))


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


def train_step_flops(cfg: dict, tokens: int, seq: int) -> float:
    """Every token through attention's projections, the router, the shared
    expert, the dense layer and the head; the cores over the keys seen;
    the held experts at uniform routing."""
    d = int(cfg["hidden_size"])
    every_token = int(cfg["vocab_size"]) * d
    cores = experts = 0.0
    for a, m in zip(cfg["layer_types"], cfg["mlp_layer_types"]):
        every_token += attention_parameters(cfg, a)
        cores += attention_flops(cfg, tokens, seq, a)
        if m == "dense":
            every_token += gated_parameters(cfg, "intermediate_size")
        else:
            every_token += d * int(cfg["num_experts"]) + gated_parameters(
                cfg, "shared_expert_intermediate_size")
            experts += experts_flops(cfg, held_pairs(cfg, tokens))
    return 6.0 * tokens * every_token + cores + experts
