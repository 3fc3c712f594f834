"""Operations and bytes of one training step of the sliding-window + full
attention, gated-expert decoder (``ModelType: hybrid_lm`` under the public
``mellum`` keys), from the configuration's shapes and the tokens a step:
the least the algorithm needs for forward + backward.  Recomputation (the
program rematerialises every layer) is NOT useful work and is not counted;
nor is element-wise work (norms, rotary, activations, Adam's arithmetic).

Every matrix product ``(tokens, in) x (in, out)`` costs ``2 * tokens * in *
out`` forward and twice that backward.  Attention counts the keys a query
sees: ``i + 1`` on a full layer, ``min(i + 1, sliding_window)`` on a
sliding one.

``cfg`` is the configuration's ``model_config.train.params`` (the public
``config.json`` keys and the share).
"""

from __future__ import annotations

F32 = 4
SLIDING, FULL = "sliding_attention", "full_attention"


def _attn_dims(cfg):
    return (int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"]),
            int(cfg["head_dim"]))


def attention_parameters(cfg: dict) -> int:
    d = int(cfg["hidden_size"])
    nq, nkv, hd = _attn_dims(cfg)
    return 2 * d * nq * hd + 2 * d * nkv * hd


def expert_parameters(cfg: dict) -> int:
    """One gated expert: gate, up, down."""
    return 3 * int(cfg["hidden_size"]) * int(cfg["moe_intermediate_size"])


def parameter_count(cfg: dict) -> int:
    d = int(cfg["hidden_size"])
    block = (attention_parameters(cfg) + d * int(cfg["num_experts"])
             + int(cfg["experts_held"][1]) * expert_parameters(cfg) + 2 * d)
    return (len(cfg["layer_types"]) * block
            + 2 * int(cfg["vocab_size"]) * d + d)


def layers_of(cfg: dict, kind: str) -> int:
    return list(cfg["layer_types"]).count(kind)


def held_pairs(cfg: dict, tokens: int) -> float:
    """(token, choice) pairs that land on a held expert in one expert
    layer, at uniform routing."""
    return (tokens * int(cfg["num_experts_per_tok"])
            * int(cfg["experts_held"][1]) / int(cfg["num_experts"]))


def keys_seen(seq: int, window: "int | None") -> int:
    """Sum over a row's queries of the keys each sees."""
    if window is None or window >= seq:
        return seq * (seq + 1) // 2
    return window * (window + 1) // 2 + (seq - window) * window


def _window(cfg: dict, kind: str):
    return int(cfg["sliding_window"]) if kind == SLIDING else None


def attention_flops(cfg: dict, tokens: int, seq: int, kind: str) -> float:
    """One layer's scores and values products, fwd + bwd, over the keys
    its queries see."""
    nq, _, hd = _attn_dims(cfg)
    pairs = (tokens // seq) * keys_seen(seq, _window(cfg, kind))
    return 3.0 * pairs * nq * 2 * 2 * hd


def attention_bytes(cfg: dict, tokens: int) -> float:
    """q read and o written forward beside k and v at their own heads;
    q, o, dO, k, v read and dq, dk, dv written backward."""
    nq, nkv, hd = _attn_dims(cfg)
    return float(F32 * tokens * hd * (2 * nq + 2 * nkv + 4 * nq + 4 * nkv))


def gated_experts_flops(cfg: dict, pairs: float) -> float:
    """One layer's three products over the held experts, fwd + bwd, for
    the (token, choice) ``pairs`` that landed on them."""
    d, f = int(cfg["hidden_size"]), int(cfg["moe_intermediate_size"])
    return 3.0 * pairs * 3 * 2 * d * f


def gated_experts_bytes(cfg: dict, pairs: float) -> float:
    """The held experts' weights read forward and backward and their
    gradients written once; each pair's row read and written each way."""
    weights = int(cfg["experts_held"][1]) * expert_parameters(cfg)
    return float(F32 * (3 * weights + 4 * pairs * int(cfg["hidden_size"])))


def train_step_flops(cfg: dict, tokens: int, seq: int) -> float:
    d = int(cfg["hidden_size"])
    dense = (attention_parameters(cfg) + d * int(cfg["num_experts"]))
    products = (len(cfg["layer_types"]) * dense
                + int(cfg["vocab_size"]) * d)
    cores = sum(attention_flops(cfg, tokens, seq, kind)
                for kind in cfg["layer_types"])
    experts = len(cfg["layer_types"]) * gated_experts_flops(
        cfg, held_pairs(cfg, tokens))
    return 6.0 * tokens * products + cores + experts
