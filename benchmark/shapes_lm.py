"""Operations and bytes of one training step of the hybrid decoder family,
from the configuration's shapes and the tokens a step: the least the
algorithm needs for forward + backward.  Recomputation (the program
rematerialises every layer) is NOT useful work and is not counted; nor is
element-wise work (norms, activations, the convolution, Adam's arithmetic).

Every matrix product ``(tokens, in) x (in, out)`` costs ``2 * tokens * in *
out`` forward and twice that backward.  Causal products (attention, the
inside of a scan chunk) count the half of the square they need.

``cfg`` is the configuration's ``model_config.train.params`` (the public
``config.json`` keys and the share).
"""

from __future__ import annotations

F32 = 4


def _mamba_dims(cfg):
    inner = int(cfg["mamba_num_heads"]) * int(cfg["mamba_head_dim"])
    bc = 2 * int(cfg["n_groups"]) * int(cfg["ssm_state_size"])
    return inner, bc, int(cfg["mamba_num_heads"])


def parameter_count(cfg: dict) -> int:
    d = int(cfg["hidden_size"])
    inner, bc, heads = _mamba_dims(cfg)
    conv_dim = inner + bc
    mamba = (d * (inner + conv_dim + heads) + inner * d
             + conv_dim * (int(cfg["conv_kernel"]) + 1) + 3 * heads + inner)
    nq, nkv, hd = (int(cfg["num_attention_heads"]),
                   int(cfg["num_key_value_heads"]), int(cfg["head_dim"]))
    attn = 2 * d * nq * hd + 2 * d * nkv * hd
    held = int(cfg["experts_held"][1])
    routed = int(cfg["n_routed_experts"])
    moe = (d * routed + routed
           + 2 * d * int(cfg["moe_shared_expert_intermediate_size"])
           * int(cfg.get("n_shared_experts", 1))
           + held * 2 * d * int(cfg["moe_intermediate_size"]))
    per = {"M": mamba, "*": attn, "E": moe}
    layers = sum(per[k] + d for k in cfg["hybrid_override_pattern"])
    return layers + 2 * int(cfg["vocab_size"]) * d + d


def held_pairs(cfg: dict, tokens: int) -> float:
    """(token, choice) pairs that land on a held expert in one expert
    layer, at uniform routing."""
    return (tokens * int(cfg["num_experts_per_tok"])
            * int(cfg["experts_held"][1]) / int(cfg["n_routed_experts"]))


def ssm_scan_flops(cfg: dict, tokens: int) -> float:
    """One layer's chunked scan, forward + backward: the scores of a chunk
    (its causal half), their product with x, the chunk's state and the
    entering state's share of the output."""
    heads, p = int(cfg["mamba_num_heads"]), int(cfg["mamba_head_dim"])
    g, n, chunk = (int(cfg["n_groups"]), int(cfg["ssm_state_size"]),
                   int(cfg["chunk_size"]))
    per_token = (chunk * g * n + chunk * heads * p   # causal halves of 2x
                 + 2 * heads * p * n + 2 * heads * p * n)
    return 3.0 * tokens * per_token


def ssm_scan_bytes(cfg: dict, tokens: int) -> float:
    """x, B, C, dt read and y written forward; read again with dy and the
    four gradients written backward."""
    inner, bc, heads = _mamba_dims(cfg)
    fwd = inner + bc + heads + inner
    bwd = inner + bc + heads + inner + inner + bc + heads
    return float(F32 * tokens * (fwd + bwd))


def moe_experts_flops(cfg: dict, pairs: float) -> float:
    """One layer's routed products over the held experts, fwd + bwd, for
    the (token, choice) ``pairs`` that landed on them."""
    d, f = int(cfg["hidden_size"]), int(cfg["moe_intermediate_size"])
    return 3.0 * pairs * 2 * 2 * d * f


def moe_experts_bytes(cfg: dict, pairs: float) -> float:
    """The held experts' weights read forward and backward and their
    gradients written once; each pair's row read and written each way."""
    d, f = int(cfg["hidden_size"]), int(cfg["moe_intermediate_size"])
    weights = int(cfg["experts_held"][1]) * 2 * d * f
    return float(F32 * (3 * weights + 4 * pairs * d))


def attention_flops(cfg: dict, tokens: int, seq: int) -> float:
    """One layer's causal scores and values products, fwd + bwd."""
    nq, hd = int(cfg["num_attention_heads"]), int(cfg["head_dim"])
    return 3.0 * tokens * 2 * seq * hd * nq  # half of 2 x (2 S hd nq)


def train_step_flops(cfg: dict, tokens: int, seq: int) -> float:
    d = int(cfg["hidden_size"])
    inner, bc, heads = _mamba_dims(cfg)
    nq, nkv, hd = (int(cfg["num_attention_heads"]),
                   int(cfg["num_key_value_heads"]), int(cfg["head_dim"]))
    dense = {
        "M": d * (2 * inner + bc + heads) + inner * d,
        "*": 2 * d * nq * hd + 2 * d * nkv * hd,
        "E": d * int(cfg["n_routed_experts"])
        + 2 * d * int(cfg["moe_shared_expert_intermediate_size"])
        * int(cfg.get("n_shared_experts", 1)),
    }
    extra = {"M": ssm_scan_flops(cfg, tokens),
             "*": attention_flops(cfg, tokens, seq),
             "E": moe_experts_flops(cfg, held_pairs(cfg, tokens))}
    pattern = cfg["hybrid_override_pattern"]
    products = sum(dense[k] for k in pattern) + int(cfg["vocab_size"]) * d
    return 6.0 * tokens * products + sum(extra[k] for k in pattern)


def layers_of(cfg: dict, kind: str) -> int:
    return cfg["hybrid_override_pattern"].count(kind)


def least_seconds(flops: float, nbytes: float, peaks: dict) -> float:
    return max(flops / peaks["flops_per_s"], nbytes / peaks["bytes_per_s"])
