"""ModelConfig.json / ColumnConfig.json ingestion.

Parity surface: the reference builds its network **dynamically** from Shifu's
``ModelConfig.json`` — ``train.numTrainEpochs``, ``train.validSetRate`` and
``train.params.{NumHiddenLayers, NumHiddenNodes, ActivationFunc,
LearningRate}`` (reference: ssgd_monitor.py:91-107,177-183) — and receives the
selected/target/weight column numbers through env vars that the Java client
derives from ``ColumnConfig.json`` (TensorflowClient.java:378-382,
TensorflowTaskExecutor.java:200-238).

Here both files are first-class typed objects.  ``ModelConfig`` additionally
understands the model families this framework adds beyond the reference's
plain DNN (Wide & Deep, multi-task heads, hashed embeddings — the
BASELINE.json config matrix) via optional ``train.params`` fields, all with
defaults that reproduce the reference behavior when absent.
"""

from __future__ import annotations

import json
import os
from dataclasses import dataclass, field
from typing import Any, Mapping, Sequence


def _parse_bool(v: Any) -> bool:
    """Same token set as Conf.get_bool (config/conf.py): a value that
    counts as true in one config surface must count everywhere."""
    if isinstance(v, bool):
        return v
    return str(v).strip().lower() in ("true", "1", "yes", "on")


class UnsupportedModelType(ValueError):
    """A plane that cannot run a model family refuses it by name: export,
    ``EvalModel``, ``serve/``, ``score/`` and the C++ scorer score one
    fixed-width row to one ``(B, 1)`` sigmoid, which a causal language
    model is not."""

    def __init__(self, model_type: str, plane: str):
        self.model_type, self.plane = model_type, plane
        super().__init__(
            f"ModelType={model_type!r} is a training-only family: "
            f"{plane} has no path for it (no scorer, artifact or serving "
            "contract exists for a per-token model)")


#: families that train through ``Trainer.fit_stream`` and nothing else
TRAINING_ONLY_MODEL_TYPES = ("hybrid_lm",)


def require_servable(model_type: str, plane: str) -> None:
    if str(model_type).lower() in TRAINING_ONLY_MODEL_TYPES:
        raise UnsupportedModelType(str(model_type).lower(), plane)


#: ``layer_types`` / ``mlp_layer_types`` entries -> pattern characters
#: (``conv``, a gated short convolution, is a block's operator where the
#: others are its attention)
CONV = "conv"
_ATTENTION_KINDS = {"full_attention": "*", "sliding_attention": "W",
                    CONV: "C"}
_MLP_KINDS = {"sparse": "E", "dense": "D"}
#: a pattern's attention characters -> the layer type whose heads and rotary
#: they take; ``L`` (latent attention) is no ``layer_types`` entry: its
#: family has no such list (``HybridLMConfig.latent_family``)
LATENT = "latent_attention"
_PATTERN_LAYER_TYPES = {**{v: k for k, v in _ATTENTION_KINDS.items()},
                        "L": LATENT}


@dataclass(frozen=True)
class RopeParameters:
    """One entry of a public config's ``rope_parameters``: ``default``
    (``theta^(-m / (head_dim / 2))``) or ``yarn`` (those frequencies
    blended with their ``1 / factor`` interpolation between the
    ``beta_fast`` and ``beta_slow`` correction dimensions, cos and sin
    scaled by ``attention_factor``; 0 = ``0.1 ln(factor) + 1``).
    ``partial_rotary_factor``: the share of a head's dimensions, from the
    first, that turn (the frequencies are then those of a head of that
    many dimensions); the rest pass through unchanged."""

    rope_type: str = "default"
    rope_theta: float = 10000.0
    factor: float = 1.0
    original_max_position_embeddings: int = 0
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 0.0
    partial_rotary_factor: float = 1.0

    def rotary_dim(self, head_dim: int) -> int:
        return int(head_dim * self.partial_rotary_factor)

    @classmethod
    def from_json(cls, obj: Mapping[str, Any]) -> "RopeParameters":
        import dataclasses

        known = {f.name: f.type for f in dataclasses.fields(cls)}
        unknown = sorted(set(obj) - set(known))
        if unknown:
            raise ValueError(
                f"rope_parameters keys {unknown} are not implemented "
                f"(known: {sorted(known)})")
        cast = {"str": str, "float": float, "int": int}
        rope = cls(**{k: cast[known[k]](v) for k, v in obj.items()})
        if rope.rope_type not in ("default", "yarn"):
            raise ValueError(
                f"rope_type={rope.rope_type!r} is not implemented "
                "(default | yarn)")
        if rope.rope_type == "yarn" and (
                rope.factor <= 1.0
                or rope.original_max_position_embeddings <= 0):
            raise ValueError(
                "rope_type=yarn needs factor > 1 and "
                "original_max_position_embeddings")
        if not 0.0 < rope.partial_rotary_factor <= 1.0:
            raise ValueError(
                f"partial_rotary_factor={rope.partial_rotary_factor} is "
                "not a share of the head in (0, 1]")
        return rope


@dataclass(frozen=True)
class HybridLMConfig:
    """``ModelType: hybrid_lm`` — the keys of a public ``config.json``
    under their own names (``train.params`` carries them beside
    ``ModelType``), plus the share this chip holds.  Six public shapes
    are read:

    - ``nemotron_h``: ``hybrid_override_pattern``, one mixer a layer (``M``
      Mamba-2, ``E`` experts, ``*`` causal attention, ``W`` causal
      attention inside ``sliding_window``), sigmoid-scored ``relu2``
      experts beside a shared expert, no rotary;
    - ``mellum``: ``layer_types`` + ``mlp_layer_types``, a block a list
      entry; block ``i`` becomes two layers of the pattern, its attention
      (``sliding_attention`` -> ``W``, ``full_attention`` -> ``*``) and
      its ``sparse`` experts (``E``); ``rope_parameters`` per layer type
      (``default`` | ``yarn``), ``hidden_act: silu`` (the gated expert
      ``W_down(silu(W_gate h) * W_up h)``), ``scoring_func: softmax``,
      ``n_shared_experts: 0``, ``num_experts`` for the router's width and
      ``rms_norm_eps`` for the norms';
    - ``laguna``: the ``mellum`` keys, and ``mlp_layer_types`` with
      ``dense`` (``D``: the gated feed-forward ``W_down(silu(W_gate h) *
      W_up h)`` of width ``intermediate_size``, no router),
      ``num_attention_heads_per_layer`` (one number a layer type: the
      query heads of a ``*`` and of a ``W`` layer differ, over the same
      ``num_key_value_heads``), ``partial_rotary_factor`` inside a
      ``rope_parameters`` entry, sigmoid scores scaled by
      ``moe_routed_scaling_factor`` over gated experts beside a gated
      shared expert of ``shared_expert_intermediate_size`` (with
      ``hidden_act: silu`` the shared expert takes the experts' form).
      ``gating: true`` is read as that gated feed-forward and adds
      nothing further (a gate on attention's output is not implemented);
    - ``glm4_moe_lite`` (the DeepSeek-V3 block): no list of layers at all.
      ``kv_lora_rank`` marks the family (:meth:`latent_family`): every
      block's attention is latent (``L``: ``q_lora_rank`` / ``kv_lora_rank``
      low-rank paths with an RMSNorm on each latent, ``qk_nope_head_dim`` +
      ``qk_rope_head_dim`` a query and key head of which the second part
      turns and, on the key, is one for all heads, ``v_head_dim`` a value
      head), the first ``first_k_dense_replace`` of ``num_hidden_layers``
      blocks' feed-forward dense (``D``) and the others' sparse (``E``),
      ``topk_method: noaux_tc`` (sigmoid scores, top-k of score +
      correction bias), the shared expert ``n_shared_experts`` x
      ``moe_intermediate_size`` wide, rotary from ``rope_theta`` with
      ``rope_scaling: null``, and ``num_nextn_predict_layers`` multi-token
      prediction modules (0 or 1: one more block of the last block's kinds
      over ``[RMSNorm(h_i) ; RMSNorm(Emb(t_{i+1}))] W_m``, its own final
      norm, the shared head; the step's loss is the next-token loss +
      ``mtp_loss_weight`` x the module's, which is no public key);
    - ``lfm2_moe``: ``layer_types`` with ``conv`` entries marks the family
      (:meth:`conv_family`).  A ``conv`` block's operator is a gated short
      convolution (``C``: ``[B ; C ; u] = x W_in``, a depth-wise causal
      convolution of ``conv_L_cache`` taps over ``B * u``, no bias
      (``conv_bias: false``) and no activation, ``out = (C * conv) W_out``),
      a ``full_attention`` block's grouped-query attention with an RMSNorm
      over each head's q and k before the rotation (``qk_norm``; one scale
      for the query heads and one for the key heads) at a head of
      ``hidden_size / num_attention_heads`` where no ``head_dim`` says
      otherwise; no ``mlp_layer_types``: the first ``num_dense_layers``
      blocks' feed-forward dense (``D``), the others' sparse (``E``);
      ``rope_parameters`` ONE parametrisation (``rope_theta``,
      ``rope_type``) and no entry a layer type; ``norm_eps``;
      ``use_expert_bias: true`` (sigmoid scores, top-k of score + a bias
      that rests), gated ``silu`` experts and no shared expert, which the
      family has no keys for; the head tied to the embedding
      (``tie_word_embeddings`` / ``tie_embedding``) unless the
      configuration says otherwise;
    - ``ouro``: ``total_ut_steps`` marks the family (:meth:`looped_family`).
      The whole stack of blocks runs ``total_ut_steps`` times a forward
      pass with the same weights, the final norm after every pass (what
      the next pass reads); every block ``full_attention`` over a dense
      feed-forward of ``intermediate_size`` (no ``mlp_layer_types``), an
      RMSNorm on each sub-layer's output before its residual add
      (``post_norms``, set by the family: no public key), rotary from
      ``rope_theta``.  After each pass the head and an exit gate read the
      normed state, and the step's loss is the next-token loss expected
      under the gates' exit distribution less ``exit_entropy_weight`` x
      that distribution's entropy (no public key); ``early_exit_threshold``
      is inference's and changes nothing in training.

    ``n_routed_experts`` is the router's width (all the experts there
    are); ``experts_held`` = (first id, count) the experts whose weights
    live here; ``vocab_size`` is the slice of the vocabulary the embedding
    and the head hold (a sliced vocabulary is a smaller vocabulary);
    ``expert_tile`` the rows of a grouped-product tile (0: the family's,
    models/hybrid_lm.py ``EXPERT_TILE``), sized with the share: a held
    expert's pairs a step should fill whole tiles with room to spare, not
    end on a tile's edge.  A public config states one ``initializer_range``;
    a training recipe may state two more, each 0 for "the same":
    ``embedding_initializer_range`` (the token embedding's) and
    ``output_initializer_range`` (every mixer's projection back onto the
    residual stream: ``o_proj``, the experts' and the shared expert's
    ``down``, Mamba's ``out_proj``; the scaled initialisation of
    pre-training recipes, ``initializer_range / sqrt(2 x blocks)``).  A
    combination the code does not implement is a config error by name."""

    hidden_size: int
    hybrid_override_pattern: str
    vocab_size: int
    layer_norm_epsilon: float = 1e-5
    initializer_range: float = 0.02
    embedding_initializer_range: float = 0.0  # 0 = initializer_range
    output_initializer_range: float = 0.0  # 0 = initializer_range
    # Mamba-2
    mamba_num_heads: int = 64
    mamba_head_dim: int = 64
    n_groups: int = 8
    ssm_state_size: int = 128
    conv_kernel: int = 4
    chunk_size: int = 128
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    intermediate_size: int = 0  # a ``D`` layer's width
    # experts
    n_routed_experts: int = 8
    num_experts_per_tok: int = 2
    moe_intermediate_size: int = 64
    moe_shared_expert_intermediate_size: int = 64
    n_shared_experts: int = 1
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    experts_held: tuple[int, int] = (0, 0)  # (first id, count); 0 = all
    expert_tile: int = 0  # rows of a grouped-product tile; 0 = EXPERT_TILE
    hidden_act: str = "relu2"  # relu2 | silu (gated)
    scoring_func: str = "sigmoid"  # sigmoid (+ correction bias) | softmax
    # attention
    num_attention_heads: int = 4
    #: ((layer type, query heads), ...) from ``num_attention_heads_per_layer``;
    #: a type without one has ``num_attention_heads``
    attention_heads_by_type: tuple = ()
    num_key_value_heads: int = 1
    head_dim: int = 16
    #: an RMSNorm over each head's q and over each head's k, before the
    #: rotation: one learned scale of ``head_dim`` for all query heads, one
    #: for all key heads
    qk_norm: bool = False
    sliding_window: int = 0  # keys a ``W`` layer's query sees, itself one
    #: ((layer type, RopeParameters), ...); a type without one: no rotary
    rope_parameters: tuple = ()
    # latent attention (an ``L`` layer)
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # multi-token prediction
    num_nextn_predict_layers: int = 0
    mtp_loss_weight: float = 0.3
    # a gated short convolution (a ``C`` layer): taps a channel
    conv_L_cache: int = 3
    #: the head reads the token embedding's table (logits = h Emb^T) and
    #: has no kernel of its own
    tie_word_embeddings: bool = False
    # a looped stack: the layers run this many times a forward pass
    total_ut_steps: int = 1
    exit_entropy_weight: float = 0.1  # beta of the expected-exit loss
    #: an RMSNorm on every mixer's output before its residual add
    post_norms: bool = False

    #: public spellings of the same number
    ALIASES = (("num_experts", "n_routed_experts"),
               ("rms_norm_eps", "layer_norm_epsilon"),
               ("norm_eps", "layer_norm_epsilon"),
               ("tie_embedding", "tie_word_embeddings"),
               ("shared_expert_intermediate_size",
                "moe_shared_expert_intermediate_size"),
               ("moe_routed_scaling_factor", "routed_scaling_factor"))

    @property
    def embedding_std(self) -> float:
        return self.embedding_initializer_range or self.initializer_range

    @property
    def output_std(self) -> float:
        return self.output_initializer_range or self.initializer_range

    @property
    def looped(self) -> bool:
        """The stack runs more than once a forward pass, read by an exit
        gate after each pass."""
        return self.total_ut_steps > 1

    def rope_for(self, kind: str) -> "RopeParameters | None":
        """The rotary parametrisation of a ``*`` or ``W`` layer."""
        return dict(self.rope_parameters).get(_PATTERN_LAYER_TYPES[kind])

    def heads_for(self, kind: str) -> int:
        """The query heads of a ``*`` or ``W`` layer."""
        return dict(self.attention_heads_by_type).get(
            _PATTERN_LAYER_TYPES[kind], self.num_attention_heads)

    @staticmethod
    def heads_by_type(params: Mapping[str, Any]) -> tuple:
        """``num_attention_heads_per_layer`` beside ``layer_types`` as one
        number a layer type."""
        heads = [int(n) for n in params["num_attention_heads_per_layer"]]
        kinds = [str(k) for k in params.get("layer_types", ())]
        if len(heads) != len(kinds):
            raise ValueError(
                f"num_attention_heads_per_layer has {len(heads)} entries, "
                f"layer_types {len(kinds)}")
        by_type: dict[str, int] = {}
        for kind, n in zip(kinds, heads):
            if by_type.setdefault(kind, n) != n:
                raise ValueError(
                    f"num_attention_heads_per_layer gives {kind} layers "
                    f"{by_type[kind]} and {n} heads: one number a layer "
                    "type is implemented")
        return tuple(sorted(by_type.items()))

    @staticmethod
    def rope_by_type(params: Mapping[str, Any]) -> tuple:
        """``rope_parameters`` as ((layer type, RopeParameters), ...).  A
        number among its entries (``original_max_position_embeddings``)
        and the config's own ``partial_rotary_factor`` stand for the
        entries that state none."""
        entries = dict(params["rope_parameters"])
        if entries and not any(isinstance(v, Mapping)
                               for v in entries.values()):
            # one parametrisation and no entry a layer type: that of every
            # attention layer type the configuration has
            kinds = sorted({str(k) for k in params.get("layer_types", ())}
                           - {CONV}) or ["full_attention"]
            entries = {kind: entries for kind in kinds}
        shared = {k: entries.pop(k) for k in list(entries)
                  if not isinstance(entries[k], Mapping)}
        unknown = sorted(set(shared) - {"original_max_position_embeddings"})
        if unknown:
            raise ValueError(
                f"rope_parameters {unknown} are neither a layer type's "
                "entry nor original_max_position_embeddings")
        if "partial_rotary_factor" in params:
            shared["partial_rotary_factor"] = params["partial_rotary_factor"]
        return tuple(sorted(
            (str(kind), RopeParameters.from_json({**shared, **r}))
            for kind, r in entries.items()))

    @staticmethod
    def latent_family(params: Mapping[str, Any]) -> dict:
        """What a configuration of the latent-attention family
        (``kv_lora_rank`` among its keys) says without the keys the other
        shapes say it with: the pattern from ``num_hidden_layers`` and
        ``first_k_dense_replace``, the rotary entry from ``rope_theta``,
        the shared expert's width from ``moe_intermediate_size``."""
        if "layer_types" in params:
            raise ValueError(
                "kv_lora_rank beside layer_types: latent attention is the "
                "attention of every block of its family, which has no "
                "layer_types")
        for key, only in (("rope_scaling", None), ("topk_method", "noaux_tc"),
                          ("partial_rotary_factor", 1)):
            if params.get(key, only) != only:
                raise ValueError(
                    f"{key}={params[key]!r} is not implemented with latent "
                    f"attention ({only!r})")
        if "num_hidden_layers" not in params:
            raise ValueError(
                "latent attention needs num_hidden_layers (and "
                "first_k_dense_replace) for its pattern")
        blocks = int(params["num_hidden_layers"])
        dense = int(params.get("first_k_dense_replace", 0))
        if not 0 <= dense <= blocks:
            raise ValueError(
                f"first_k_dense_replace={dense} of num_hidden_layers="
                f"{blocks}")
        said = {"hybrid_override_pattern":
                "LD" * dense + "LE" * (blocks - dense),
                "rope_parameters": {
                    LATENT: {"rope_theta": params.get("rope_theta", 10000.0)}}}
        if "moe_intermediate_size" in params:
            said["moe_shared_expert_intermediate_size"] = params[
                "moe_intermediate_size"]
        for key, value in said.items():
            if params.get(key, value) != value:
                raise ValueError(
                    f"{key}={params[key]!r} but the latent-attention "
                    f"family's keys give {value!r}")
        return said

    @staticmethod
    def conv_family(params: Mapping[str, Any]) -> dict:
        """What a configuration of the gated-short-convolution family (a
        ``conv`` entry among its ``layer_types``) says without the keys
        the other shapes say it with: a norm on every head's q and k,
        gated ``silu`` experts and no shared expert (the family has no
        keys for them: stated otherwise is an error), and, unless the
        configuration says, a head of ``hidden_size /
        num_attention_heads`` and the head tied to the embedding (the
        family's public configuration class defaults to tied)."""
        for key, only in (("conv_bias", False), ("use_expert_bias", True)):
            if _parse_bool(params.get(key, only)) != only:
                raise ValueError(
                    f"{key}={params[key]!r} is not implemented with conv "
                    f"layers ({only!r}: the taps carry no bias; the choice "
                    "of experts is by sigmoid score + a bias that rests)")
        said = {"qk_norm": True, "hidden_act": "silu", "n_shared_experts": 0}
        for key, value in said.items():
            if params.get(key, value) != value:
                raise ValueError(
                    f"{key}={params[key]!r} but the conv family's blocks "
                    f"give {value!r}")
        if "head_dim" not in params and "num_attention_heads" in params:
            said["head_dim"] = (int(params["hidden_size"])
                                // int(params["num_attention_heads"]))
        if "tie_word_embeddings" not in params:
            said["tie_word_embeddings"] = True
        return said

    @staticmethod
    def looped_family(params: Mapping[str, Any]) -> dict:
        """What a configuration of the looped family (``total_ut_steps``
        among its keys) says without the keys the other shapes say it
        with: every block full attention over a dense gated feed-forward,
        rotary from ``rope_theta``, and, unless the configuration says,
        the norms on the sub-layers' outputs.  What the family's keys ask
        for and this program does not implement is an error by name:
        sliding windows, a threshold that is no probability, scaled rotary
        (``total_ut_steps < 1`` is :meth:`validate`'s).
        ``early_exit_threshold`` is inference's and read for its range
        alone."""
        threshold = float(params.get("early_exit_threshold", 1.0))
        if not 0.0 < threshold <= 1.0:
            raise ValueError(
                f"early_exit_threshold={threshold} is not a probability in "
                "(0, 1]")
        if _parse_bool(params.get("use_sliding_window", False)):
            raise ValueError(
                "use_sliding_window=true is not implemented with "
                "total_ut_steps (every block attends over all earlier keys)")
        if params.get("rope_scaling") is not None:
            raise ValueError(
                f"rope_scaling={params['rope_scaling']!r} is not implemented "
                "with total_ut_steps (plain rotary from rope_theta)")
        kinds = [str(k) for k in params.get("layer_types") or (
            ["full_attention"] * int(params.get("num_hidden_layers", 0)))]
        if not kinds or set(kinds) != {"full_attention"}:
            raise ValueError(
                f"layer_types {sorted(set(kinds))} with total_ut_steps: the "
                "looped family's blocks are full_attention")
        said = {"layer_types": kinds,
                "mlp_layer_types": ["dense"] * len(kinds),
                "hidden_act": "silu"}
        if "rope_parameters" not in params:
            said["rope_parameters"] = {
                "rope_theta": params.get("rope_theta", 10000.0)}
        for key, value in said.items():
            if params.get(key, value) != value:
                raise ValueError(
                    f"{key}={params[key]!r} but the looped family's blocks "
                    f"give {value!r}")
        if "post_norms" not in params:
            said["post_norms"] = True
        return said

    @staticmethod
    def pattern_of(params: Mapping[str, Any]) -> str:
        """``layer_types`` + ``mlp_layer_types`` as a pattern string; with
        ``num_dense_layers`` in their place, the first that many blocks'
        feed-forward dense and the others' sparse."""
        kinds = [str(k) for k in params["layer_types"]]
        mlps = [str(k) for k in params.get("mlp_layer_types",
                                           ["sparse"] * len(kinds))]
        if "num_dense_layers" in params:
            dense = int(params["num_dense_layers"])
            if "mlp_layer_types" in params:
                raise ValueError(
                    "num_dense_layers beside mlp_layer_types: one of the "
                    "two says which blocks are dense")
            if not 0 <= dense <= len(kinds):
                raise ValueError(
                    f"num_dense_layers={dense} of {len(kinds)} layer_types")
            mlps = ["dense"] * dense + ["sparse"] * (len(kinds) - dense)
        if len(mlps) != len(kinds):
            raise ValueError(
                f"mlp_layer_types has {len(mlps)} entries, layer_types "
                f"{len(kinds)}")
        bad = sorted(set(kinds) - set(_ATTENTION_KINDS))
        if bad:
            raise ValueError(
                f"layer_types {bad} are not implemented "
                f"({' | '.join(_ATTENTION_KINDS)})")
        bad = sorted(set(mlps) - set(_MLP_KINDS))
        if bad:
            raise ValueError(
                f"mlp_layer_types {bad} are not implemented "
                f"({' | '.join(_MLP_KINDS)})")
        return "".join(_ATTENTION_KINDS[k] + _MLP_KINDS[m]
                       for k, m in zip(kinds, mlps))

    @classmethod
    def from_json(cls, params: Mapping[str, Any]) -> "HybridLMConfig":
        import dataclasses

        params = dict(params)
        for public, ours in cls.ALIASES:
            if public in params:
                if ours in params and params[ours] != params[public]:
                    raise ValueError(
                        f"{public}={params[public]} and {ours}="
                        f"{params[ours]} name the same number")
                params.setdefault(ours, params[public])
        blocks = None
        if "kv_lora_rank" in params:
            params.update(cls.latent_family(params))
            blocks = int(params["num_hidden_layers"])
        if CONV in params.get("layer_types", ()):
            params.update(cls.conv_family(params))
        if "total_ut_steps" in params:
            params.update(cls.looped_family(params))
        if "layer_types" in params:
            pattern = cls.pattern_of(params)
            if params.get("hybrid_override_pattern", pattern) != pattern:
                raise ValueError(
                    "hybrid_override_pattern="
                    f"{params['hybrid_override_pattern']!r} but layer_types "
                    f"+ mlp_layer_types give {pattern!r}")
            params["hybrid_override_pattern"] = pattern
            blocks = len(params["layer_types"])
        if "num_attention_heads_per_layer" in params:
            params["attention_heads_by_type"] = cls.heads_by_type(params)
        if "rope_parameters" in params:
            params["rope_parameters"] = cls.rope_by_type(params)
        known = {f.name: f for f in dataclasses.fields(cls)}
        missing = [k for k in ("hidden_size", "hybrid_override_pattern",
                               "vocab_size") if k not in params]
        if missing:
            raise ValueError(
                f"ModelType=hybrid_lm needs train.params {missing}")
        kw: dict[str, Any] = {}
        for name, f in known.items():
            if name not in params:
                continue
            v = params[name]
            if name == "experts_held":
                kw[name] = (int(v[0]), int(v[1]))
            elif f.type == "tuple":
                kw[name] = tuple(v)
            elif f.type in ("int",):
                kw[name] = int(v or 0)
            elif f.type in ("float",):
                kw[name] = float(v)
            elif f.type in ("bool",):
                kw[name] = _parse_bool(v)
            else:
                kw[name] = str(v)
        cfg = cls(**kw)
        if cfg.experts_held[1] <= 0:
            cfg = dataclasses.replace(
                cfg, experts_held=(0, cfg.n_routed_experts))
        cfg.validate(params, blocks)
        return cfg

    def validate(self, params: Mapping[str, Any] = (),
                 blocks: "int | None" = None) -> None:
        pattern = self.hybrid_override_pattern
        bad = set(pattern) - set("MEDW*LC")
        if bad or not pattern:
            raise ValueError(
                "hybrid_override_pattern is a string of M (Mamba-2), E "
                "(experts), D (dense gated feed-forward), * (attention), "
                "W (attention inside sliding_window), L (latent "
                "attention) and C (gated short convolution); got "
                f"{sorted(bad)}")
        layers = params.get("num_hidden_layers") if params else None
        expect = len(pattern) if blocks is None else blocks
        if layers is not None and int(layers) != expect:
            raise ValueError(
                f"num_hidden_layers={layers} but "
                + (f"hybrid_override_pattern has {expect} characters"
                   if blocks is None else f"layer_types has {expect} entries"))
        for key in ("n_group", "topk_group"):
            if params and int(params.get(key, 1)) != 1:
                raise ValueError(
                    f"{key}={params[key]}: group-limited routing is not "
                    "implemented (the family routes over all experts)")
        for key in ("attention_bias", "mlp_bias",
                    "moe_apply_router_weight_on_input"):
            if params and _parse_bool(params.get(key, False)):
                raise ValueError(f"{key}=true is not implemented")
        first, count = self.experts_held
        if first < 0 or first + count > self.n_routed_experts:
            raise ValueError(
                f"experts_held={list(self.experts_held)} lies outside the "
                f"router's {self.n_routed_experts} experts")
        if self.num_experts_per_tok > self.n_routed_experts:
            raise ValueError("num_experts_per_tok > n_routed_experts")
        if self.expert_tile < 0 or self.expert_tile % 8:
            raise ValueError(
                f"expert_tile={self.expert_tile}: a tile is a multiple of 8 "
                "rows (0: the family's)")
        for key in ("initializer_range", "embedding_initializer_range",
                    "output_initializer_range"):
            if getattr(self, key) < 0:
                raise ValueError(f"{key}={getattr(self, key)} is negative")
        if self.hidden_act not in ("relu2", "silu"):
            raise ValueError(
                f"hidden_act={self.hidden_act!r} is not implemented "
                "(relu2 | silu, the gated expert)")
        if self.scoring_func not in ("sigmoid", "softmax"):
            raise ValueError(
                f"scoring_func={self.scoring_func!r} is not implemented "
                "(sigmoid | softmax)")
        if self.n_shared_experts < 0:
            raise ValueError(
                f"n_shared_experts={self.n_shared_experts} is negative")
        if "D" in pattern and (self.intermediate_size <= 0
                               or self.hidden_act != "silu"):
            raise ValueError(
                f"a D (dense) layer needs intermediate_size > 0 and "
                f"hidden_act silu, the gated feed-forward; got "
                f"intermediate_size={self.intermediate_size}, hidden_act="
                f"{self.hidden_act!r}")
        if (params and _parse_bool(params.get("gating", False))
                and self.hidden_act != "silu"):
            raise ValueError(
                f"gating=true with hidden_act={self.hidden_act!r}: gating "
                "is read as the gated (silu) feed-forward and nothing else")
        if "W" in pattern and self.sliding_window <= 0:
            raise ValueError(
                "a W (sliding_attention) layer needs sliding_window > 0")
        if "L" in pattern:
            self.validate_latent()
        if "C" in pattern and self.conv_L_cache < 1:
            raise ValueError(
                f"conv_L_cache={self.conv_L_cache}: a C (gated short "
                "convolution) layer needs at least one tap")
        unknown = sorted(set(dict(self.rope_parameters))
                         - set(_PATTERN_LAYER_TYPES.values()))
        if unknown:
            raise ValueError(
                f"rope_parameters for {unknown}: the layer types are "
                f"{' | '.join(_ATTENTION_KINDS)}")
        for kind, rope in self.rope_parameters:
            of = self.qk_rope_head_dim if kind == LATENT else self.head_dim
            turning = rope.rotary_dim(of)
            if turning % 2 or not turning:
                raise ValueError(
                    f"rotary positions need an even number of dimensions: "
                    f"{kind} turns {turning} of {of}")
        if self.num_nextn_predict_layers not in (0, 1):
            raise ValueError(
                f"num_nextn_predict_layers={self.num_nextn_predict_layers}: "
                "one multi-token prediction module is implemented (0 | 1)")
        if self.num_nextn_predict_layers and (
                len(pattern) < 2 or pattern[-2] not in "*WL"
                or pattern[-1] not in "ED"):
            raise ValueError(
                "a multi-token prediction module is one more block of the "
                "last block's kinds, attention then feed-forward; the "
                f"pattern ends in {pattern[-2:]!r}")
        if self.mtp_loss_weight < 0:
            raise ValueError(
                f"mtp_loss_weight={self.mtp_loss_weight} is negative")
        if self.total_ut_steps < 1:
            raise ValueError(
                f"total_ut_steps={self.total_ut_steps}: the stack runs at "
                "least once")
        if self.exit_entropy_weight < 0:
            raise ValueError(
                f"exit_entropy_weight={self.exit_entropy_weight} is negative")
        if self.looped and self.num_nextn_predict_layers:
            raise ValueError(
                "a multi-token prediction module beside total_ut_steps > 1 "
                "is not implemented")
        if self.mamba_num_heads % self.n_groups:
            raise ValueError("n_groups must divide mamba_num_heads")
        for heads in (self.num_attention_heads,
                      *dict(self.attention_heads_by_type).values()):
            if heads <= 0 or heads % self.num_key_value_heads:
                raise ValueError(
                    f"num_key_value_heads={self.num_key_value_heads} must "
                    f"divide a layer's query heads ({heads})")

    def validate_latent(self) -> None:
        """What an ``L`` layer needs: both low-rank paths, a part of the
        head that turns, and a value head as wide as the query's and key's
        (the attention cores take one head size)."""
        for key in ("q_lora_rank", "kv_lora_rank", "qk_rope_head_dim",
                    "v_head_dim"):
            if getattr(self, key) <= 0:
                raise ValueError(
                    f"an L (latent attention) layer needs {key} > 0; got "
                    f"{getattr(self, key)} (a query without its low-rank "
                    "path is not implemented)")
        if self.qk_nope_head_dim < 0:
            raise ValueError(
                f"qk_nope_head_dim={self.qk_nope_head_dim} is negative")
        if self.qk_nope_head_dim + self.qk_rope_head_dim != self.v_head_dim:
            raise ValueError(
                f"qk_nope_head_dim + qk_rope_head_dim = "
                f"{self.qk_nope_head_dim + self.qk_rope_head_dim} but "
                f"v_head_dim = {self.v_head_dim}: one head size for "
                "queries, keys and values is implemented")
        if self.num_key_value_heads != self.num_attention_heads:
            raise ValueError(
                f"num_key_value_heads={self.num_key_value_heads} under "
                f"latent attention's {self.num_attention_heads} heads: every "
                "head has its own key and value there")
        if self.rope_for("L") is None:
            raise ValueError(
                "an L (latent attention) layer needs rope_parameters for "
                f"{LATENT}: the part of its head that turns carries position")


@dataclass(frozen=True)
class TrainParams:
    """``train.params`` — network-shape hyperparameters."""

    num_hidden_layers: int = 2
    num_hidden_nodes: tuple[int, ...] = (50, 50)
    activation_funcs: tuple[str, ...] = ("tanh", "tanh")
    learning_rate: float = 0.1
    # reference optimizer is Adadelta (ssgd_monitor.py:136-142); older script
    # used Adam (ssgd.py:56-62) — selectable here.
    optimizer: str = "adadelta"
    # The reference *declares* l2_regularizer(scale=0.1) on every variable
    # (ssgd_monitor.py:58) but never adds REGULARIZATION_LOSSES to its loss,
    # so its effective L2 is zero.  Ours is real, hence default 0.0 for
    # convergence parity; opt in via train.params.L2Reg.
    l2_reg: float = 0.0
    # ---- extensions beyond the reference (BASELINE.json configs) ----
    # dnn | wide_deep | multi_task | sequence | hybrid_lm
    model_type: str = "dnn"
    # ModelType "hybrid_lm" (models/hybrid_lm.py): the public config keys
    hybrid_lm: "HybridLMConfig | None" = None
    wide_column_nums: tuple[int, ...] = ()  # crossed/categorical cols for wide part
    cross_hash_size: int = 0  # >0: hashed-cross table for the wide part
    num_tasks: int = 1  # >1 => multi-task sigmoid heads sharing the trunk
    embedding_columns: tuple[int, ...] = ()  # high-cardinality hashed cols
    embedding_hash_size: int = 0  # rows per hashed table (0 = disabled)
    embedding_dim: int = 8
    # "device" (default): table in HBM, sharded over the mesh 'model' axis
    # (capacity = N x HBM).  "host": table in host RAM with host-side
    # hashed gather + sparse Adagrad updates (SURVEY §7.2-6's spill tier —
    # capacity = host memory; per-step training path only).
    embedding_placement: str = "device"
    # ModelType "sequence": transformer encoder over event sequences.  Each
    # PSV row carries seq_len steps x (features/seq_len) values flattened,
    # so the whole ingest pipeline (schema, cache, streaming) is unchanged.
    seq_len: int = 0  # >0 selects/validates the sequence family
    seq_d_model: int = 64
    seq_heads: int = 4
    seq_blocks: int = 2
    # "auto": ring attention when the mesh has a seq axis >1, else full;
    # chunked and flash are explicit choices (models/sequence.py)
    seq_attention: str = "auto"  # auto|full|chunked|flash|ring|ulysses
    # rematerialize encoder blocks: backward recomputes each block's
    # activations instead of storing them — the standard long-context
    # memory lever (jax.checkpoint via nn.remat)
    seq_remat: bool = False

    @property
    def features_carry_ids(self) -> bool:
        """Whether any feature column carries an integer the model reads
        exactly — a category code that feeds a hash, or a token id that
        indexes an embedding.  bfloat16 (8-bit mantissa) rounds integers
        above 256, so such rows stream as float32."""
        return self.uses_feature_hashing or self.model_type == "hybrid_lm"

    @property
    def uses_feature_hashing(self) -> bool:
        """Whether any column's raw float BITS feed a hash (hashed
        embeddings / wide crosses).  Such columns carry category codes that
        bfloat16 cannot represent exactly (8-bit mantissa: codes > 256
        round), so bf16 feature ingest would silently re-bucket them —
        train/serve skew against the f32-hashing exported scorer."""
        return (
            (len(self.embedding_columns) > 0 and self.embedding_hash_size > 0)
            # the factory only engages the wide cross when WideColumnNums
            # is present (models/factory.py passes cross_hash_size=0
            # otherwise) — a bare CrossHashSize hashes nothing, and
            # counting it here would wrongly block bf16 transport
            or (self.cross_hash_size > 0 and len(self.wide_column_nums) > 0)
        )
    # ---- learning-rate schedule (beyond the reference's fixed LR) ----
    # constant | cosine | exponential; warmup_steps applies to any of them
    # (linear 0 -> LearningRate over that many optimizer steps)
    lr_schedule: str = "constant"
    warmup_steps: int = 0
    decay_steps: int = 0  # required > 0 for cosine/exponential
    decay_rate: float = 0.1  # exponential: LR multiplier per decay_steps;
    # cosine: alpha (final LR fraction)
    # local-update DP: >1 reproduces SAGN's communication window of local
    # steps before the global update (reference: SAGN.py:110-176)
    update_window: int = 1
    # training algorithm: "ssgd" (ssgd_monitor.py, plain sync-DP) or "sagn"
    # (SAGN.py local-SGD windows) — the reference selected between them by
    # swapping the python script path in global-default.xml
    algorithm: str = "ssgd"

    @classmethod
    def from_json(cls, params: Mapping[str, Any]) -> "TrainParams":
        n_layers = int(params.get("NumHiddenLayers", 2))
        nodes = tuple(int(s) for s in params.get("NumHiddenNodes", [50, 50]))
        acts = tuple(str(s) for s in params.get("ActivationFunc", ["tanh"] * n_layers))
        if len(nodes) < n_layers or len(acts) < n_layers:
            raise ValueError(
                f"NumHiddenNodes/ActivationFunc shorter than NumHiddenLayers={n_layers}"
            )
        return cls(
            num_hidden_layers=n_layers,
            num_hidden_nodes=nodes,
            activation_funcs=acts,
            learning_rate=float(params.get("LearningRate", 0.1)),
            optimizer=str(params.get("Optimizer", "adadelta")).lower(),
            l2_reg=float(params.get("L2Reg", 0.0)),
            model_type=str(params.get("ModelType", "dnn")).lower(),
            wide_column_nums=tuple(int(c) for c in params.get("WideColumnNums", [])),
            cross_hash_size=int(params.get("CrossHashSize", 0)),
            num_tasks=int(params.get("NumTasks", 1)),
            embedding_columns=tuple(int(c) for c in params.get("EmbeddingColumnNums", [])),
            embedding_hash_size=int(params.get("EmbeddingHashSize", 0)),
            embedding_dim=int(params.get("EmbeddingDim", 8)),
            embedding_placement=str(
                params.get("EmbeddingPlacement", "device")).lower(),
            hybrid_lm=(HybridLMConfig.from_json(params)
                       if str(params.get("ModelType", "dnn")).lower()
                       == "hybrid_lm" else None),
            seq_len=int(params.get("SeqLen", 0)),
            seq_d_model=int(params.get("SeqDModel", 64)),
            seq_heads=int(params.get("SeqHeads", 4)),
            seq_blocks=int(params.get("SeqBlocks", 2)),
            seq_attention=str(params.get("SeqAttention", "auto")).lower(),
            seq_remat=_parse_bool(params.get("SeqRemat", False)),
            lr_schedule=str(params.get("LearningRateSchedule",
                                       "constant")).lower(),
            warmup_steps=int(params.get("WarmupSteps", 0)),
            decay_steps=int(params.get("DecaySteps", 0)),
            decay_rate=float(params.get("DecayRate", 0.1)),
            update_window=int(params.get("UpdateWindow", 1)),
            algorithm=str(params.get("Algorithm", "ssgd")).lower(),
        )


@dataclass(frozen=True)
class ModelConfig:
    """Typed view of Shifu's ``ModelConfig.json`` (the fields the trainer uses)."""

    num_train_epochs: int = 100
    valid_set_rate: float = 0.1  # reference VALID_TRAINING_DATA_RATIO default
    params: TrainParams = field(default_factory=TrainParams)
    batch_size: int = 100  # reference BATCH_SIZE (ssgd_monitor.py:33)
    delimiter: str = "|"  # reference DELIMITER (ssgd_monitor.py:32)
    model_set_name: str = "shifu_tpu_model"
    raw: Mapping[str, Any] = field(default_factory=dict, repr=False, compare=False)

    @classmethod
    def from_json(cls, obj: Mapping[str, Any]) -> "ModelConfig":
        train = obj.get("train", {})
        dataset = obj.get("dataSet", {})
        basic = obj.get("basic", {})
        return cls(
            num_train_epochs=int(train.get("numTrainEpochs", 100)),
            valid_set_rate=float(train.get("validSetRate", 0.1)),
            params=TrainParams.from_json(train.get("params", {})),
            batch_size=int(train.get("params", {}).get("MiniBatchs", 100)),
            delimiter=_decode_delimiter(dataset.get("dataDelimiter", "|")),
            model_set_name=str(basic.get("name", "shifu_tpu_model")),
            raw=dict(obj),
        )

    @classmethod
    def load(cls, path: str | os.PathLike) -> "ModelConfig":
        from shifu_tensorflow_tpu.utils import fs

        return cls.from_json(json.loads(fs.read_text(os.fspath(path))))


@dataclass(frozen=True)
class Column:
    """One entry of ``ColumnConfig.json``."""

    column_num: int
    column_name: str
    column_flag: str | None = None  # Target | ForceSelect | Meta | Weight | None
    final_select: bool = False
    column_type: str = "N"  # N numeric | C categorical
    mean: float = 0.0
    stddev: float = 1.0
    #: whether columnStats actually carried mean/stdDev — the 0.0/1.0
    #: above are then REAL statistics, not the silent substitution a
    #: half-populated ColumnConfig would otherwise smuggle into ZSCALE
    #: normalization (zscale_stats warns + journals when False)
    has_stats: bool = True

    @property
    def is_target(self) -> bool:
        return (self.column_flag or "").lower() == "target"

    @property
    def is_weight(self) -> bool:
        return (self.column_flag or "").lower() == "weight"


@dataclass(frozen=True)
class ColumnConfig:
    """Typed view of ``ColumnConfig.json`` — drives column selection and the
    ZSCALE normalization constants used by the streaming input pipeline."""

    columns: tuple[Column, ...]

    @classmethod
    def from_json(cls, arr: Sequence[Mapping[str, Any]]) -> "ColumnConfig":
        cols = []
        for c in arr:
            stats = c.get("columnStats", {}) or {}
            cols.append(
                Column(
                    column_num=int(c["columnNum"]),
                    column_name=str(c.get("columnName", f"col_{c['columnNum']}")),
                    column_flag=c.get("columnFlag"),
                    final_select=bool(c.get("finalSelect", False)),
                    column_type=str(c.get("columnType", "N")),
                    mean=float(stats.get("mean") or 0.0),
                    stddev=float(stats.get("stdDev") or 1.0),
                    # stdDev=0.0 parses to the SUBSTITUTED 1.0 above
                    # (the "or" swallows it), so zero-std counts as
                    # unusable here — zscale_stats warns for it too
                    has_stats=(stats.get("mean") is not None
                               and bool(stats.get("stdDev"))),
                )
            )
        return cls(columns=tuple(cols))

    @classmethod
    def load(cls, path: str | os.PathLike) -> "ColumnConfig":
        from shifu_tensorflow_tpu.utils import fs

        return cls.from_json(json.loads(fs.read_text(os.fspath(path))))

    # ---- derived selections (what the Java client computed into env vars) ----
    @property
    def target_column_num(self) -> int:
        for c in self.columns:
            if c.is_target:
                return c.column_num
        return -1

    @property
    def weight_column_num(self) -> int:
        for c in self.columns:
            if c.is_weight:
                return c.column_num
        return -1

    @property
    def selected_column_nums(self) -> list[int]:
        sel = [
            c.column_num
            for c in self.columns
            if c.final_select and not c.is_target and not c.is_weight
        ]
        if sel:
            return sel
        # fallback parity: with no explicit selection, every non-target,
        # non-weight column is a feature (ssgd_monitor.py:390-394)
        return [
            c.column_num
            for c in self.columns
            if not c.is_target and not c.is_weight
        ]

    def zscale_stats(self, column_nums: Sequence[int]) -> tuple[list[float], list[float]]:
        by_num = {c.column_num: c for c in self.columns}
        means = [by_num[n].mean if n in by_num else 0.0 for n in column_nums]
        stds = [
            (by_num[n].stddev if n in by_num and by_num[n].stddev else 1.0)
            for n in column_nums
        ]
        # columns the ZSCALE constants are SUBSTITUTED for rather than
        # computed: absent from ColumnConfig entirely, present with an
        # empty/partial columnStats, or carrying stdDev=0.0 (which the
        # std list above silently replaces with 1.0 — same substitution,
        # different disguise).  Silently mis-normalizing them is the
        # classic half-populated-ColumnConfig failure — say so once
        # (per distinct set) and journal it so a dead fleet's files
        # still show it.
        missing = sorted(
            n for n in column_nums
            if n not in by_num or not by_num[n].has_stats
            or not by_num[n].stddev
        )
        if missing:
            _warn_stats_missing(tuple(missing), len(column_nums))
        return means, stds


def _decode_delimiter(d: str) -> str:
    return {"\\|": "|", "\\t": "\t"}.get(d, d) or "|"


#: column-number sets already warned about — one warning per distinct
#: set per process, not one per stream build (every epoch path resolves
#: zscale stats, and a page of repeated warnings hides the real one)
_warned_stats_missing: set[tuple[int, ...]] = set()


def _warn_stats_missing(missing: tuple[int, ...], total: int) -> None:
    if missing in _warned_stats_missing:
        return
    _warned_stats_missing.add(missing)
    from shifu_tensorflow_tpu.utils import logs

    shown = list(missing[:20])
    suffix = f" (+{len(missing) - 20} more)" if len(missing) > 20 else ""
    logs.get("config").warning(
        "ColumnConfig carries no usable columnStats (missing mean/stdDev "
        "or stdDev=0) for %d of %d selected columns: %s%s — ZSCALE "
        "substitutes defaults (mean=0 and/or std=1) for them, which "
        "silently mis-normalizes any column whose true distribution is "
        "not standard normal",
        len(missing), total, shown, suffix,
    )
    # journal the condition too: the data-drift leg exists because
    # mis-normalized features are invisible in latency metrics, and this
    # is the config-side edition.  Config resolution runs BEFORE the CLI
    # installs obs, so the emit is DEFERRED to journal install (fires
    # immediately when one is already active) — without that, the
    # process-level warn dedup above would eat every later chance and a
    # dead fleet's files would never show the record.
    from shifu_tensorflow_tpu.obs import journal as obs_journal

    def _emit(shown=shown, n=len(missing), total=total):
        obs_journal.emit(
            "config_stats_missing", plane="train",
            columns=shown, missing=n, selected=total,
        )

    obs_journal.notify_on_install(_emit)
