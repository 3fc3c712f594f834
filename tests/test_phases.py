"""The train step's phases: the ``jax.named_scope`` names the step is
written with reach the compiled program as each instruction's ``op_name``,
and ``obs.profile.phases`` splits a capture's device time by them.

The recorded capture (``tests/fixtures/wdl_criteo_stream_4steps.phases.json.gz``)
is four steps of the flagship cell on one TPU v5e, as
``obs.profile.load_capture`` read them from the chip's ``.xplane.pb``
(its ``origin`` key says which run).  The synthetic capture below is a
hand-encoded ``XSpace`` in the layout the chip's has: ops with no scope
of their own on the op line, the scopes in the HLO module under
``/host:metadata``.
"""

import gzip
import io
import json
import os
import re
import struct
from contextlib import redirect_stderr, redirect_stdout

import numpy as np
import pytest

from shifu_tensorflow_tpu.obs import profile as profile_mod
from shifu_tensorflow_tpu.obs.profile import (
    COLLECTIVE,
    HYBRID_LM_SCOPES,
    PHASE_SCOPES,
    TABULAR_SCOPES,
    UNSCOPED,
    phase_of,
    reduce_phases,
)

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "wdl_criteo_stream_4steps.phases.json.gz")
#: three epochs of the Nemotron cell on one TPU v5e (PR 34; its ``origin``
#: key says which run and how the op line was merged)
BOUNDARY_FIXTURE = os.path.join(
    os.path.dirname(__file__), "fixtures",
    "nemotron3_nano_ep16_stream_s4k_3epochs.boundaries.json.gz")


# ---- the scopes in the program ----

def _wdl_trainer(embedding_dim=4, **kw):
    from shifu_tensorflow_tpu.config.model_config import ModelConfig
    from shifu_tensorflow_tpu.train.trainer import Trainer

    mc = ModelConfig.from_json({"train": {"params": {
        "NumHiddenLayers": 2, "NumHiddenNodes": [8, 4],
        "ActivationFunc": ["relu", "relu"], "LearningRate": 0.01,
        "Optimizer": "adam", "ModelType": "wide_deep",
        "WideColumnNums": [3, 4], "CrossHashSize": 64,
        "EmbeddingColumnNums": [3, 4], "EmbeddingHashSize": 32,
        "EmbeddingDim": embedding_dim}}})
    return Trainer(mc, 4, feature_columns=(1, 2, 3, 4), **kw)


def _batch(*lead):
    return {"x": np.ones((*lead, 4), np.float32),
            "y": np.ones((*lead, 1), np.float32),
            "w": np.ones((*lead, 1), np.float32)}


_STEP_TEXTS: dict = {}
LINES_DIM = 32  # four rows to a 128-lane line; the tiny table is 32 rows


def _step_texts(path: str, embedding_dim: int = 4) -> dict:
    """``{"lowered": ..., "compiled": ..., "module": ...}`` of the tiny
    wide-deep step on one path (built once per path).  Embeddings of 4
    floats keep XLA's transpose of the lookup; 8 tile a 128-lane line and
    take ``ops/embedding_grad.py``'s backward."""
    if (path, embedding_dim) not in _STEP_TEXTS:
        if path == "per_step":
            trainer = _wdl_trainer(embedding_dim)
            fn, batch = trainer._train_step, _batch(8)
        elif path == "scan":
            trainer = _wdl_trainer(embedding_dim, scan_steps=2)
            fn, batch = trainer._path.step, _batch(2, 8)
        elif path == "accum":
            trainer = _wdl_trainer(embedding_dim, accum_steps=2)
            fn, batch = trainer._path.step, _batch(2, 8)
        else:
            import jax

            from shifu_tensorflow_tpu.parallel.mesh import make_mesh

            trainer = _wdl_trainer(embedding_dim, mesh=make_mesh(
                "data:2,model:2", devices=jax.devices()[:4]))
            fn, batch = trainer._train_step, _batch(8)
        lowered = fn.lower(trainer.state, batch)
        compiled = lowered.compile().as_text()
        _STEP_TEXTS[path, embedding_dim] = {
            "lowered": lowered.as_text(debug_info=True),
            "compiled": compiled,
            "module": re.search(r"^HloModule (\w+)", compiled, re.M).group(1),
        }
    return _STEP_TEXTS[path, embedding_dim]


def _op_names(compiled: str) -> set[str]:
    return set(re.findall(r'op_name="([^"]+)"', compiled))


@pytest.mark.parametrize("path", ["per_step", "scan", "accum"])
@pytest.mark.parametrize("scope", TABULAR_SCOPES)
def test_compiled_step_carries_every_scope(path, scope):
    texts = _step_texts(path)
    assert scope in texts["lowered"]
    phases = {phase_of(n) for n in _op_names(texts["compiled"])}
    assert phases & {scope, scope + ".fwd", scope + ".bwd"}, phases


def _lm_step_text() -> str:
    """The compiled per-step program of a tiny ``hybrid_lm`` trainer (the
    health guard's step, as the CLI builds it): one layer of every kind, the
    dense gated feed-forward among them (its experts are then gated too),
    the window and the full attention each with its rotary."""
    if "lm" not in _STEP_TEXTS:
        from shifu_tensorflow_tpu.config.model_config import ModelConfig
        from shifu_tensorflow_tpu.train.trainer import HealthConfig, Trainer

        mc = ModelConfig.from_json({"train": {"params": {
            "ModelType": "hybrid_lm", "Optimizer": "adam",
            "LearningRate": 1e-3, "hidden_size": 32,
            "hybrid_override_pattern": "MEW*D", "vocab_size": 64,
            "intermediate_size": 48, "hidden_act": "silu",
            "sliding_window": 4, "rope_parameters": {
                "sliding_attention": {"rope_type": "default",
                                      "rope_theta": 100.0},
                "full_attention": {
                    "rope_type": "yarn", "rope_theta": 100.0, "factor": 4,
                    "original_max_position_embeddings": 8}},
            "mamba_num_heads": 2, "mamba_head_dim": 8, "n_groups": 1,
            "ssm_state_size": 8, "chunk_size": 8, "n_routed_experts": 4,
            "experts_held": [0, 2], "num_experts_per_tok": 2,
            "moe_intermediate_size": 16,
            "moe_shared_expert_intermediate_size": 16,
            "num_attention_heads": 2, "num_key_value_heads": 1,
            "head_dim": 8}}})
        trainer = Trainer(mc, 16, health=HealthConfig())
        batch = {"x": np.ones((2, 16), np.float32),
                 "y": np.ones((2, 1), np.float32),
                 "w": np.ones((2, 1), np.float32)}
        _STEP_TEXTS["lm"] = trainer._path.step.lower(
            trainer.state, batch).compile().as_text()
    return _STEP_TEXTS["lm"]


#: the scopes only a latent-attention decoder with a multi-token
#: prediction module has, and every scope its step has
LATENT_ONLY = ("attn.latent", "attn.expand", "mtp.merge", "mtp.block",
               "mtp.head")
LATENT_SCOPES = ("embed.gather", "moe.route", "moe.experts", "moe.shared",
                 "mlp.dense", "attn.proj", "attn.rope", "attn.core",
                 *LATENT_ONLY, "lm.head", "optimizer.update")


def _latent_lm_step_text() -> str:
    """The compiled per-step program of a tiny ``hybrid_lm`` trainer under
    the ``glm4_moe_lite`` keys: latent attention over a dense and a sparse
    block, and the multi-token prediction module in the loss."""
    if "latent_lm" not in _STEP_TEXTS:
        from shifu_tensorflow_tpu.config.model_config import ModelConfig
        from shifu_tensorflow_tpu.train.trainer import HealthConfig, Trainer

        mc = ModelConfig.from_json({"train": {"params": {
            "ModelType": "hybrid_lm", "Optimizer": "adam",
            "LearningRate": 1e-3, "hidden_size": 32, "vocab_size": 64,
            "num_hidden_layers": 2, "first_k_dense_replace": 1,
            "intermediate_size": 48, "hidden_act": "silu",
            "num_attention_heads": 2, "num_key_value_heads": 2,
            "q_lora_rank": 12, "kv_lora_rank": 8, "qk_nope_head_dim": 12,
            "qk_rope_head_dim": 4, "v_head_dim": 16, "rope_theta": 100.0,
            "n_routed_experts": 4, "experts_held": [0, 2],
            "num_experts_per_tok": 2, "moe_intermediate_size": 16,
            "n_shared_experts": 1, "topk_method": "noaux_tc",
            "num_nextn_predict_layers": 1}}})
        trainer = Trainer(mc, 16, health=HealthConfig())
        batch = {"x": np.ones((2, 16), np.float32),
                 "y": np.ones((2, 1), np.float32),
                 "w": np.ones((2, 1), np.float32)}
        _STEP_TEXTS["latent_lm"] = trainer._path.step.lower(
            trainer.state, batch).compile().as_text()
    return _STEP_TEXTS["latent_lm"]


#: the scopes only a short-convolution decoder has, and every scope its
#: step has
CONV_ONLY = ("conv.proj", "conv.mix", "attn.qknorm")
CONV_SCOPES = ("embed.gather", "moe.route", "moe.experts", "mlp.dense",
               "attn.proj", "attn.rope", "attn.core", *CONV_ONLY, "lm.head",
               "optimizer.update")


def _conv_lm_step_text() -> str:
    """The compiled per-step program of a tiny ``hybrid_lm`` trainer under
    the ``lfm2_moe`` keys: a gated short convolution over a dense block,
    attention with its head norms and a convolution over sparse ones, the
    head tied to the embedding."""
    if "conv_lm" not in _STEP_TEXTS:
        from shifu_tensorflow_tpu.config.model_config import ModelConfig
        from shifu_tensorflow_tpu.train.trainer import HealthConfig, Trainer

        mc = ModelConfig.from_json({"train": {"params": {
            "ModelType": "hybrid_lm", "Optimizer": "adam",
            "LearningRate": 1e-3, "hidden_size": 32, "vocab_size": 64,
            "num_hidden_layers": 3,
            "layer_types": ["conv", "full_attention", "conv"],
            "num_dense_layers": 1, "intermediate_size": 48,
            "num_attention_heads": 2, "num_key_value_heads": 1,
            "conv_L_cache": 3, "conv_bias": False, "norm_eps": 1e-5,
            "rope_parameters": {"rope_theta": 100.0, "rope_type": "default"},
            "num_experts": 4, "experts_held": [0, 2],
            "num_experts_per_tok": 2, "moe_intermediate_size": 16,
            "use_expert_bias": True}}})
        trainer = Trainer(mc, 16, health=HealthConfig())
        batch = {"x": np.ones((2, 16), np.float32),
                 "y": np.ones((2, 1), np.float32),
                 "w": np.ones((2, 1), np.float32)}
        _STEP_TEXTS["conv_lm"] = trainer._path.step.lower(
            trainer.state, batch).compile().as_text()
    return _STEP_TEXTS["conv_lm"]


@pytest.mark.parametrize("scope", HYBRID_LM_SCOPES)
def test_compiled_lm_step_carries_every_scope(scope):
    text = (_latent_lm_step_text() if scope in LATENT_ONLY
            else _conv_lm_step_text() if scope in CONV_ONLY
            else _lm_step_text())
    assert re.search(r"^HloModule (\w+)", text, re.M).group(1) == \
        profile_mod.STEP_PROGRAM
    phases = {phase_of(n) for n in _op_names(text)}
    assert phases & {scope, scope + ".fwd", scope + ".bwd"}, phases


@pytest.mark.parametrize("scope", LATENT_SCOPES)
def test_compiled_latent_lm_step_carries_its_scopes_both_ways(scope):
    """The lowered GLM-shaped step: every scope of the main model and of
    the module, forward and backward, and none of another mixer's."""
    phases = {phase_of(n) for n in _op_names(_latent_lm_step_text())}
    if scope == "optimizer.update":
        assert scope in phases
    else:
        assert {scope + ".fwd", scope + ".bwd"} <= phases, phases
    assert not {p for p in phases if p.startswith(("ssm.", "attn.window"))}


@pytest.mark.parametrize("scope", CONV_SCOPES)
def test_compiled_conv_lm_step_carries_its_scopes_both_ways(scope):
    """The lowered LFM2-shaped step: every scope of its blocks, forward
    and backward, and none of another mixer's; the tied head's pass stays
    ``lm.head`` and the lookup ``embed.gather``."""
    phases = {phase_of(n) for n in _op_names(_conv_lm_step_text())}
    if scope == "optimizer.update":
        assert scope in phases
    else:
        assert {scope + ".fwd", scope + ".bwd"} <= phases, phases
    assert not {p for p in phases if p.startswith((
        "ssm.", "attn.window", "attn.latent", "mtp.", "moe.shared"))}


def test_phase_scopes_are_both_families_and_each_name_once():
    assert set(PHASE_SCOPES) == set(TABULAR_SCOPES) | set(HYBRID_LM_SCOPES)
    assert len(set(PHASE_SCOPES)) == len(PHASE_SCOPES)
    assert phase_of("jit(train_step)/transpose(jvp(HybridLM))/layers_0/"
                    "checkpoint/mixer/ssm.scan/mul") == "ssm.scan.bwd"
    assert phase_of("jit(train_step)/jvp(HybridLM)/layers_1/mixer/"
                    "moe.experts/while/body/dot_general") == "moe.experts.fwd"
    # the first scope on the path: the module's block keeps its inner
    # mixers' ops, its head pass the shared head's
    assert phase_of("jit(train_step)/transpose(jvp(HybridLM))/mtp/mtp.block/"
                    "attn/checkpoint/mixer/attn.core/mul") == "mtp.block.bwd"
    assert phase_of("jit(train_step)/jvp(HybridLM)/mtp.head/lm_head/"
                    "lm.head/dot_general") == "mtp.head.fwd"


@pytest.mark.parametrize("path", ["per_step", "scan", "accum"])
def test_gather_scope_has_both_directions_and_update_holds_the_select(path):
    names = _op_names(_step_texts(path)["compiled"])
    fwd = [n for n in names if "embed.gather" in n and "transpose(" not in n]
    bwd = [n for n in names if "embed.gather" in n and "transpose(jvp(" in n]
    assert any(n.endswith("/gather") for n in fwd), sorted(names)
    assert any(n.endswith("/scatter-add") for n in bwd), sorted(names)
    assert {phase_of(n) for n in fwd} == {"embed.gather.fwd"}
    assert {phase_of(n) for n in bwd} == {"embed.gather.bwd"}
    # Adam and the guard's select beside it, under no conditional:
    # optimizer.update/add ..., optimizer.update/jit(_where)/select_n
    update = [n for n in names if "/optimizer.update/" in n]
    assert any(n.endswith("/optimizer.update/jit(_where)/select_n")
               for n in update), sorted(update)
    assert not [n for n in update if "/cond" in n]


@pytest.mark.parametrize("path", ["per_step", "scan", "accum", "mesh"])
def test_the_lookups_own_backward_is_filed_under_the_gather(path):
    """``ops/embedding_grad.py``: the sort of the lookups, the gather of
    the gradient rows into that order, their scatter into lines and the
    turning of the lines (the branch ``lax.platform_dependent`` keeps)
    are a third of the chip's step; none may go to ``(unscoped)``, on one
    device or under the mesh's ``shard_map``."""
    names = _op_names(_step_texts(path, embedding_dim=8)["compiled"])
    for op in ("sort", "gather", "scatter-add", "transpose"):
        ours = [n for n in names if re.search(
            r"/embed\.gather/(shard_map/)?(cond/branch_\d_fun/)?%s$" % op, n)]
        assert ours, (op, sorted(names))
        assert {phase_of(n) for n in ours} == {"embed.gather.bwd"}, ours
    assert any("/shard_map/" in n for n in names) == (path == "mesh")
    sorts = [n for n in names if n.endswith("/sort")]
    assert {phase_of(n) for n in sorts} == {"embed.gather.bwd"}, sorts


@pytest.mark.parametrize("path", ["per_step", "scan", "mesh"])
def test_the_lookups_forward_by_lines_is_filed_under_the_gather(path):
    """``ops/embedding_grad.py`` ``rows_by_lines``: the turn of the table
    into lines, the gather of a line a lookup and the pick of the rows off
    them carry inner scopes (``lines.turn``, ``lines.take``) below
    ``embed.gather`` and are the forward's; the backward's ops stay the
    backward's."""
    names = _op_names(_step_texts(path, embedding_dim=LINES_DIM)["compiled"])
    inner = r"/embed\.gather/(shard_map/)?lines\.%s/(cond/branch_\d_fun/)?%s$"
    for scope, op in (("turn", "transpose"), ("take", "gather"),
                      ("take", r"(jit\(_where\)/)?select_n")):
        ours = [n for n in names if re.search(inner % (scope, op), n)]
        assert ours, (scope, op, sorted(names))
        assert {phase_of(n) for n in ours} == {"embed.gather.fwd"}, ours
    assert any("/shard_map/lines." in n for n in names) == (path == "mesh")
    assert not [n for n in names if n.endswith("jit(_take)/gather")
                and "/embed.gather/" in n], "no gather of table rows"
    for op in ("sort", "scatter-add"):
        theirs = [n for n in names if "/embed.gather/" in n
                  and n.endswith("/" + op)]
        assert {phase_of(n) for n in theirs} == {"embed.gather.bwd"}, theirs


@pytest.mark.parametrize("path,module", [
    ("per_step", profile_mod.STEP_PROGRAM),
    ("scan", "jit_scan_epoch"),
    ("accum", "jit_accum_step"),
])
def test_step_programs_keep_their_module_names(path, module):
    """The benchmark's ``step_pattern``, PERF.md and ``phases()``'s default
    read ``jit_train_step``: the jitted functions are not renamed."""
    assert module == _step_texts(path)["module"]
    assert profile_mod.STEP_PROGRAM == "jit_train_step"


@pytest.mark.parametrize("op_name,phase", [
    ("jit(train_step)/jvp(EmbeddingAugmented)/hashed_columns/embed.gather/"
     "jit(_take)/gather", "embed.gather.fwd"),
    ("jit(train_step)/transpose(jvp(EmbeddingAugmented))/hashed_columns/"
     "embed.gather/jit(_take)/scatter-add", "embed.gather.bwd"),
    ("jit(train_step)/jvp(loss)/reduce_sum", "loss.fwd"),
    ("jit(train_step)/transpose(jvp(loss))/mul", "loss.bwd"),
    ("jit(train_step)/optimizer.update/cond/branch_1_fun/add",
     "optimizer.update"),
    ("jit(train_step)/transpose(jvp(EmbeddingAugmented))/base/deep.mlp/deep/"
     "hidden_layer0/dot_general", "deep.mlp.bwd"),
    ("jit(scan_epoch)/while/body/jvp(EmbeddingAugmented)/base/wide_cross/"
     "wide.cross/xor", "wide.cross.fwd"),
    ("jit(train_step)/jvp(EmbeddingAugmented)/concatenate", UNSCOPED),
    ("jit(train_step)/reduce_sum", UNSCOPED),
    ("", UNSCOPED),
])
def test_phase_of(op_name, phase):
    assert phase_of(op_name) == phase


# ---- the reduction, on what the chip recorded ----

@pytest.fixture(scope="module")
def recorded():
    with gzip.open(FIXTURE, "rt") as f:
        return json.load(f)


def test_recorded_capture_phases_sum_to_the_step(recorded):
    out = reduce_phases(recorded)
    assert out["steps"] == 4 and out["devices"] == 1
    assert 120.0 < out["step_ms"] < 125.0
    total = sum(out["phases_ms"].values())
    assert abs(total - out["step_ms"]) < 0.02 * out["step_ms"]
    # the three phases the next perf_opt PRs rewrite are most of the step
    top = list(out["phases_ms"])[:3]
    assert set(top) == {"embed.gather.bwd", "optimizer.update",
                        "embed.gather.fwd"}
    assert out["phases_ms"][UNSCOPED] < 0.05 * out["step_ms"]
    assert out["host_spans"]["step.dispatch"]["count"] >= 4


def test_recorded_conditional_is_not_counted_twice(recorded):
    """``cond.40`` spans ``fusion.7`` (Adam) and seven layout copies that
    are on the op line too: the raw durations count that time twice, the
    phases once, and the copies (no ``op_name`` of their own) go to
    ``optimizer.update`` with the conditional around them."""
    dev = recorded["devices"]["0"]
    start, dur = dev["steps"][0]
    inside = [o for o in dev["ops"]
              if start <= o[2] and o[2] + o[3] <= start + dur]
    raw_ms = sum(o[3] for o in inside) / 1e6
    cond = next(o for o in inside if o[0].startswith("cond."))
    nested = [o for o in inside if o is not cond
              and cond[2] <= o[2] and o[2] + o[3] <= cond[2] + cond[3]]
    assert any(o[0].startswith("copy.") and not o[1] for o in nested)
    out = reduce_phases({"devices": {"0": {"steps": [[start, dur]],
                                           "ops": inside}}, "host": []})
    assert raw_ms > out["step_ms"] * 1.3            # the double count
    assert sum(out["phases_ms"].values()) <= out["step_ms"]
    assert abs(out["phases_ms"]["optimizer.update"] - cond[3] / 1e6) < 0.01
    assert not {o[0] for o in nested} & set(out["unscoped_ops_ms"])


def test_reduce_phases_medians_over_steps_then_devices():
    def dev(scale):
        ops, steps = [], []
        for k in range(3):
            t = k * 1000
            steps.append([t, 100 * scale])
            ops += [
                ["fusion.1", "jit(train_step)/jvp(M)/embed.gather/gather",
                 t, 10 * scale + k],
                ["all-reduce.2", "jit(train_step)/transpose(jvp(M))/x",
                 t + 20 * scale, 5 * scale],
                ["copy.9", "", t + 40 * scale, 2 * scale],
            ]
        return {"steps": steps, "ops": ops}

    out = reduce_phases({"devices": {"0": dev(1), "1": dev(3)}, "host": [
        ["epoch.turn", 0, 2_000_000], ["epoch.turn", 5, 1_000_000]]})
    assert out["devices"] == 2 and out["steps"] == 3
    # median over steps (k = 1), then over the two devices
    assert out["phases_ms"]["embed.gather.fwd"] == pytest.approx(
        ((10 + 1) + (30 + 1)) / 2 / 1e6)
    assert out["phases_ms"][COLLECTIVE] == pytest.approx(10 / 1e6)
    assert out["phases_ms"][UNSCOPED] == pytest.approx(4 / 1e6)
    assert out["unscoped_ops_ms"] == {"copy.9": pytest.approx(4 / 1e6)}
    assert out["step_ms"] == pytest.approx(200 / 1e6)
    assert out["host_spans"] == {"epoch.turn": {"count": 2,
                                                "total_s": 0.003}}


def test_reduce_phases_without_a_step_program_is_empty():
    assert reduce_phases({"devices": {}, "host": []}) == {}
    assert reduce_phases({"devices": {"0": {"steps": [], "ops": [
        ["fusion.1", "", 0, 5]]}}, "host": [["step.block", 0, 9]]}) == {}


# ---- the reading, on a hand-encoded capture ----

def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        byte, n = n & 0x7F, n >> 7
        out.append(byte | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _field(number: int, value) -> bytes:
    """One protobuf field: an int as a varint, bytes/str length-delimited."""
    if isinstance(value, int):
        return _varint(number << 3) + _varint(value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(number << 3 | 2) + _varint(len(value)) + value


def _plane(name: str, lines=(), event_names=(), extra=b"") -> bytes:
    """XPlane: name 2, lines 3, event_metadata 4 (map: key 1, value 2;
    XEventMetadata id 1, name 2)."""
    body = _field(2, name) + extra
    for k, ev_name in enumerate(event_names, start=1):
        body += _field(4, _field(1, k) + _field(
            2, _field(1, k) + _field(2, ev_name)))
    for line_name, events in lines:
        line = _field(2, line_name) + _field(3, 0)   # timestamp_ns 0
        for meta_id, start_ns, dur_ns in events:
            # XEvent: metadata_id 1, offset_ps 2, duration_ps 3
            line += _field(4, _field(1, meta_id) + _field(2, start_ns * 1000)
                           + _field(3, dur_ns * 1000))
        body += _field(3, line)
    return _field(1, body)


def _hlo_metadata_plane(program: str, op_names: dict) -> bytes:
    """``/host:metadata``: the program's event metadata carries a stat
    whose bytes value is the HloProto (module 1, computations 3,
    instructions 2; instruction name 1, metadata 7, op_name 2)."""
    comp = b"".join(
        _field(2, _field(1, ins) + _field(7, _field(2, op_name)))
        for ins, op_name in op_names.items())
    # a fixed64 field the reader has to step over (wire type 1)
    hlo = _field(1, _field(3, comp)) + _varint(9 << 3 | 1) + struct.pack(
        "<d", 1.0)
    meta = _field(1, 1) + _field(2, program) + _field(
        5, _field(1, 1) + _field(6, hlo))
    return _field(1, _field(2, "/host:metadata")
                  + _field(4, _field(1, 1) + _field(2, meta)))


def _write_dump(tmp_path, *, program="jit_train_step(123)",
                between=False) -> str:
    """Two steps, [0, 100) and [1000, 1100).  ``between`` puts an epoch's
    boundary in the gap: a fetch that ends at 120, a fill of [200, 900)
    with the put thread's first placement [300, 800) on a line of its
    own, the next dispatch from 900."""
    ops = ["%fusion.2 = f32[8,4]{1,0} fusion(%p0), kind=kLoop",
           "%cond.4 = (f32[8]) conditional(%p1, %t, %f)",
           "%fusion.7 = f32[8] fusion(%p2), kind=kLoop",
           "%copy.3 = f32[8] copy(%p3)",
           "%all-reduce.1 = f32[8] all-reduce(%p4)",
           "%copy.9 = f32[8] copy(%p5)"]
    step_ops = [(1, 0, 30), (2, 30, 50), (3, 32, 20), (4, 55, 10),
                (5, 80, 10), (6, 95, 5)]
    events = [(m, 1000 * k + s, d) for k in range(2) for m, s, d in step_ops]
    device = _plane(
        "/device:TPU:0",
        lines=[("XLA Modules", [(7, 0, 100), (7, 1000, 100)]),
               ("XLA Ops", events)],
        event_names=ops + [program])
    main, put_thread = [(1, 0, 40), (2, 5, 10), (3, 50, 7)], []
    if between:
        main += [(4, 90, 30), (5, 200, 700), (1, 900, 150)]
        put_thread = [(6, 300, 500)]
    host = _plane("/host:CPU",
                  lines=[("main/1", main), ("stpu-infeed-put/2", put_thread)],
                  event_names=["step.dispatch", "epoch.turn",
                               "PjitFunction(train_step)", "step.block",
                               "epoch.fill", "step.infeed.put"])
    names = {"fusion.2": "jit(train_step)/jvp(M)/embed.gather/gather",
             "cond.4": "jit(train_step)/optimizer.update/cond",
             "fusion.7": "jit(train_step)/optimizer.update/cond/"
                         "branch_1_fun/add",
             "all-reduce.1": "jit(train_step)/transpose(jvp(M))/deep.mlp/x"}
    dump = tmp_path / "dump" / "plugins" / "profile" / "t0"
    dump.mkdir(parents=True)
    (dump / "host.xplane.pb").write_bytes(
        device + host + _hlo_metadata_plane(program, names))
    return str(tmp_path / "dump")


def test_phases_reads_scopes_from_the_capture_itself(tmp_path):
    out = profile_mod.phases(_write_dump(tmp_path))
    assert out["step"] == "jit_train_step"
    assert out["xplane"].endswith("host.xplane.pb")
    assert out["steps"] == 2 and out["devices"] == 1
    assert out["step_ms"] == pytest.approx(100 / 1e6)
    assert out["phases_ms"] == {
        # the conditional, Adam and the scopeless copy inside it: once
        "optimizer.update": pytest.approx(50 / 1e6),
        "embed.gather.fwd": pytest.approx(30 / 1e6),
        COLLECTIVE: pytest.approx(10 / 1e6),
        UNSCOPED: pytest.approx(5 / 1e6),
    }
    assert out["unscoped_ops_ms"] == {"copy.9": pytest.approx(5 / 1e6)}
    # the program's spans, not the runtime's own events
    assert set(out["host_spans"]) == {"step.dispatch", "epoch.turn"}


def test_phases_takes_the_newest_capture(tmp_path):
    dump = _write_dump(tmp_path)
    older = os.path.join(dump, "plugins", "profile", "t0", "host.xplane.pb")
    newer = os.path.join(dump, "plugins", "profile", "t1")
    os.makedirs(newer)
    with open(os.path.join(newer, "host.xplane.pb"), "wb") as f:
        f.write(_plane("/host:CPU"))
    os.utime(older, (1, 1))
    assert profile_mod.find_xplane(dump).startswith(newer)
    assert profile_mod.phases(dump) == {}


def _cli(*argv) -> tuple[int, str, str]:
    from shifu_tensorflow_tpu.obs.__main__ import main

    out, err = io.StringIO(), io.StringIO()
    with redirect_stdout(out), redirect_stderr(err):
        rc = main(list(argv))
    return rc, out.getvalue(), err.getvalue()


def test_cli_profile_phases_prints_the_split(tmp_path):
    dump = _write_dump(tmp_path)
    rc, out, _ = _cli("profile", "--phases", dump)
    assert rc == 0
    assert "jit_train_step" in out and "optimizer.update" in out
    assert "embed.gather.fwd" in out and "epoch.turn" in out
    rc, out, _ = _cli("profile", "--phases", dump, "--json")
    assert rc == 0
    assert json.loads(out)["phases_ms"]["collective"] == pytest.approx(1e-5)


def test_phases_and_the_cli_report_the_boundary_of_a_dump(tmp_path):
    """The same dump with an epoch's boundary in the gap between its two
    steps: 900 ns of idle, shared out by the span open over it."""
    assert profile_mod.phases(_write_dump(tmp_path / "a"))["boundaries"] == {}
    dump = _write_dump(tmp_path / "b", between=True)
    found = profile_mod.phases(dump)["boundaries"]
    assert found["boundaries"] == 1 and found["devices"] == 1
    assert found["gap_ms"]["median"] == found["idle_ms"]["median"] == (
        pytest.approx(900 / 1e6))
    assert {k: round(v["median"] * 1e6)
            for k, v in found["idle_split_ms"].items()} == {
        "step.infeed.put": 500, "epoch.fill": 200, "step.dispatch": 100,
        "(no span)": 80, "step.block": 20}
    assert found["first_ms"] == {"step.infeed.put": {
        "median": pytest.approx(500 / 1e6), "max": pytest.approx(500 / 1e6)}}
    rc, out, _ = _cli("profile", "--phases", dump)
    assert rc == 0
    assert "between epochs: 1 boundary" in out
    assert re.search(r"step\.infeed\.put\s+0\.001\s+0\.001", out)  # 500 ns
    assert "an epoch's first step.infeed.put" in out
    assert "the window's edges" in out
    rc, out, _ = _cli("profile", "--phases", dump, "--json")
    assert rc == 0
    assert json.loads(out)["boundaries"]["idle_ms"]["sum"] == pytest.approx(
        900 / 1e6)
    # a dump with no boundary prints no such block
    rc, out, _ = _cli("profile", "--phases", _write_dump(tmp_path / "c"))
    assert rc == 0 and "between epochs" not in out


@pytest.mark.parametrize("as_json", [False, True])
def test_cli_profile_phases_exits_1_without_a_step_program(tmp_path, as_json):
    dump = _write_dump(tmp_path, program="jit_eval_step(5)")
    rc, out, err = _cli("profile", "--phases", dump,
                        *(["--json"] if as_json else []))
    assert rc == 1
    assert (out.strip() == "{}") if as_json else ("jit_train_step" in err)
    rc, _, _ = _cli("profile", "--phases", str(tmp_path / "nothing"))
    assert rc == 1


def test_cli_profile_without_journal_or_phases_is_a_usage_error():
    rc, _, err = _cli("profile")
    assert rc == 2 and "--journal" in err


# ---- between epochs ----

def _three_epochs(second_device=False) -> dict:
    """Three epochs of two 100 ns steps on one device inside a
    ``bench.window`` of [40, 1000): boundary 1 is the gap [300, 400) with a
    10 ns program of another kind inside it, boundary 2 the gap [600, 760);
    the third epoch's fetch has no step after it.  The put thread's spans
    lie inside the consumer's wait for them."""
    steps = [[100, 100], [200, 100], [400, 100], [500, 100], [760, 100],
             [860, 100]]
    ops = [["fusion.1", "", s, d] for s, d in steps if s != 500]
    ops += [["fusion.1", "", 500, 40], ["fusion.1", "", 550, 50],
            ["convert.3", "", 330, 10]]
    host = [
        ["bench.window", 40, 960],
        # the first epoch's fill, before the first step: the window's edge
        ["epoch.fill", 45, 30], ["step.host.produce", 50, 10],
        ["step.infeed.put", 61, 9],
        ["step.host.produce", 110, 4], ["step.infeed.put", 115, 2],
        # boundary 1
        ["epoch.drain", 290, 45], ["step.block", 295, 35],
        ["epoch.turn", 336, 14],
        ["epoch.fill", 352, 44], ["step.infeed.wait", 354, 41],
        ["step.host.produce", 356, 14], ["step.infeed.put", 371, 21],
        ["step.dispatch", 396, 6],
        ["step.host.produce", 410, 4], ["step.infeed.put", 415, 3],
        # boundary 2
        ["epoch.drain", 598, 42], ["step.block", 601, 29],
        ["epoch.turn", 640, 20],
        ["epoch.fill", 660, 90], ["step.infeed.wait", 662, 87],
        ["step.host.produce", 664, 16], ["step.infeed.put", 681, 64],
        ["step.dispatch", 750, 20],
        ["step.host.produce", 770, 4], ["step.infeed.put", 775, 2],
        # the last epoch's fetch: no step follows it
        ["epoch.drain", 950, 45], ["step.block", 955, 35],
    ]
    devices = {"0": {"steps": steps, "ops": ops}}
    if second_device:  # the same steps, nothing else in boundary 1
        devices["1"] = {"steps": steps,
                        "ops": [o for o in ops if o[0] != "convert.3"]}
    return {"devices": devices, "host": host}


def test_boundaries_split_a_gaps_idle_to_the_nanosecond():
    out = profile_mod.boundaries(_three_epochs())
    assert out["boundaries"] == 2 and out["devices"] == 1
    first, second = out["each"]
    ns = 1e-6  # the report is in ms
    assert first["gap_ms"] == pytest.approx(100 * ns)
    assert first["idle_ms"] == pytest.approx(90 * ns)  # less convert.3
    assert {k: round(v / ns) for k, v in first["split_ms"].items()} == {
        "step.block": 30, "step.infeed.put": 21, "step.host.produce": 14,
        "epoch.turn": 10, "step.infeed.wait": 6, "step.dispatch": 4,
        "epoch.fill": 3, "(no span)": 2}
    assert second["gap_ms"] == second["idle_ms"] == pytest.approx(160 * ns)
    assert {k: round(v / ns) for k, v in second["split_ms"].items()} == {
        "step.infeed.put": 64, "step.block": 29, "epoch.turn": 20,
        "step.host.produce": 16, "epoch.drain": 11, "step.dispatch": 10,
        "step.infeed.wait": 7, "epoch.fill": 3}
    for b in out["each"]:
        assert sum(b["split_ms"].values()) == pytest.approx(b["idle_ms"])
    assert out["gap_ms"] == {"median": pytest.approx(130 * ns),
                             "max": pytest.approx(160 * ns)}
    assert out["idle_ms"] == {"median": pytest.approx(125 * ns),
                              "max": pytest.approx(160 * ns),
                              "sum": pytest.approx(250 * ns)}
    assert list(out["idle_split_ms"])[0] == "step.infeed.put"
    assert out["idle_split_ms"]["step.infeed.put"] == {
        "median": pytest.approx(42.5 * ns), "max": pytest.approx(64 * ns)}
    assert out["idle_split_ms"]["epoch.drain"]["median"] == pytest.approx(
        5.5 * ns)


def test_boundaries_keep_the_windows_edges_and_the_steps_holes_apart():
    """``bench.window`` holds every step: it is the window, not a span a
    gap is named after.  The idle of the whole window is the boundaries'
    plus the two edges' plus what is inside and between the steps."""
    out = profile_mod.boundaries(_three_epochs())
    ns = 1e-6
    assert "bench.window" not in out["idle_split_ms"]
    assert out["edges_ms"] == {
        "open": {"gap": pytest.approx(60 * ns), "idle": pytest.approx(60 * ns)},
        "close": {"gap": pytest.approx(40 * ns),
                  "idle": pytest.approx(40 * ns)}}
    assert out["in_steps_idle_ms"] == pytest.approx(10 * ns)  # [540, 550)
    busy = 5 * 100 + 90 + 10
    assert (out["idle_ms"]["sum"] + out["edges_ms"]["open"]["idle"]
            + out["edges_ms"]["close"]["idle"] + out["in_steps_idle_ms"]
            ) == pytest.approx((960 - busy) * ns)
    # without a span around the steps the window is the events' own extent
    bare = _three_epochs()
    bare["host"] = [h for h in bare["host"] if h[0] != "bench.window"]
    out = profile_mod.boundaries(bare)
    assert out["edges_ms"]["open"]["gap"] == pytest.approx(55 * ns)
    assert out["edges_ms"]["close"]["gap"] == pytest.approx(35 * ns)


def test_boundaries_tell_an_epochs_first_batch_from_the_others():
    out = profile_mod.boundaries(_three_epochs())
    ns = 1e-6
    assert out["first_ms"] == {
        "step.host.produce": {"median": pytest.approx(15 * ns),
                              "max": pytest.approx(16 * ns)},
        "step.infeed.put": {"median": pytest.approx(42.5 * ns),
                            "max": pytest.approx(64 * ns)}}
    # the window's own first batch (before the first step) is neither
    assert out["steady_ms"] == {"step.host.produce": pytest.approx(4 * ns),
                                "step.infeed.put": pytest.approx(2 * ns)}


def test_boundaries_are_per_device_then_the_median_over_devices():
    out = profile_mod.boundaries(_three_epochs(second_device=True))
    ns = 1e-6
    assert out["devices"] == 2 and out["boundaries"] == 2
    # device 0 idles 90 and 160, device 1 100 and 160
    assert out["idle_ms"]["median"] == pytest.approx((125 + 130) / 2 * ns)
    assert out["idle_ms"]["sum"] == pytest.approx((250 + 260) / 2 * ns)
    assert out["each"][0]["idle_ms"] == pytest.approx(90 * ns)


def test_a_capture_of_one_epoch_has_no_boundary():
    one = _three_epochs()
    one["devices"]["0"]["steps"] = one["devices"]["0"]["steps"][:2]
    one["devices"]["0"]["ops"] = [o for o in one["devices"]["0"]["ops"]
                                  if o[2] < 300]
    assert profile_mod.boundaries(one) == {}
    # nor has one whose gaps hold no fetch's end, or no step at all
    no_block = _three_epochs()
    no_block["host"] = [h for h in no_block["host"] if h[0] != "step.block"]
    assert profile_mod.boundaries(no_block) == {}
    assert profile_mod.boundaries({"devices": {}, "host": []}) == {}
    assert profile_mod.boundaries({"devices": {"0": {"steps": [], "ops": []}},
                                   "host": [["step.block", 0, 9]]}) == {}


def test_recorded_boundaries_of_three_epochs_on_the_chip():
    """What the chip recorded (a traced run under ``--obs``): two
    boundaries, each one's idle shared out whole, ``(no span)`` a hundredth
    of it, and the two populations ``infeed_put_ms`` averages told apart:
    an epoch's first placement 14 and 128 ms, the others 1.4."""
    with gzip.open(BOUNDARY_FIXTURE, "rt") as f:
        recorded = json.load(f)
    assert "seed 3400000102" in recorded["origin"]
    out = profile_mod.boundaries(recorded)
    assert out["boundaries"] == 2 and out["devices"] == 1
    assert [round(b["idle_ms"], 3) for b in out["each"]] == [39.646, 162.648]
    for b in out["each"]:
        assert b["gap_ms"] == b["idle_ms"]  # nothing else ran in the gap
        assert sum(b["split_ms"].values()) == pytest.approx(b["idle_ms"])
        assert b["split_ms"]["(no span)"] < 0.02 * b["idle_ms"]
        # the drain outside the fetch and the fill outside the wait are
        # the consumer thread's own work: under a millisecond
        assert b["split_ms"]["epoch.drain"] < 0.5
        assert b["split_ms"]["epoch.fill"] < 1.0
    assert list(out["idle_split_ms"])[:3] == [
        "step.infeed.put", "step.host.produce", "step.block"]
    assert out["idle_ms"]["sum"] == pytest.approx(202.293825)
    first, steady = out["first_ms"], out["steady_ms"]
    assert first["step.infeed.put"]["max"] == pytest.approx(128.354575)
    assert first["step.infeed.put"]["median"] > 50 * steady["step.infeed.put"]
    assert first["step.host.produce"]["median"] > 100 * steady[
        "step.host.produce"]
    # the window: 48 steps, two boundaries, two edges, the launch gaps
    window = next(h for h in recorded["host"] if h[0] == "bench.window")
    steps = recorded["devices"]["0"]["steps"]
    assert len(steps) == 48
    assert (sum(d for _, d in steps) / 1e6 + out["idle_ms"]["sum"]
            + out["edges_ms"]["open"]["idle"]
            + out["edges_ms"]["close"]["idle"] + out["in_steps_idle_ms"]
            ) == pytest.approx(window[2] / 1e6)
    # the same lists reduce as a capture's do
    assert reduce_phases(recorded)["host_spans"]["epoch.fill"]["count"] == 3
