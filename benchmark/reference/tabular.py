"""Plain references for the tabular families: DNN and Wide&Deep with
hashed embeddings and a hashed cross.

Straightforward float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``: forward, MSE loss, gradient
by ``jax.grad`` of this file's own forward, and Adam written out.  Nothing
is imported from the program: the layer equations follow
``models/dnn.py`` / ``wide_deep.py`` / ``embeddings.py`` /
``factory.EmbeddingAugmented`` as read, and the bucket hash is
``ops/hashing.py``'s definition re-implemented in numpy.  Parameters come
in as the program's own nested dict of arrays (names are the only thing
shared), so a wrong bucket, a dropped wide or cross term or a wrong Adam
moment in the program shows as a different loss.

Departures from Cheng et al. 2016, as the configuration files note: MSE
on the sigmoid output (the trainer's default loss) instead of logistic
loss, Adam for both parts instead of FTRL (wide) + AdaGrad (deep), one
shared hashed table instead of a vocabulary per feature.
"""

from __future__ import annotations

import numpy as np

HASH_MULT = 2654435761
HASH_MULT2 = 40503
COLUMN_SALT = 0x9E3779B9
_M32 = np.uint64(0xFFFFFFFF)


def _mix(h: np.ndarray) -> np.ndarray:
    """uint32 finalizer, computed in uint64 and masked back to 32 bits."""
    h = (h * np.uint64(HASH_MULT)) & _M32
    h = h ^ (h >> np.uint64(16))
    return (h * np.uint64(HASH_MULT2)) & _M32


def salted_bucket_ids(x: np.ndarray, hash_size: int) -> np.ndarray:
    """(B, C) float32 codes -> (B, C) bucket ids: the float's bits, xor a
    per-column salt, multiplicative mix, modulo the table size."""
    bits = np.ascontiguousarray(x, np.float32).view(np.uint32).astype(
        np.uint64)
    cols = np.arange(x.shape[1], dtype=np.uint64)[None, :]
    salted = bits ^ ((cols * np.uint64(COLUMN_SALT)) & _M32)
    return (_mix(salted) % np.uint64(hash_size)).astype(np.int32)


def crossed_bucket_ids(x: np.ndarray, hash_size: int) -> np.ndarray:
    """(B, C) float32 codes -> (B,) one joint id per row."""
    bits = np.ascontiguousarray(x, np.float32).view(np.uint32).astype(
        np.uint64)
    h = np.zeros(x.shape[0], np.uint64)
    for c in range(x.shape[1]):
        h = ((h ^ bits[:, c]) * np.uint64(HASH_MULT)) & _M32
        h = h ^ (h >> np.uint64(13))
    return (h % np.uint64(hash_size)).astype(np.int32)


def positions(column_nums, feature_columns) -> list[int]:
    pos = {c: i for i, c in enumerate(feature_columns)}
    return [pos[c] for c in column_nums if c in pos]


def ids_for(params_cfg: dict, feature_columns, x: np.ndarray) -> dict:
    """The integer inputs of a batch, hashed on the host in numpy."""
    out = {}
    emb_cols = params_cfg.get("EmbeddingColumnNums") or []
    if emb_cols and int(params_cfg.get("EmbeddingHashSize", 0)) > 0:
        out["emb"] = salted_bucket_ids(
            x[:, positions(emb_cols, feature_columns)],
            int(params_cfg["EmbeddingHashSize"]))
    wide_cols = params_cfg.get("WideColumnNums") or []
    if (params_cfg.get("ModelType", "dnn").lower() == "wide_deep"
            and wide_cols and int(params_cfg.get("CrossHashSize", 0)) > 0):
        out["cross"] = crossed_bucket_ids(
            x[:, positions(wide_cols, feature_columns)],
            int(params_cfg["CrossHashSize"]))
    return out


def _act(name: str, x):
    import jax
    import jax.numpy as jnp

    name = (name or "").lower()
    if name == "relu":
        return jnp.maximum(x, 0.0)
    if name == "tanh":
        return jnp.tanh(x)
    if name == "sigmoid":
        return jax.nn.sigmoid(x)
    return jnp.where(x >= 0, x, 0.01 * x)  # leaky relu, the fallback


def _tower(p: dict, acts, x):
    for i, act in enumerate(acts):
        layer = p[f"hidden_layer{i}"]
        x = _act(act, x @ layer["kernel"] + layer["bias"])
    return x


def forward(params: dict, params_cfg: dict, feature_columns, x, ids: dict):
    """Scores (B, 1) in float32.  ``x`` (B, F) float32, ``ids`` from
    :func:`ids_for`."""
    import jax
    import jax.numpy as jnp

    n = int(params_cfg["NumHiddenLayers"])
    acts = list(params_cfg["ActivationFunc"])[:n]
    wide_deep = params_cfg.get("ModelType", "dnn").lower() == "wide_deep"
    base = params.get("base", params)
    h = x
    if "emb" in ids:
        table = params["hashed_columns"]["table"]
        emb = table[ids["emb"].reshape(-1)].reshape(x.shape[0], -1)
        h = jnp.concatenate([x, emb], axis=-1)
    if not wide_deep:
        out = base["shifu_output_0"]
        logit = _tower(base["trunk"], acts, h) @ out["kernel"] + out["bias"]
        return jax.nn.sigmoid(logit)
    out = base["deep_logit"]
    logit = _tower(base["deep"], acts, h) @ out["kernel"] + out["bias"]
    wide_pos = positions(params_cfg.get("WideColumnNums") or [],
                         feature_columns)
    wide_x = h[:, np.asarray(wide_pos)] if wide_pos else h
    logit = logit + wide_x @ base["wide_logit"]["kernel"]
    if "cross" in ids:
        logit = logit + base["wide_cross"]["table"][ids["cross"]]
    return jax.nn.sigmoid(logit)


def mse(pred, y, w):
    """sum(w (y - p)^2) over the COUNT of nonzero weights (TF1's
    SUM_BY_NONZERO_WEIGHTS, which the reference trainer used) — the
    trainer's default loss (``ops/losses.py`` ``mse``)."""
    import jax.numpy as jnp

    nonzero = jnp.sum((w != 0.0).astype(jnp.float32))
    return jnp.sum(w * (pred - y) ** 2) / jnp.maximum(nonzero, 1.0)


def make_adam_step(params_cfg: dict, feature_columns,
                   precision: str = "highest"):
    """(params, mu, nu, count, batch, ids) -> (params, mu, nu, count,
    loss): one Adam step, b1 0.9, b2 0.999, eps 1e-8, bias-corrected.
    ``precision`` is the matmul precision: ``highest`` is the truth;
    ``default`` is what a configuration that states float32 at the TPU's
    default precision asks of the program."""
    import jax
    import jax.numpy as jnp

    lr = float(params_cfg["LearningRate"])
    b1, b2, eps = 0.9, 0.999, 1e-8

    def loss_of(params, batch, ids):
        pred = forward(params, params_cfg, feature_columns, batch["x"], ids)
        return mse(pred, batch["y"], batch["w"])

    def step(params, mu, nu, count, batch, ids):
        with jax.default_matmul_precision(precision):
            loss, g = jax.value_and_grad(loss_of)(params, batch, ids)
        count = count + 1
        mu = jax.tree.map(lambda m, gi: b1 * m + (1 - b1) * gi, mu, g)
        nu = jax.tree.map(lambda v, gi: b2 * v + (1 - b2) * gi * gi, nu, g)
        c1 = 1 - b1 ** count.astype(jnp.float32)
        c2 = 1 - b2 ** count.astype(jnp.float32)
        params = jax.tree.map(
            lambda p, m, v: p - lr * (m / c1) / (jnp.sqrt(v / c2) + eps),
            params, mu, nu)
        return params, mu, nu, count, loss

    return step


def reference_steps(params, params_cfg: dict, feature_columns,
                    batches: list[dict], precision: str = "highest"):
    """(losses, parameters after) of the first ``len(batches)`` Adam steps
    from ``params`` — a copy the caller owns and gives up: the arrays may
    carry a ``NamedSharding``, which the jitted step keeps.  The moments
    are freed on return."""
    import jax
    import jax.numpy as jnp

    step = jax.jit(make_adam_step(params_cfg, feature_columns, precision),
                   donate_argnums=(0, 1, 2))
    mu = jax.tree.map(jnp.zeros_like, params)
    nu = jax.tree.map(jnp.zeros_like, params)
    count = jnp.zeros((), jnp.int32)
    losses = []
    for batch in batches:
        ids = ids_for(params_cfg, feature_columns, batch["x"])
        params, mu, nu, count, loss = step(params, mu, nu, count, batch, ids)
        losses.append(float(loss))
    del mu, nu
    return losses, params

