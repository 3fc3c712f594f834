"""``train_step_roofline`` — layer: kernels XLA gather scatter-add optimizer fusions.  Unit ``%``, source
``device_trace``; should move ``train_rows_per_s``.

The least time the chip could take for one step — max(FLOPs / peak
FLOP/s, bytes / peak bytes/s), both from ``benchmark/shapes.py`` at the
batch rows and table rows ONE chip handles — over ``step_device_ms``.
Which bound binds is printed as a note; for the tabular families it is
bytes.  No Pallas kernel is on a first cell's path: the step is XLA's own
gather, scatter-add and optimizer fusions.
"""

LAYER = "kernels XLA gather scatter-add optimizer fusions"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "train_rows_per_s"

import json

from benchmark import shapes, xplane


def per_chip(config: dict, traffic: dict, chips: int):
    """(model_config, batch) as one chip sees them: the batch over the
    mesh's data axis, the table rows over its model axis."""
    mc = json.loads(json.dumps(config["model_config"]))
    axes = dict(a.split(":") for a in (config.get("mesh") or "").split(",")
                if ":" in a)
    model = int(axes.get("model", 1))
    data = int(axes.get("data", -1))
    data = chips // model if data < 0 else data
    p = mc["train"]["params"]
    for key in ("EmbeddingHashSize", "CrossHashSize"):
        if key in p:
            p[key] = int(p[key]) // model
    return mc, int(traffic["batch"]) // max(1, data)


def read(r):
    if r["peaks"] is None or not r["step_pattern"]:
        return None
    ms = xplane.step_device_ms(r["trace"], r["step_pattern"],
                               r["window_ns"])
    if not ms:
        return None
    d = r["config"]["data"]
    mc, batch = per_chip(r["config"], r["traffic"], int(r["cell"]["chips"]))
    nf = int(d["numeric"]) + int(d["categorical"])
    least = shapes.roofline(shapes.train_step_flops(mc, nf, batch),
                            shapes.train_step_bytes(mc, nf, batch),
                            r["peaks"])
    print(json.dumps({"note": {"train_step_roofline": least}}), flush=True)
    return 100.0 * least["seconds"] * 1e3 / ms
