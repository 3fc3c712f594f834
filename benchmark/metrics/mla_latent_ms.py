"""``mla_latent_ms`` — layer: models models/ ops/.  Unit ``ms``, source
``device_trace``; should move ``train_rows_per_s``.

Device ms a step inside ``attn.latent`` and ``attn.expand``, forward +
backward summed (the backward's recomputed forward included): the main
blocks' projections onto the query and key/value latents with the
RMSNorm on each, and the latents' projections up to 20 heads (``W_qa``,
``W_kva``; ``W_qb``, ``W_kvb``).  The multi-token prediction module's
own are under ``mtp.block`` (``mtp_ms``).  From
``obs.profile.phases`` on the run's own capture, handed on by the plane;
``None`` on a reading without the phase or of another configuration's
kind.
"""

LAYER = "models models/ ops/"
UNIT = "ms"
SOURCE = "device_trace"
MOVES = "train_rows_per_s"

from benchmark.mla_lm_readings import mla_phase_ms


def read(r):
    return mla_phase_ms(r, "attn.latent", "attn.expand")
