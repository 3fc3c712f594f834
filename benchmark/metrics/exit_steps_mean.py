"""``exit_steps_mean`` — layer: models models/ ops/.  Unit ``steps``, source
``program_counter``; should move ``train_rows_per_s``.

The pass a token is expected to leave at under the exit gates' distribution,
``sum_t t p_t``, averaged over the step's positions (the step's
``exit_steps_mean`` counter, ``models/hybrid_lm.py``
``HybridLM.loop_losses``), then over the last epoch's steps.  Lower is what
the gates would make a served token cost below a threshold of 1 (here every
pass runs: ``early_exit_threshold`` 1).  ``None`` without the counter or in
a cell of another kind.
"""

LAYER = "models models/ ops/"
UNIT = "steps"
SOURCE = "program_counter"
MOVES = "train_rows_per_s"

from benchmark.looped_lm_readings import counter_mean


def read(r):
    return counter_mean(r, "exit_steps_mean")
